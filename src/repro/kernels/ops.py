"""jit'd public wrappers around the Pallas kernels.

Each wrapper:
  * handles the unaligned edge case by padding to tile multiples (the TPU
    analogue of the paper's unaligned-memory specialization in the put
    copy loop) and un-padding the result;
  * dispatches kernel vs. pure-jnp reference via `use_pallas` — off a
    TPU, kernels default to interpret mode for validation, while
    the models/dry-run default to the XLA reference path (DESIGN.md);
  * makes attention differentiable with a custom VJP whose backward
    recomputes through the reference (flash-style remat).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import paged_decode as _pd
from . import put_copy as _pc
from . import reduce_combine as _rc
from . import ref
from . import ssd_scan as _ssd


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# Edge-padding plan + jitted-executor cache, per (kernel, shapes, dtype,
# block, interpret) — the gather_arrays_device pattern from PR 4: pad
# shapes were being recomputed and the pallas wrapper re-traced on EVERY
# eager call.  One cached jax.jit closure per key makes the hot path
# re-trace-free (XLA's trace cache keys on the function object, so the
# closure must be the same object across calls).  _PLAN_STATS is test
# observability (tests/test_fused.py asserts the hot path hits).
_EXEC_CACHE: dict = {}
_PLAN_STATS = {"hits": 0, "misses": 0}


def _cached_exec(key, build):
    fn = _EXEC_CACHE.get(key)
    if fn is None:
        _PLAN_STATS["misses"] += 1
        fn = _EXEC_CACHE[key] = build()
    else:
        _PLAN_STATS["hits"] += 1
    return fn


def _clear_exec_cache():
    _EXEC_CACHE.clear()
    _PLAN_STATS["hits"] = _PLAN_STATS["misses"] = 0


def _pad2d(x, br, bc):
    r, c = x.shape
    pr = (-r) % br
    pc_ = (-c) % bc
    if pr or pc_:
        x = jnp.pad(x, ((0, pr), (0, pc_)))
    return x, (r, c)


def put_copy(src, *, use_pallas: bool = True, interpret: bool | None = None):
    """The paper's optimized shmem_put byte-mover (identity copy)."""
    if not use_pallas:
        return ref.put_copy_ref(src)
    interpret = _default_interpret() if interpret is None else interpret
    key = ("put_copy", src.shape, jnp.dtype(src.dtype).name, interpret)

    def build():
        @jax.jit
        def run(x):
            x2 = x.reshape(-1, x.shape[-1]) if x.ndim != 2 else x
            padded, (r, c) = _pad2d(x2, _pc.BLOCK_ROWS, _pc.BLOCK_COLS)
            out = _pc.put_copy_2d(padded, interpret=interpret)[:r, :c]
            return out.reshape(x.shape)
        return run

    return _cached_exec(key, build)(src)


def dma_copy(src, dst, *, src_origin, dst_origin, region,
             use_pallas: bool = True, interpret: bool | None = None):
    if not use_pallas:
        return ref.dma_copy_ref(src, dst, src_origin=src_origin,
                                dst_origin=dst_origin, region=region)
    interpret = _default_interpret() if interpret is None else interpret
    return _pc.dma_copy_2d(src, dst, src_origin=src_origin,
                           dst_origin=dst_origin, region=region,
                           interpret=interpret)


def reduce_combine(bufs, op: str = "sum", *, use_pallas: bool = True,
                   interpret: bool | None = None):
    if not use_pallas:
        return ref.reduce_combine_ref(bufs, op)
    interpret = _default_interpret() if interpret is None else interpret
    shape = bufs[0].shape
    key = ("reduce_combine", len(bufs), op, shape,
           jnp.dtype(bufs[0].dtype).name, interpret)

    def build():
        @jax.jit
        def run(*bs):
            flat = [b.reshape(-1, b.shape[-1]) if b.ndim != 2 else b
                    for b in bs]
            padded = []
            for f in flat:
                p, (r, c) = _pad2d(f, _rc.BLOCK_ROWS, _rc.BLOCK_COLS)
                padded.append(p)
            out = _rc.reduce_combine_2d(padded, op,
                                        interpret=interpret)[:r, :c]
            return out.reshape(shape)
        return run

    return _cached_exec(key, build)(*bufs)


# ---------------------------------------------------------------------------
# attention: pallas forward, reference-recompute backward
# ---------------------------------------------------------------------------

def _pad_seq(x, axis, mult):
    n = x.shape[axis]
    p = (-n) % mult
    if p == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, p)
    return jnp.pad(x, pads)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _attention(q, k, v, causal, window, softcap, sm_scale, bq, bk, interpret):
    lq, lk = q.shape[2], k.shape[2]
    qp = _pad_seq(q, 2, bq)
    kp = _pad_seq(k, 2, bk)
    vp = _pad_seq(v, 2, bk)
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              softcap=softcap, sm_scale=sm_scale, bq=bq,
                              bk=bk, lk_valid=lk, interpret=interpret)
    return out[:, :, :lq]


def _attention_fwd(q, k, v, causal, window, softcap, sm_scale, bq, bk,
                   interpret):
    out = _attention(q, k, v, causal, window, softcap, sm_scale, bq, bk,
                     interpret)
    return out, (q, k, v)


def _attention_bwd(causal, window, softcap, sm_scale, bq, bk, interpret,
                   res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention_ref(
            q_, k_, v_, causal=causal, window=window, softcap=softcap,
            sm_scale=sm_scale), q, k, v)
    return vjp(g)


_attention.defvjp(_attention_fwd, _attention_bwd)


BLOCKWISE_THRESHOLD = 8192


def attention(q, k, v, *, causal=True, window=None, softcap=None,
              sm_scale=None, use_pallas: bool = False, bq: int = _fa.DEFAULT_BQ,
              bk: int = _fa.DEFAULT_BK, interpret: bool | None = None,
              blockwise_unroll: bool = False):
    """Public attention op.  use_pallas=True runs the flash kernel; the
    XLA path uses the dense reference for short sequences and the
    blockwise-scan flash equivalent beyond BLOCKWISE_THRESHOLD (O(L*blk)
    memory — required for 32k prefill)."""
    if not use_pallas:
        if k.shape[2] >= BLOCKWISE_THRESHOLD:
            return ref.attention_blockwise(
                q, k, v, causal=causal, window=window, softcap=softcap,
                sm_scale=sm_scale,
                block=4096 if blockwise_unroll else 1024,
                unroll=blockwise_unroll)
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap, sm_scale=sm_scale)
    interpret = _default_interpret() if interpret is None else interpret
    return _attention(q, k, v, causal, window, softcap, sm_scale, bq, bk,
                      interpret)


def paged_decode_attention(q, k_new, v_new, k_pool, v_pool, layer,
                           page_table, positions, *, page_size: int,
                           window=None, softcap=None,
                           interpret: bool | None = None,
                           v_lanes: int | None = None):
    """Paged decode attention (`paged_decode.py`): one new row per slot
    against the pages it has filled, read in place from the stacked pool;
    `v_lanes` selects the latent mode (MLA).  Forward-only (serving);
    interpret mode off a TPU."""
    interpret = _default_interpret() if interpret is None else interpret
    return _pd.paged_decode_attention(
        q, k_new, v_new, k_pool, v_pool, layer, page_table, positions,
        page_size=page_size, window=window, softcap=softcap,
        interpret=interpret, v_lanes=v_lanes)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

def ssd(x, dt, a_log, b_mat, c_mat, h0=None, *, chunk: int = 128,
        use_pallas: bool = False, interpret: bool | None = None,
        unroll: bool = False):
    """SSD scan: (y, h_final).  Kernel path is forward-only (serving);
    training uses the chunked XLA reference, which is freely differentiable
    and runs the same math (ref.ssd_chunked_ref)."""
    length = x.shape[1]
    pad = (-length) % chunk
    if pad:
        x = _pad_seq(x, 1, chunk)
        dt = _pad_seq(dt, 1, chunk)
        b_mat = _pad_seq(b_mat, 1, chunk)
        c_mat = _pad_seq(c_mat, 1, chunk)
    if not use_pallas:
        y, h = ref.ssd_chunked_ref(x, dt, a_log, b_mat, c_mat, h0,
                                   chunk=chunk, unroll=unroll)
    else:
        interpret = _default_interpret() if interpret is None else interpret
        y, h = _ssd.ssd_scan(x, dt, a_log, b_mat, c_mat, h0, chunk=chunk,
                             interpret=interpret)
    return y[:, :length], h
