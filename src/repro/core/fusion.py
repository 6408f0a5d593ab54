"""The fusion layer: Schedule stages interleaved with Pallas kernel
execution (DESIGN.md §14).

The paper's core speed trick is that communication is not a separate
phase — remote stores issue from inside the compute loop (§4, and the
hybrid-model companion paper's "device kernels issue SHMEM ops").  Up to
PR 5 this repo alternated jitted compute with Schedule-layer collectives;
this module interleaves them, with two flagship fused paths:

ring_attention
    Sequence-sharded attention.  Each ring step's KV-block rotation is a
    CommPattern issued via `put_nbi` on a DEDICATED context (its own
    pending-op queue, so the rotation cannot be drained by unrelated
    traffic) while the flash online-softmax machinery consumes the block
    that arrived in the previous step.  `fence()` orders the puts per ring
    neighbor; `quiet(fk, fv, fp)` completes exactly this step's rotation
    before the next step consumes it — the double-buffer slot protocol.

fused_rs_adam
    Ring reduce-scatter whose FINAL combine lands inside the k-ary
    combine+AdamW kernel (kernels/fused_update.py): the fully-reduced
    gradient chunk is consumed by the optimizer in the same kernel pass
    and the full gradient is never materialized.  Only the updated PARAM
    chunk is allgathered — at param dtype, so vs the unfused
    reduce-scatter + f32 allgather the wire bytes drop from 2B to
    B * (1 + itemsize/4).

choose_attention / choose_grad_rs price the fused variants against the
monolithic ones (abmodel.modeled_overlapped_time) and consult the
measured-performance tuner first, the same contract as choose_algorithm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import abmodel
from . import collectives as coll
from . import netops
from .collectives import allgather_schedule, reduce_scatter_schedule
from .netops import NetOps, SimNetOps
from .pattern import ring_pattern
from ..kernels import fused_update as _fu
from ..kernels import ring_attention as _ra


# ---------------------------------------------------------------------------
# ring attention
# ---------------------------------------------------------------------------

def ring_attention(ctx, q, k, v, q_pos, k_pos, *, causal: bool = True,
                   window: int | None = None, softcap: float | None = None,
                   sm_scale: float | None = None, use_pallas: bool = False,
                   bq: int = _ra.DEFAULT_BQ, bk: int = _ra.DEFAULT_BK,
                   interpret: bool | None = None, out_dtype=None):
    """Sequence-sharded attention over `ctx`'s PE space.

    Each PE holds its query shard q (B, Hq, Lq_shard, D) with global
    positions q_pos (Lq_shard,), and its KV shard k/v (B, Hkv, Lk_shard,
    D) with global positions k_pos (Lk_shard,; -1 marks padded slots).
    The KV shard walks the ring: at step s the NEXT block is issued with
    put_nbi on a private context while the flash partials of the CURRENT
    block are computed, then quiet() completes the rotation — comm hidden
    behind compute whenever the NoC keeps up.  Output matches monolithic
    flash attention over the gathered sequence to f32 allclose (identical
    per-block arithmetic; merge order differs per PE, which online
    softmax absorbs up to rounding)."""
    net = ctx.net
    n = net.n_pes
    kw = dict(causal=causal, window=window, softcap=softcap,
              sm_scale=sm_scale, use_pallas=use_pallas, bq=bq, bk=bk,
              interpret=interpret)

    def partials(q_, k_, v_, qp_, kp_):
        return _ra.attn_block_partials(q_, k_, v_, qp_, kp_, **kw)

    out_dtype = q.dtype if out_dtype is None else out_dtype
    if n == 1:
        return _ra.finalize(
            coll._lmap(net, partials, q, k, v, q_pos, k_pos), out_dtype)

    c = ctx.ctx_create()                 # private queue: DESIGN.md §14
    ring = ring_pattern(n)               # PE i -> (i+1) % n, every step
    cur_k, cur_v, cur_kp = k, v, k_pos
    state = None
    for s in range(n):
        last = s == n - 1
        if not last:
            # issue the rotation BEFORE computing on the current block:
            # the 'DMA engine' flies while the kernel runs
            fk = c.put_nbi(cur_k, ring)
            fv = c.put_nbi(cur_v, ring)
            fp = c.put_nbi(cur_kp, ring)
            c.fence()                    # per-neighbor ordering of k/v/pos
        part = coll._lmap(net, partials, q, cur_k, cur_v, q_pos, cur_kp)
        state = part if state is None else _ra.merge_partials(state, part)
        if not last:
            # double-buffer swap: completion of THIS step's rotation is
            # the next step's front buffer
            cur_k, cur_v, cur_kp = c.quiet(fk, fv, fp)
    return _ra.finalize(state, out_dtype)


# ---------------------------------------------------------------------------
# fused reduce-scatter -> AdamW update
# ---------------------------------------------------------------------------

def fused_rs_adam(net: NetOps, g_buf, p_buf, m, v, wd_mask, c1, c2, *,
                  lr: float, b1: float, b2: float, eps: float,
                  wd_coef: float, scale: float = 1.0, out_dtype=None,
                  team=None, use_pallas: bool = False,
                  interpret: bool | None = None, profile=None):
    """Ring reduce-scatter of the flat f32 gradient bucket `g_buf` with
    the final combine fused into the AdamW update of this PE's owned
    param chunk.  `p_buf` is the matching flat f32 param bucket
    (replicated); `m`/`v` are this PE's OWNED moment chunks, shape
    (ceil(size/n),) — they never ride the ring.  wd_mask (full bucket
    length) is nonzero where weight decay applies; c1/c2 the traced
    1-beta**t scalars; `scale` the grad-mean divisor.

    Returns ``(new_p_chunk, new_m, new_v, info)``: the updated owned
    param chunk (cast to `out_dtype`) plus the reduce-scatter `info`
    handle — allgather it with ``coll.allgather_unpad(net, new_p_chunk,
    info, team=team)`` to rebuild the full updated bucket.  Arithmetic is
    bitwise equal to grad_sync(mean)-then-apply_updates on f32 moments
    (kernels/fused_update.py documents why)."""
    out_dtype = p_buf.dtype if out_dtype is None else out_dtype
    fn = coll.OPS["sum"]
    local, incoming, info, mask = coll._reduce_scatter_parts(
        net, g_buf, fn, team=team)
    orig_shape, size, chunk, own_idx = info
    if profile is not None:
        nbytes = coll._payload_bytes(net, g_buf)
        profile.note(algorithm="fused_rs_adam",
                     schedule=reduce_scatter_schedule(net.n_pes, nbytes),
                     collective="grad_sync", nbytes=nbytes,
                     n_pes=net.n_pes)
    n = net.n_pes
    padded = chunk * n

    def flatpad(x):
        f = x.reshape(-1)
        return jnp.pad(f, (0, padded - f.size))

    p_pad = coll._lmap(net, flatpad, p_buf)
    wd_pad = jnp.pad(wd_mask.reshape(-1).astype(jnp.int8),
                     (0, padded - size))
    if isinstance(net, SimNetOps):
        wd_pad = jnp.broadcast_to(wd_pad, (n, padded))
    p_chunk = netops.dyn_slice_block(net, p_pad, own_idx, chunk, axis=-1)
    wd_chunk = netops.dyn_slice_block(net, wd_pad, own_idx, chunk, axis=-1)
    g_parts = [local] if incoming is None else [local, incoming]

    def update(gs, p_, m_, v_, w_):
        return _fu.fused_adam_update(
            gs, p_, m_, v_, w_, c1, c2, lr=lr, b1=b1, b2=b2, eps=eps,
            wd_coef=wd_coef, scale=scale, out_dtype=out_dtype,
            use_pallas=use_pallas, interpret=interpret)

    if isinstance(net, SimNetOps):
        new_p, new_m, new_v = jax.vmap(
            lambda *a: update(list(a[:len(g_parts)]), *a[len(g_parts):]))(
            *g_parts, p_chunk, m, v, wd_chunk)
    else:
        new_p, new_m, new_v = update(g_parts, p_chunk, m, v, wd_chunk)
    new_p = coll._mask_out(net, mask, new_p, keep=p_chunk.astype(out_dtype))
    return new_p, new_m, new_v, info


# ---------------------------------------------------------------------------
# pricing: the fused variants as selectable algorithms
# ---------------------------------------------------------------------------

def choose_attention(n: int, kv_block_bytes: float, block_compute_s: float,
                     *, topo=None, link=None, tuner=None
                     ) -> tuple[str, dict]:
    """"ring" vs "mono" for sequence-sharded attention over n PEs.

    kv_block_bytes: bytes of ONE PE's K+V(+pos) shard — what each ring
    step moves; block_compute_s: flash time of q against one block.  Mono
    allgathers the KV sequence first and computes monolithically; ring
    overlaps each rotation with one block's compute
    (abmodel.modeled_overlapped_time).  A measured-best tuner verdict for
    collective "attention" wins over the analytic model."""
    if n <= 1:
        return "mono", {"ring": 0.0, "mono": 0.0}
    total = kv_block_bytes * n
    ring_stages = allgather_schedule(n, total).cost(topo)
    t_ring = abmodel.modeled_overlapped_time(
        ring_stages, block_compute_s,
        link if link is not None else abmodel.ICI_V5E)
    t_mono = (allgather_schedule(n, total).time(topo, link)
              + n * block_compute_s)
    times = {"ring": t_ring, "mono": t_mono}
    if tuner is not None:
        got = tuner.algorithm("attention", n, total, topo=topo,
                              candidates=("ring", "mono"))
        if got in times:
            return got, times
    return ("ring" if t_ring <= t_mono else "mono"), times


def choose_grad_rs(n: int, bucket_bytes: float, param_itemsize: int = 4,
                   *, topo=None, link=None, tuner=None) -> tuple[str, dict]:
    """"fused" vs "bucketed" for the gradient sync of one f32 bucket.

    Both price the same ring reduce-scatter; the fused path allgathers
    the updated PARAM chunk at `param_itemsize` instead of the f32
    gradient — strictly fewer wire bytes for sub-f32 params, equal for
    f32 (where fusing still saves the separate optimizer kernel pass, so
    ties go to "fused").  Tuner verdicts for collective "grad_sync" win."""
    if n <= 1:
        return "bucketed", {"fused": 0.0, "bucketed": 0.0}
    t_rs = reduce_scatter_schedule(n, bucket_bytes).time(topo, link)
    t_ag_f32 = allgather_schedule(n, bucket_bytes).time(topo, link)
    t_ag_out = allgather_schedule(
        n, bucket_bytes * param_itemsize / 4.0).time(topo, link)
    times = {"fused": t_rs + t_ag_out, "bucketed": t_rs + t_ag_f32}
    if tuner is not None:
        got = tuner.algorithm("grad_sync", n, bucket_bytes, topo=topo,
                              candidates=("fused", "bucketed"))
        if got in times:
            return got, times
    return ("fused" if times["fused"] <= times["bucketed"]
            else "bucketed"), times
