"""TP-aware model layers over the Comm substrate.

Megatron-style manual tensor parallelism inside shard_map: attention/SSM
heads and FFN hidden are sharded over the `model` axis; every layer ends
with one allreduce over `model` (shmem dissemination/ring or XLA psum —
the --comm switch).  KV projections are replicated over `model` when
n_kv_heads < tp (GQA groups), costing a few MB but avoiding fractional
shards.  MoE layers switch the model axis from TP to EP: tokens are
sequence-split over `model`, dispatched to expert owners with the paper's
pairwise `alltoall`, and gathered back (DESIGN.md §3).

All functions take local shards; collectives are explicit; autodiff
produces the reversed communication schedule automatically.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels import ops as kops
from ..kernels import ref as kref
from ..parallel.comm import Comm
from .config import ModelConfig

Params = dict


# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + w.astype(jnp.float32))
            ).astype(x.dtype)


def rope(x, positions, theta: float, inv_freq=None):
    """x: (..., L, H, D) with D even; positions: (..., L).  Rotate-half
    form; `inv_freq` (D/2,) replaces theta's plain frequencies."""
    d = x.shape[-1]
    half = d // 2
    freqs = (1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
             if inv_freq is None else inv_freq)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., L, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _dense(x, w, b=None):
    y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# embedding / LM head (vocab-sharded over `model`)
# ---------------------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig, tp: int) -> Params:
    v_local = -(-cfg.vocab // tp)
    scale = 1.0 / math.sqrt(cfg.d_model)
    p = {"table": jax.random.normal(key, (v_local, cfg.d_model),
                                    jnp.float32) * scale}
    if not cfg.tie_embeddings:
        p["head"] = jax.random.normal(
            jax.random.fold_in(key, 1), (cfg.d_model, v_local),
            jnp.float32) * scale
    return p


def embed(comm: Comm, cfg: ModelConfig, p: Params, tokens):
    """tokens: (B, L) global ids -> (B, L, d) replicated over model."""
    tp = comm.axis_size(comm.axes.model)
    v_local = p["table"].shape[0]
    base = comm.axis_index(comm.axes.model) * v_local
    local_ids = tokens - base
    ok = (local_ids >= 0) & (local_ids < v_local)
    emb = jnp.take(p["table"], jnp.clip(local_ids, 0, v_local - 1), axis=0)
    emb = jnp.where(ok[..., None], emb, 0.0)
    emb = comm.allreduce(emb, comm.axes.model)
    return emb.astype(cfg.dtype)


@jax.named_scope("lm_head")
def lm_logits(comm: Comm, cfg: ModelConfig, p: Params, x):
    w = p["table"].T if cfg.tie_embeddings else p["head"]
    return _dense(x, w.astype(cfg.logit_dtype))   # (B, L, V_local)


def sharded_xent(comm: Comm, cfg: ModelConfig, logits, targets):
    """Cross-entropy with vocab sharded over `model`: the logsumexp and the
    target-logit pick each need one small allreduce (max, then sum)."""
    v_local = logits.shape[-1]
    base = comm.axis_index(comm.axes.model) * v_local
    lg = logits.astype(jnp.float32)
    if cfg.final_softcap is not None:
        lg = cfg.final_softcap * jnp.tanh(lg / cfg.final_softcap)
    # stop-grad on the stabilizer: exact logsumexp gradient is preserved
    # and the max-allreduce needs no VJP (XLA pmax has none)
    m_loc = lax.stop_gradient(jnp.max(lg, -1))
    m = comm.allreduce(m_loc, comm.axes.model, "max")
    se = jnp.sum(jnp.exp(lg - m[..., None]), -1)
    se = comm.allreduce(se, comm.axes.model)
    lse = jnp.log(se) + m
    loc_t = targets - base
    ok = (loc_t >= 0) & (loc_t < v_local)
    tl = jnp.take_along_axis(
        lg, jnp.clip(loc_t, 0, v_local - 1)[..., None], -1)[..., 0]
    tl = jnp.where(ok, tl, 0.0)
    tl = comm.allreduce(tl, comm.axes.model)
    return lse - tl   # (B, L) token losses


# ---------------------------------------------------------------------------
# GQA attention (sharded heads; replicated KV proj when n_kv < tp)
# ---------------------------------------------------------------------------

def _gqa_dims(cfg: ModelConfig, tp: int):
    """Local head bookkeeping.  Head counts that don't divide tp are padded
    with 'ghost' q heads whose outputs are masked to zero (exact semantics,
    a sliver of wasted compute — e.g. qwen2's 14 heads on tp=16).  KV
    projections are stored replicated when n_kv < tp; each chip gathers the
    kv head(s) its q heads map to."""
    nq_local = -(-cfg.n_heads // tp)
    kv_repl = cfg.n_kv_heads < tp or cfg.n_heads % tp != 0
    nkv_store = cfg.n_kv_heads if kv_repl else cfg.n_kv_heads // tp
    return nq_local, nkv_store, kv_repl


def init_attention(key, cfg: ModelConfig, tp: int) -> Params:
    d, hd = cfg.d_model, cfg.hd
    nq_local, nkv_store, _ = _gqa_dims(cfg, tp)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(cfg.n_heads * hd)
    p = {
        "wq": jax.random.normal(k1, (d, nq_local * hd), jnp.float32) * s_in,
        "wk": jax.random.normal(k2, (d, nkv_store * hd), jnp.float32) * s_in,
        "wv": jax.random.normal(k3, (d, nkv_store * hd), jnp.float32) * s_in,
        "wo": jax.random.normal(k4, (nq_local * hd, d), jnp.float32) * s_out,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq_local * hd,), jnp.float32)
        p["bk"] = jnp.zeros((nkv_store * hd,), jnp.float32)
        p["bv"] = jnp.zeros((nkv_store * hd,), jnp.float32)
    return p


def _head_ids(comm: Comm, cfg: ModelConfig, tp: int):
    """(global q-head ids for this chip, validity mask for ghost heads)."""
    nq_local, _, _ = _gqa_dims(cfg, tp)
    first = comm.axis_index(comm.axes.model) * nq_local
    ids = first + jnp.arange(nq_local)
    return ids, ids < cfg.n_heads


def _local_kv(comm: Comm, cfg: ModelConfig, k, v, tp: int):
    """Return per-local-q-head K/V: when the KV proj is replicated, gather
    each q head's kv group head (handles any head/kv/tp combination);
    otherwise K/V are already the local shard (group attention)."""
    nq_local, _, kv_repl = _gqa_dims(cfg, tp)
    if not kv_repl:
        return k, v, cfg.n_kv_heads // tp
    group = cfg.n_heads // cfg.n_kv_heads
    ids, _ = _head_ids(comm, cfg, tp)
    kv_idx = jnp.clip(ids, 0, cfg.n_heads - 1) // group      # (nq_local,)
    k_l = jnp.take(k, kv_idx, axis=2)
    v_l = jnp.take(v, kv_idx, axis=2)
    return k_l, v_l, nq_local                                # group of 1


def kv_cache_plan(cfg: ModelConfig, tp: int):
    """Static per-rank bookkeeping for the replicated-KV decode cache:
    store only the DISTINCT kv heads each chip's q heads touch (ndk of
    them, constant-padded), not one copy per q head — internlm-class GQA
    (group 6, 3 q heads/chip) caches 1 head instead of 3.

    Returns (ndk, store_idx (tp, ndk), q2slot (tp, nq_local))."""
    nq_local, _, kv_repl = _gqa_dims(cfg, tp)
    if not kv_repl:
        return None
    group = max(1, cfg.n_heads // max(cfg.n_kv_heads, 1))
    store, q2slot = [], []
    for r in range(tp):
        ids = [min(r * nq_local + j, cfg.n_heads - 1)
               for j in range(nq_local)]
        kvs = [i // group for i in ids]
        distinct = sorted(set(kvs))
        store.append(distinct)
        q2slot.append([distinct.index(kv) for kv in kvs])
    ndk = max(len(d) for d in store)
    store_idx = np.asarray([d + [d[-1]] * (ndk - len(d)) for d in store],
                           np.int32)
    return ndk, store_idx, np.asarray(q2slot, np.int32)


def attention(comm: Comm, cfg: ModelConfig, p: Params, x, positions, *,
              is_local_layer: bool = False):
    """Full-sequence attention (train/prefill). x replicated over model;
    returns replicated (one allreduce)."""
    tp = comm.axis_size(comm.axes.model)
    B, L, d = x.shape
    hd = cfg.hd
    nq_local, nkv_store, _ = _gqa_dims(cfg, tp)
    q = _dense(x, p["wq"], p.get("bq")).reshape(B, L, nq_local, hd)
    k = _dense(x, p["wk"], p.get("bk")).reshape(B, L, nkv_store, hd)
    v = _dense(x, p["wv"], p.get("bv")).reshape(B, L, nkv_store, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    k, v, nkv_local = _local_kv(comm, cfg, k, v, tp)
    window = cfg.window
    if cfg.local_global_period is not None and is_local_layer:
        window = cfg.local_window
    seq_shards = (comm.axis_size(comm.axes.data)
                  if cfg.attention == "ring" and comm.backend == "shmem"
                  else 1)
    if seq_shards > 1:
        # attention="ring" (DESIGN.md §14): the caller sequence-sharded
        # x over `data` (long-context; `positions` are GLOBAL), so each
        # PE attends its query shard against the KV ring — each rotation
        # a put_nbi hidden behind the previous block's flash partials.
        # Head/TP layout and the wo allreduce are untouched.
        from ..core import fusion, shmem
        sctx = shmem.spmd_ctx(comm.axes.data)
        pos1 = positions[0].astype(jnp.int32)        # shared across batch
        o = fusion.ring_attention(
            sctx, q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), pos1, pos1, causal=cfg.causal,
            window=window, softcap=cfg.softcap, use_pallas=cfg.use_pallas,
            out_dtype=q.dtype)
    else:
        o = kops.attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=cfg.causal, window=window,
            softcap=cfg.softcap, use_pallas=cfg.use_pallas,
            blockwise_unroll=cfg.probe_unroll)
    o = o.transpose(0, 2, 1, 3)
    if cfg.n_heads % tp:   # zero ghost heads (padded head count)
        _, valid = _head_ids(comm, cfg, tp)
        o = o * valid[None, None, :, None]
    o = o.reshape(B, L, nq_local * hd).astype(cfg.dtype)
    out = _dense(o, p["wo"])
    return comm.allreduce(out, comm.axes.model)


def init_attn_cache(cfg: ModelConfig, tp: int, batch_local: int,
                    cache_len: int, window_bound: int | None = None):
    nq_local, _, kv_repl = _gqa_dims(cfg, tp)
    if kv_repl:
        ndk, _, _ = kv_cache_plan(cfg, tp)   # distinct kv heads only
        nkv_local = ndk
    else:
        nkv_local = cfg.n_kv_heads // tp
    s = cache_len if window_bound is None else min(cache_len, window_bound)
    shape = (batch_local, s, nkv_local, cfg.hd)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def attention_decode(comm: Comm, cfg: ModelConfig, p: Params, x, cache,
                     position, *, is_local_layer: bool = False,
                     seq_shards: int = 1):
    """One-token decode against a KV cache.

    Replicated-KV archs cache only each chip's DISTINCT kv heads
    (kv_cache_plan); q heads pick their slot through a one-hot map at
    attend time.  seq_shards > 1: cache sequence dim sharded over `data`
    (long-context); partial softmax stats are combined with two tiny
    allreduces over the data axis (flash-decode on shmem collectives)."""
    tp = comm.axis_size(comm.axes.model)
    B, one, d = x.shape
    hd = cfg.hd
    nq_local, nkv_store, kv_repl = _gqa_dims(cfg, tp)
    q = _dense(x, p["wq"], p.get("bq")).reshape(B, 1, nq_local, hd)
    k = _dense(x, p["wk"], p.get("bk")).reshape(B, 1, nkv_store, hd)
    v = _dense(x, p["wv"], p.get("bv")).reshape(B, 1, nkv_store, hd)
    q = rope(q, position[:, None], cfg.rope_theta)
    k = rope(k, position[:, None], cfg.rope_theta)

    slot_map = None
    if kv_repl:
        ndk, store_idx, q2slot = kv_cache_plan(cfg, tp)
        rank = comm.axis_index(comm.axes.model)
        sidx = jnp.asarray(store_idx)[rank]              # (ndk,)
        k = jnp.take(k, sidx, axis=2)
        v = jnp.take(v, sidx, axis=2)
        q2 = jnp.asarray(q2slot)[rank]                   # (nq_local,)
        slot_map = jax.nn.one_hot(q2, ndk, dtype=jnp.float32)

    S = cache["k"].shape[1]
    window = cfg.window
    if cfg.local_global_period is not None and is_local_layer:
        window = cfg.local_window
    ring = window is not None and S <= (window or 0)

    if seq_shards == 1:
        slot = position % S if ring else position
        ck = jax.vmap(lambda c, u, i: lax.dynamic_update_slice_in_dim(
            c, u, i, axis=0))(cache["k"], k, slot)
        cv = jax.vmap(lambda c, u, i: lax.dynamic_update_slice_in_dim(
            c, u, i, axis=0))(cache["v"], v, slot)
        pos_idx = jnp.arange(S)[None, :]                 # (1,S)
        if ring:
            age = position[:, None] - ((position[:, None] - pos_idx) % S)
            valid = (age >= 0) & (age <= position[:, None])
        else:
            valid = pos_idx <= position[:, None]
            if window is not None:
                valid &= pos_idx > (position[:, None] - window)
        out = _cache_attend(cfg, q, ck, cv, valid, slot_map)
        new_cache = {"k": ck, "v": cv}
    else:
        # sequence-sharded cache: my shard covers rows
        # [shard*S, shard*S + S) of the global sequence
        shard = comm.axis_index(comm.axes.data)
        g_start = shard * S
        slot = position - g_start
        here = (slot >= 0) & (slot < S)
        slot_c = jnp.clip(slot, 0, S - 1)
        upd = lambda c, u, i, h: jnp.where(
            h, lax.dynamic_update_slice_in_dim(c, u, i, axis=0), c)
        ck = jax.vmap(upd)(cache["k"], k, slot_c, here)
        cv = jax.vmap(upd)(cache["v"], v, slot_c, here)
        pos_idx = g_start + jnp.arange(S)[None, :]
        valid = pos_idx <= position[:, None]
        if window is not None:
            valid &= pos_idx > (position[:, None] - window)
        out = _cache_attend(cfg, q, ck, cv, valid, slot_map,
                            comm=comm, combine_axis=comm.axes.data)
        new_cache = {"k": ck, "v": cv}

    if cfg.n_heads % tp:   # zero ghost heads
        _, valid_h = _head_ids(comm, cfg, tp)
        out = out * valid_h[None, None, :, None]
    out = out.reshape(B, 1, nq_local * hd).astype(cfg.dtype)
    y = _dense(out, p["wo"])
    return comm.allreduce(y, comm.axes.model), new_cache


def _cache_attend(cfg, q, ck, cv, valid, slot_map=None, comm=None,
                  combine_axis=None):
    """q: (B,1,Hq,hd); ck/cv: (B,S,K,hd); valid: (B,S) -> (B,1,Hq,hd).

    slot_map (Hq,K) one-hot: replicated-KV path — logits computed against
    all K stored heads (K = distinct kv heads, small) then selected per q
    head.  slot_map None: grouped GQA (Hq = K*group)."""
    B, S = ck.shape[0], ck.shape[1]
    hd = cfg.hd
    qf = q[:, 0].astype(jnp.float32) / math.sqrt(hd)     # (B,Hq,hd)
    kf, vf = ck.astype(jnp.float32), cv.astype(jnp.float32)
    if slot_map is not None:
        logits = jnp.einsum("bqd,bskd->bqks", qf, kf)    # (B,Hq,K,S)
        logits = jnp.einsum("bqks,qk->bqs", logits, slot_map)
    else:
        K = ck.shape[2]
        group = qf.shape[1] // K
        qg = qf.reshape(B, K, group, hd)
        logits = jnp.einsum("bkgd,bskd->bkgs", qg, kf) \
            .reshape(B, K * group, S)
    if cfg.softcap is not None:
        logits = cfg.softcap * jnp.tanh(logits / cfg.softcap)
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    m_loc = jnp.max(logits, -1, keepdims=True)
    if comm is not None:
        m = lax.stop_gradient(comm.allreduce(m_loc, combine_axis, "max"))
    else:
        m = m_loc
    p_ = jnp.exp(logits - m)
    l_loc = jnp.sum(p_, -1, keepdims=True)
    if slot_map is not None:
        ctx = jnp.einsum("bqs,bskd->bqkd", p_, vf)
        acc = jnp.einsum("bqkd,qk->bqd", ctx, slot_map)
    else:
        K = ck.shape[2]
        group = p_.shape[1] // K
        pg = p_.reshape(B, K, group, S)
        acc = jnp.einsum("bkgs,bskd->bkgd", pg, vf) \
            .reshape(B, p_.shape[1], hd)
    if comm is not None:
        l_den = comm.allreduce(l_loc, combine_axis)
        acc = comm.allreduce(acc, combine_axis)
    else:
        l_den = l_loc
    out = acc / jnp.maximum(l_den, 1e-30)
    return out[:, None]                                  # (B,1,Hq,hd)


# ---------------------------------------------------------------------------
# Paged KV attention (serving engine, DESIGN.md §15)
# ---------------------------------------------------------------------------

@jax.named_scope("kv_update")
def paged_kv_write_block(pool_leaf, page_table, rows, positions,
                         page_size: int):
    """Write the new rows of every layer, for every position of every
    row, into a stacked pool.  pool_leaf: (layers, num_pages, page_size,
    C); rows: (layers, B, L, C); positions: (B, L)."""
    n = rows.shape[0]
    phys = page_table[jnp.arange(positions.shape[0])[:, None],
                      positions // page_size]
    shape = rows.shape[:3]
    layer = jnp.broadcast_to(jnp.arange(n)[:, None, None], shape)
    return pool_leaf.at[layer, jnp.broadcast_to(phys, shape),
                        jnp.broadcast_to(positions % page_size, shape)].set(
        rows.astype(pool_leaf.dtype))


@jax.named_scope("kv_gather")
def paged_kv_gather(pool_leaf, page_table):
    """Gather a sequence-contiguous (B, S_max, K*hd) view of each row's
    pages (S_max = max_pages * page_size).  Invalid/unallocated table
    entries point at the null page; the attention validity mask excludes
    them."""
    got = jnp.take(pool_leaf, page_table, axis=0)   # (B, P, ps, K*hd)
    B, P, ps = got.shape[0], got.shape[1], got.shape[2]
    return got.reshape((B, P * ps) + got.shape[3:])


@jax.named_scope("attend")
def _attend_mq(cfg, q, ck, cv, valid, slot_map=None):
    """Multi-query generalization of `_cache_attend` for the paged path.

    q: (B,L,Hq,hd) f32, already scaled; ck/cv: (B,S,K,hd); valid: (B,L,S)
    -> (B,L,Hq,hd).  Shared by paged prefill (L = prompt bucket) and the
    paged decode's gather reference (L = 1); every op is per row, which
    the engine's batched-vs-alone bit-identity rests on."""
    B, S = ck.shape[0], ck.shape[1]
    hd = q.shape[-1]
    kf, vf = ck.astype(jnp.float32), cv.astype(jnp.float32)
    if slot_map is not None:
        logits = jnp.einsum("blqd,bskd->blqks", q, kf)
        logits = jnp.einsum("blqks,qk->blqs", logits, slot_map)
    else:
        K = ck.shape[2]
        group = q.shape[2] // K
        qg = q.reshape(B, q.shape[1], K, group, hd)
        logits = jnp.einsum("blkgd,bskd->blkgs", qg, kf) \
            .reshape(B, q.shape[1], K * group, S)
    if cfg.softcap is not None:
        logits = cfg.softcap * jnp.tanh(logits / cfg.softcap)
    logits = jnp.where(valid[:, :, None, :], logits, -1e30)
    m = jnp.max(logits, -1, keepdims=True)
    p_ = jnp.exp(logits - m)
    l_den = jnp.sum(p_, -1, keepdims=True)
    if slot_map is not None:
        ctx = jnp.einsum("blqs,bskd->blqkd", p_, vf)
        acc = jnp.einsum("blqkd,qk->blqd", ctx, slot_map)
    else:
        K = ck.shape[2]
        group = p_.shape[2] // K
        pg = p_.reshape(B, p_.shape[1], K, group, S)
        acc = jnp.einsum("blkgs,bskd->blkgd", pg, vf) \
            .reshape(B, p_.shape[1], p_.shape[2], hd)
    return acc / jnp.maximum(l_den, 1e-30)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def paged_decode_kernel(cfg: ModelConfig, tp: int) -> bool:
    """Whether the paged decode attention runs the Pallas paged-decode
    kernel (`attention_paged_decode`, or `mla_paged_decode` in its latent
    mode): on a TPU, with the KV heads sharded over the model axis or with
    MLA.  The replicated-KV layout and every other backend take the gather
    reference the kernel is tested against (`kv_attend_gather`,
    `latent_attend_gather`)."""
    return _on_tpu() and (cfg.attn == "mla" or not _gqa_dims(cfg, tp)[2])


def _layer_window(cfg: ModelConfig, is_local_layer: bool):
    if cfg.local_global_period is not None and is_local_layer:
        return cfg.local_window
    return cfg.window


def _paged_qkv(comm: Comm, cfg: ModelConfig, p: Params, x, positions):
    """q (B,L,Hq_local,hd) f32, scaled by 1/sqrt(hd); k/v (B,L,K_store,hd)
    rounded to the pool's dtype (`cfg.dtype`); and, for the replicated KV
    layout, the one-hot q-head -> stored-KV-head map (else None)."""
    tp = comm.axis_size(comm.axes.model)
    B, L, d = x.shape
    hd = cfg.hd
    nq_local, nkv_store, kv_repl = _gqa_dims(cfg, tp)
    with jax.named_scope("attn_proj"):
        q = _dense(x, p["wq"], p.get("bq")).reshape(B, L, nq_local, hd)
        k = _dense(x, p["wk"], p.get("bk")).reshape(B, L, nkv_store, hd)
        v = _dense(x, p["wv"], p.get("bv")).reshape(B, L, nkv_store, hd)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    slot_map = None
    if kv_repl:
        ndk, store_idx, q2slot = kv_cache_plan(cfg, tp)
        rank = comm.axis_index(comm.axes.model)
        sidx = jnp.asarray(store_idx)[rank]                  # (ndk,)
        k = jnp.take(k, sidx, axis=2)
        v = jnp.take(v, sidx, axis=2)
        q2 = jnp.asarray(q2slot)[rank]                       # (nq_local,)
        slot_map = jax.nn.one_hot(q2, ndk, dtype=jnp.float32)
    return (q.astype(jnp.float32) / math.sqrt(hd), k.astype(cfg.dtype),
            v.astype(cfg.dtype), slot_map)


def _paged_out(comm: Comm, cfg: ModelConfig, p: Params, out, k, v):
    """Ghost-head mask, output projection and the model-axis allreduce;
    with the new K/V rows {"k","v"} (B, L, K_store*hd) for the caller to
    write into the pool once every layer has run."""
    tp = comm.axis_size(comm.axes.model)
    B, L, nq_local, hd = out.shape
    if cfg.n_heads % tp:   # zero ghost heads
        _, valid_h = _head_ids(comm, cfg, tp)
        out = out * valid_h[None, None, :, None]
    out = out.reshape(B, L, nq_local * hd).astype(cfg.dtype)
    with jax.named_scope("attn_proj"):
        y = _dense(out, p["wo"])
    return comm.allreduce(y, comm.axes.model), {
        "k": k.reshape(B, L, -1), "v": v.reshape(B, L, -1)}


def attention_paged_prefill(comm: Comm, cfg: ModelConfig, p: Params, x,
                            positions, *, keys: int,
                            is_local_layer: bool = False):
    """GQA attention of the engine's paged prefill, whose prompt starts at
    position 0, so its keys are its own rows, under the causal(+window)
    mask.  They are zero-padded to `keys` positions (the page table's
    span): every bucket then attends one key length, and the f32 sums
    over keys, whose rounding follows their length, give a prompt row
    the same result in every bucket.  Returns the output (B, L, d) and
    the K/V rows (`_paged_out`)."""
    q, k, v, slot_map = _paged_qkv(comm, cfg, p, x, positions)
    window = _layer_window(cfg, is_local_layer)
    kv_pos = jnp.arange(keys)[None, None, :]
    valid = kv_pos <= positions[:, :, None]
    if window is not None:
        valid &= kv_pos > positions[:, :, None] - window
    pad = ((0, 0), (0, keys - x.shape[1]), (0, 0), (0, 0))
    out = _attend_mq(cfg, q, jnp.pad(k, pad), jnp.pad(v, pad), valid,
                     slot_map)
    return _paged_out(comm, cfg, p, out, k, v)


def kv_attend_gather(cfg: ModelConfig, q, k_new, v_new, pool, layer,
                     page_table, positions, *, window=None, slot_map=None):
    """Decode attention of one new row per slot against its pages,
    gathered: the reference of the paged-decode kernel, on its operands.

    q: (B, Hq, hd) f32, scaled; k_new, v_new: (B, K, hd) the slot's new
    row; pool: {"k","v"} (layers, pages, page_size, K*hd), read at
    `layer`; positions: (B,).  Keys are the pool's positions [pos -
    window + 1, pos) plus the new row; `slot_map` as `_attend_mq`.
    Returns (B, Hq, hd) f32."""
    B, K, hd = k_new.shape
    ck, cv = (jnp.concatenate(
        [paged_kv_gather(pool[c][layer], page_table),
         new.reshape(B, 1, -1).astype(pool[c].dtype)], 1).reshape(
             B, -1, K, hd) for c, new in (("k", k_new), ("v", v_new)))
    kv_pos = jnp.arange(ck.shape[1] - 1)[None, :]
    valid = kv_pos < positions[:, None]
    if window is not None:
        valid &= kv_pos > positions[:, None] - window
    valid = jnp.pad(valid, ((0, 0), (0, 1)), constant_values=True)
    return _attend_mq(cfg, q[:, None], ck, cv, valid[:, None], slot_map)[:, 0]


def attention_paged_decode(comm: Comm, cfg: ModelConfig, p: Params, x,
                           pool, layer, page_table, positions, *,
                           page_size: int, is_local_layer: bool = False):
    """Single-token GQA decode against the paged pool, through the Pallas
    paged-decode kernel where `paged_decode_kernel` says, else its gather
    reference `kv_attend_gather`.

    x: (B, 1, d); pool: {"k","v"} the WHOLE stacked pool (layers,
    num_pages, page_size, K_local*hd), read in place at `layer`: a
    per-layer slice handed to a custom call would be materialised.  Each
    slot reads the pages holding its earlier positions; its new K/V row
    is attended from registers and returned (`_paged_out`).  Both paths:
    bf16 K/V, f32 scores, softmax and weighted sum."""
    q, k, v, slot_map = _paged_qkv(comm, cfg, p, x, positions)
    window = _layer_window(cfg, is_local_layer)
    if paged_decode_kernel(cfg, comm.axis_size(comm.axes.model)):
        with jax.named_scope("attend"):
            out = kops.paged_decode_attention(
                q[:, 0], k[:, 0], v[:, 0], pool["k"], pool["v"], layer,
                page_table, positions[:, 0], page_size=page_size,
                window=window, softcap=cfg.softcap)
    else:
        out = kv_attend_gather(cfg, q[:, 0], k[:, 0], v[:, 0], pool, layer,
                               page_table, positions[:, 0], window=window,
                               slot_map=slot_map)
    return _paged_out(comm, cfg, p, out[:, None], k, v)


# ---------------------------------------------------------------------------
# MLA (deepseek-v3): latent KV, cache = compressed c_kv (+ rope key)
# ---------------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def mla_rope_freqs(cfg: ModelConfig):
    """Inverse frequencies (qk_rope/2,) of the MLA rope lanes.  Under
    YaRN each frequency j moves from theta's own to 1/factor of it along
    r_j = clip((j - low) / (high - low), 0, 1), where low and high are the
    lanes that turn beta_fast and beta_slow times over the original
    context: inv_j (1 - r_j) + (inv_j / factor) r_j."""
    dim = cfg.mla.qk_rope_dim
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, dim, 2) / dim))
    y = cfg.yarn
    if y is not None:
        def lane(turns):
            return dim * math.log(y.original_max_pos / (turns * 2 * math.pi)) \
                / (2 * math.log(cfg.rope_theta))
        low = max(math.floor(lane(y.beta_fast)), 0)
        high = min(math.ceil(lane(y.beta_slow)), dim - 1)
        r = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                    0.0, 1.0)
        inv = inv * (1.0 - r) + inv / y.factor * r
    return jnp.asarray(inv, jnp.float32)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk_nope + qk_rope), times mscale(factor, mscale_all_dim)^2
    under YaRN."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if cfg.yarn is not None:
        scale *= _yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return scale


def _mla_rope(cfg: ModelConfig, x, positions):
    """Rope on the MLA rope lanes; YaRN scales cos and sin by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    out = rope(x, positions, cfg.rope_theta, mla_rope_freqs(cfg))
    y = cfg.yarn
    if y is not None and y.mscale != y.mscale_all_dim:
        out = out * (_yarn_mscale(y.factor, y.mscale)
                     / _yarn_mscale(y.factor, y.mscale_all_dim))
    return out.astype(x.dtype)


def init_mla(key, cfg: ModelConfig, tp: int) -> Params:
    m = cfg.mla
    d = cfg.d_model
    nq_local = cfg.n_heads // tp
    ks = jax.random.split(key, 6)
    qk_dim = m.qk_nope_dim + m.qk_rope_dim

    def nrm(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    return {
        "wq_a": nrm(ks[0], (d, m.q_lora_rank), d),
        "wq_b": nrm(ks[1], (m.q_lora_rank, nq_local * qk_dim), m.q_lora_rank),
        "wkv_a": nrm(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim), d),
        "wkv_b": nrm(ks[3], (m.kv_lora_rank,
                             nq_local * (m.qk_nope_dim + m.v_dim)),
                     m.kv_lora_rank),
        "wo": nrm(ks[4], (nq_local * m.v_dim, d), cfg.n_heads * m.v_dim),
        "q_norm": jnp.zeros((m.q_lora_rank,), jnp.float32),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), jnp.float32),
    }


def _mla_qkv(cfg: ModelConfig, p: Params, x, positions):
    """q_nope (B, L, H, nope), q_rope (B, L, H, rope) with rope applied,
    and the latent rows [c_kv | k_rope] (B, L, kv_lora + rope) that the
    cache holds."""
    m = cfg.mla
    B, L, _ = x.shape
    with jax.named_scope("attn_proj"):
        cq = rms_norm(_dense(x, p["wq_a"]), p["q_norm"])
        q = _dense(cq, p["wq_b"]).reshape(B, L, -1,
                                          m.qk_nope_dim + m.qk_rope_dim)
        q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
        q_rope = _mla_rope(cfg, q_rope, positions)
        kv_a = _dense(x, p["wkv_a"])
        c_kv = rms_norm(kv_a[..., :m.kv_lora_rank], p["kv_norm"])
        k_rope = _mla_rope(cfg, kv_a[..., None, m.kv_lora_rank:],
                           positions)[..., 0, :]
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], -1)


def _mla_full(comm: Comm, cfg: ModelConfig, p: Params, x, positions, *,
              blockwise: bool = False):
    """Non-absorbed causal MLA over the rows of x (keys are x's own rows):
    per-head keys [W_kb c_kv | k_rope] and values W_vb c_kv.  Returns the
    output (B, L, d) and the latent rows (B, L, kv_lora + rope)."""
    m = cfg.mla
    B, L, _ = x.shape
    q_nope, q_rope, lat = _mla_qkv(cfg, p, x, positions)
    with jax.named_scope("attn_proj"):
        kv = _dense(lat[..., :m.kv_lora_rank], p["wkv_b"]).reshape(
            B, L, -1, m.qk_nope_dim + m.v_dim)
        k_nope, v = kv[..., :m.qk_nope_dim], kv[..., m.qk_nope_dim:]
        nh = k_nope.shape[2]
        k_rope = jnp.broadcast_to(lat[:, :, None, m.kv_lora_rank:],
                                  (B, L, nh, m.qk_rope_dim))
        k = jnp.concatenate([k_nope, k_rope], -1).transpose(0, 2, 1, 3)
        q = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
    with jax.named_scope("attend"):
        if blockwise:
            o = kref.attention_blockwise(q, k, v, causal=True,
                                         sm_scale=mla_softmax_scale(cfg),
                                         block=min(L, 512))
        else:
            o = kops.attention(q, k, v, causal=True,
                               sm_scale=mla_softmax_scale(cfg),
                               use_pallas=cfg.use_pallas,
                               blockwise_unroll=cfg.probe_unroll)
    o = o.transpose(0, 2, 1, 3).reshape(B, L, nh * m.v_dim)
    with jax.named_scope("attn_proj"):
        y = _dense(o.astype(cfg.dtype), p["wo"])
    return comm.allreduce(y, comm.axes.model), lat


def mla_attention(comm: Comm, cfg: ModelConfig, p: Params, x, positions):
    return _mla_full(comm, cfg, p, x, positions)[0]


def latent_width(cfg: ModelConfig) -> int:
    """Lanes of one row of the latent pool: [c_kv | k_rope], zero-padded
    to a whole 128-lane tile (the TPU's HBM layout pads the row to it
    anyway, and the decode kernel's page DMA must move whole tiles)."""
    n = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
    return -(-n // 128) * 128


def _pad_lanes(x, width: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def mla_paged_prefill(comm: Comm, cfg: ModelConfig, p: Params, x,
                      positions):
    """MLA of the engine's paged prefill, whose prompt starts at position
    0, so its keys are its own rows: the non-absorbed form, blockwise over
    keys.  Returns the output (B, L, d) and the latent rows {"latent":
    (B, L, `latent_width`)} for the caller to write into the latent
    pages."""
    y, lat = _mla_full(comm, cfg, p, x, positions, blockwise=True)
    return y, {"latent": _pad_lanes(lat, latent_width(cfg))}


@jax.named_scope("attend")
def latent_attend_gather(q, new_row, pool, layer, page_table, positions,
                         v_lanes: int):
    """Absorbed latent attention of one new row per slot against its pages,
    gathered: the reference of the paged-decode kernel's latent mode.

    q: (B, H, C) f32, scaled; new_row: (B, C) the slot's latent row;
    pool: (layers, pages, page_size, C), read at `layer`; keys are the
    pool's positions < pos plus the new row, values the first `v_lanes`
    lanes of each key.  Returns (B, H, v_lanes) f32."""
    lat = paged_kv_gather(pool[layer], page_table).astype(jnp.float32)
    new = new_row.astype(pool.dtype).astype(jnp.float32)
    s = jnp.einsum("bhc,bsc->bhs", q, lat)
    valid = jnp.arange(lat.shape[1])[None, :] < positions[:, None]
    s = jnp.where(valid[:, None, :], s, -1e30)
    s_new = jnp.einsum("bhc,bc->bh", q, new)[..., None]
    mx = jnp.maximum(jnp.max(s, -1, keepdims=True), s_new)
    pr, p_new = jnp.exp(s - mx), jnp.exp(s_new - mx)
    ctx = jnp.einsum("bhs,bsv->bhv", pr, lat[..., :v_lanes]) \
        + p_new * new[:, None, :v_lanes]
    return ctx / (jnp.sum(pr, -1, keepdims=True) + p_new)


def mla_paged_decode(comm: Comm, cfg: ModelConfig, p: Params, x, pool,
                     layer, page_table, positions, *, page_size: int):
    """Absorbed single-token MLA against the latent pages.

    x: (B, 1, d); pool: {"latent": the WHOLE stacked latent pool (layers,
    pages, page_size, `latent_width`)}, read in place at `layer`.  The query
    absorbs W_kb, q_abs = q_nope W_kb^T, so each score is [q_abs | q_rope]
    . [c_kv | k_rope] over the cached latent rows; the context sum p c_kv
    is lifted by W_vb.  Through the paged-decode kernel's latent mode
    where `paged_decode_kernel` says, else its gather reference.  The
    query and the row are zero-padded to the pool's width, which adds
    exact zeros to every score.  Returns the output (B, 1, d) and the
    slot's new latent row {"latent": (B, 1, `latent_width`)} for the
    caller to write once every layer has run."""
    m = cfg.mla
    B = x.shape[0]
    pool = pool["latent"]
    q_nope, q_rope, lat = _mla_qkv(cfg, p, x, positions)
    wkv = p["wkv_b"].reshape(m.kv_lora_rank, -1, m.qk_nope_dim + m.v_dim)
    wkv = wkv.astype(cfg.dtype)
    with jax.named_scope("mla_absorb"):
        q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0],
                           wkv[..., :m.qk_nope_dim],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_abs, q_rope[:, 0].astype(jnp.float32)], -1) \
            * mla_softmax_scale(cfg)
        q = _pad_lanes(q, pool.shape[-1])
    row = _pad_lanes(lat[:, 0], pool.shape[-1])
    if paged_decode_kernel(cfg, comm.axis_size(comm.axes.model)):
        with jax.named_scope("attend"):
            ctx = kops.paged_decode_attention(
                q, row[:, None], None, pool, None, layer, page_table,
                positions[:, 0], page_size=page_size,
                v_lanes=m.kv_lora_rank)
    else:
        ctx = latent_attend_gather(q, row, pool, layer, page_table,
                                   positions[:, 0], m.kv_lora_rank)
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("bhr,rhv->bhv", ctx.astype(cfg.dtype),
                       wkv[..., m.qk_nope_dim:],
                       preferred_element_type=jnp.float32)
    with jax.named_scope("attn_proj"):
        y = _dense(o.reshape(B, 1, -1).astype(cfg.dtype), p["wo"])
    return comm.allreduce(y, comm.axes.model), {
        "latent": row[:, None].astype(pool.dtype)}


def init_mla_cache(cfg: ModelConfig, batch_local: int, cache_len: int):
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch_local, cache_len, m.kv_lora_rank),
                              cfg.dtype),
            "k_rope": jnp.zeros((batch_local, cache_len, m.qk_rope_dim),
                                cfg.dtype)}


def mla_decode(comm: Comm, cfg: ModelConfig, p: Params, x, cache, position):
    m = cfg.mla
    tp = comm.axis_size(comm.axes.model)
    nq_local = cfg.n_heads // tp
    B = x.shape[0]
    q_nope, q_rope, lat = _mla_qkv(cfg, p, x, position[:, None])
    c_kv_new = lat[:, :, :m.kv_lora_rank]
    k_rope_new = lat[:, :, m.kv_lora_rank:]

    upd = lambda c, u, i: lax.dynamic_update_slice_in_dim(c, u, i, axis=0)
    ckv = jax.vmap(upd)(cache["c_kv"], c_kv_new.astype(cfg.dtype), position)
    ckr = jax.vmap(upd)(cache["k_rope"], k_rope_new.astype(cfg.dtype),
                        position)
    S = ckv.shape[1]

    # absorbed attention: score = q_nope . (W_kb^T c) + q_rope . k_rope
    wkv = p["wkv_b"].reshape(m.kv_lora_rank, nq_local, m.qk_nope_dim + m.v_dim)
    w_k = wkv[..., :m.qk_nope_dim]         # (r, h, nope)
    w_v = wkv[..., m.qk_nope_dim:]         # (r, h, v)
    q_abs = jnp.einsum("bohn,rhn->bohr", q_nope.astype(jnp.float32),
                       w_k.astype(jnp.float32))   # (B,1,h,r)
    sc = mla_softmax_scale(cfg)
    logits = (jnp.einsum("bohr,bsr->bhs", q_abs,
                         ckv.astype(jnp.float32)) +
              jnp.einsum("bohn,bsn->bhs", q_rope.astype(jnp.float32),
                         ckr.astype(jnp.float32))) * sc
    valid = jnp.arange(S)[None, :] <= position[:, None]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    pr = jax.nn.softmax(logits, -1)
    ctx = jnp.einsum("bhs,bsr->bhr", pr, ckv.astype(jnp.float32))
    o = jnp.einsum("bhr,rhv->bhv", ctx, w_v.astype(jnp.float32))
    o = o.reshape(B, 1, nq_local * m.v_dim).astype(cfg.dtype)
    y = comm.allreduce(_dense(o, p["wo"]), comm.axes.model)
    return y, {"c_kv": ckv, "k_rope": ckr}


# ---------------------------------------------------------------------------
# MLP (dense swiglu, column+row parallel)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, tp: int, d_ff: int | None = None) -> Params:
    d = cfg.d_model
    ff_local = (d_ff or cfg.d_ff) // tp
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": jax.random.normal(k1, (d, ff_local), jnp.float32)
        / math.sqrt(d),
        "w_up": jax.random.normal(k2, (d, ff_local), jnp.float32)
        / math.sqrt(d),
        "w_down": jax.random.normal(k3, (ff_local, d), jnp.float32)
        / math.sqrt(d_ff or cfg.d_ff),
    }


def _swiglu(comm: Comm, p: Params, x):
    h = jax.nn.silu(_dense(x, p["w_gate"])) * _dense(x, p["w_up"])
    return comm.allreduce(_dense(h, p["w_down"]), comm.axes.model)


@jax.named_scope("mlp")
def mlp(comm: Comm, cfg: ModelConfig, p: Params, x):
    return _swiglu(comm, p, x)


# ---------------------------------------------------------------------------
# MoE (EP over `model` axis, pairwise-alltoall dispatch)
# ---------------------------------------------------------------------------

def moe_ep_size(cfg: ModelConfig, tp: int, dp: int) -> int:
    return tp * dp if cfg.moe.ep_over_data else tp


def init_moe(key, cfg: ModelConfig, tp: int, dp: int = 1) -> Params:
    mo = cfg.moe
    d = cfg.d_model
    e_local = -(-mo.n_experts // moe_ep_size(cfg, tp, dp))
    if mo.experts_held is not None:
        e_local = mo.experts_held
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)

    def nrm(k, shape, fan):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)

    p = {
        "router": nrm(k1, (d, mo.n_experts), d),
        "w_gate": nrm(k2, (e_local, d, mo.d_ff), d),
        "w_up": nrm(k3, (e_local, d, mo.d_ff), d),
        "w_down": nrm(k4, (e_local, mo.d_ff, d), mo.d_ff),
    }
    if mo.correction_bias:
        p["router_bias"] = jnp.zeros((mo.n_experts,), jnp.float32)
    if mo.n_shared:
        p["shared"] = init_mlp(k5, cfg, tp, d_ff=mo.n_shared * mo.d_ff)
    return p


def route(cfg: ModelConfig, p: Params, x):
    """Top-k routing of token rows x (T, d) over all n_experts.

    Scores s = softmax or sigmoid of the router logits (computed in f32).
    Selection ranks s + b (b the correction bias, where the config has
    one); with n_group > 1 a group scores the sum of its top 2 of s + b
    and only the topk_group best groups' experts stay eligible.  Gates
    are the selected experts' s, normalised to sum 1 and scaled by
    routed_scale.  Returns (s (T, E), experts (T, k), gates (T, k))."""
    mo = cfg.moe
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if mo.score_func == "sigmoid"
              else jax.nn.softmax(logits, -1))
    sel = scores + p["router_bias"] if mo.correction_bias else scores
    if mo.n_group > 1:
        T, E = sel.shape
        per = E // mo.n_group
        group_score = lax.top_k(sel.reshape(T, mo.n_group, per), 2)[0].sum(-1)
        _, groups = lax.top_k(group_score, mo.topk_group)
        keep = jnp.zeros((T, mo.n_group), bool).at[
            jnp.arange(T)[:, None], groups].set(True)
        sel = jnp.where(jnp.repeat(keep, per, axis=1), sel, -jnp.inf)
    _, experts = lax.top_k(sel, mo.top_k)
    gates = jnp.take_along_axis(scores, experts, -1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9) \
        * mo.routed_scale
    return scores, experts, gates


def moe_held(comm: Comm, cfg: ModelConfig, p: Params, x, valid=None):
    """Dropless MoE over the expert share this chip holds (serving).

    Every row routes over all n_experts (`route`); the rows of the held
    experts [experts_offset, experts_offset + experts_held) are sorted by
    expert and go through one grouped matmul each for gate, up and down
    (no capacity, nothing dropped); assignments to the other experts add
    nothing here.  The shared experts run on every row.  Rows where
    `valid` (B, L) is False (a batch's empty slots) route nowhere.
    Returns (out (B, L, d), rows (experts_held,) int32: the token rows
    each held expert computed)."""
    mo = cfg.moe
    held = mo.experts_held or mo.n_experts
    B, L, d = x.shape
    flat = x.reshape(B * L, d)
    with jax.named_scope("moe_route"):
        _, experts, gates = route(cfg, p, flat)
        local = experts - mo.experts_offset
        mine = (local >= 0) & (local < held)
        if valid is not None:
            mine &= valid.reshape(-1, 1)
        group = jnp.where(mine, local, held).reshape(-1)   # held: nowhere
        order = jnp.argsort(group, stable=True)
        rows = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
        tok = order // mo.top_k
        w = jnp.where(mine, gates, 0.0).reshape(-1)[order]
    with jax.named_scope("moe_experts"):
        xs = flat[tok]
        h = jax.nn.silu(lax.ragged_dot(xs, p["w_gate"].astype(x.dtype), rows)) \
            * lax.ragged_dot(xs, p["w_up"].astype(x.dtype), rows)
        y = lax.ragged_dot(h, p["w_down"].astype(x.dtype), rows)
        # rows past the held groups are left undefined by the grouped
        # matmul (garbage, not zeros, on a TPU): drop them outright
        held_row = jnp.arange(y.shape[0]) < jnp.sum(rows)
        y = jnp.where(held_row[:, None], y.astype(jnp.float32) * w[:, None],
                      0.0)
        out = jnp.zeros((B * L, d), jnp.float32).at[tok].add(y)
    out = out.reshape(B, L, d).astype(x.dtype)
    if mo.n_shared:
        with jax.named_scope("moe_shared"):
            out = out + _swiglu(comm, p["shared"], x)
    return out, rows


def moe(comm: Comm, cfg: ModelConfig, p: Params, x):
    """x: (B, L, d) replicated over model -> same.

    EP dispatch: tokens are sequence-split over the model axis (free — x is
    replicated there), routed top-k with capacity dropping, delivered to
    expert owners with the paper's pairwise `alltoall` (Fig. 9), and
    returned the same way.  With ep_over_data the EP group is the flattened
    (data, model) PE space — 256-way expert sharding for deepseek-v3."""
    mo = cfg.moe
    if mo.experts_held is not None:
        raise ValueError("a held expert share serves through moe_held")
    tp = comm.axis_size(comm.axes.model)
    ep_axes = ((comm.axes.data, comm.axes.model) if mo.ep_over_data
               else comm.axes.model)
    ep = (int(np.prod([comm.axis_size(a) for a in ep_axes]))
          if isinstance(ep_axes, tuple)
          else comm.axis_size(ep_axes))   # None (dp_only) -> 1
    B, L, d = x.shape
    e_local = -(-mo.n_experts // ep)
    e_pad = e_local * ep

    # 1. my token slice among the model group (data split is the batch);
    # decode steps can carry fewer tokens than tp — pad with zero tokens
    # (they route, compute garbage, and are dropped on return)
    flat = x.reshape(B * L, d)
    t_total = B * L
    t_pad = -(-t_total // tp) * tp
    if t_pad != t_total:
        flat = jnp.pad(flat, ((0, t_pad - t_total), (0, 0)))
    t_local = t_pad // tp
    my = comm.axis_index(comm.axes.model)
    xs = lax.dynamic_slice_in_dim(flat, my * t_local, t_local, axis=0)

    # 2. route (over the real expert count)
    gates, tope, topv = route(cfg, p, xs)                      # (T, E/K/K)

    # 3. capacity + dispatch buffers (E_pad, C, d) via scatter
    cap = max(1, int(mo.capacity_factor * t_local * mo.top_k
                     / mo.n_experts))
    e_flat = tope.reshape(-1)                                  # (T*K,)
    onehot = jax.nn.one_hot(e_flat, mo.n_experts, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(ranks, e_flat[:, None], 1)[:, 0]
    keep = slot < cap
    tok_idx = jnp.repeat(jnp.arange(t_local), mo.top_k)
    disp = jnp.zeros((e_pad, cap, d), x.dtype)
    disp = disp.at[
        jnp.where(keep, e_flat, 0),
        jnp.where(keep, slot, 0)].add(
        jnp.where(keep[:, None], xs[tok_idx], 0.0))

    # 4. alltoall over the EP group: (E_pad, C, d) -> (e_local, ep*C, d)
    a2a = comm.alltoall(disp.reshape(ep, e_local * cap, d),
                        ep_axes, split_axis=0, concat_axis=0)
    exp_in = a2a.reshape(ep, e_local, cap, d).transpose(1, 0, 2, 3) \
        .reshape(e_local, ep * cap, d)

    # 5. expert FFN
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", exp_in,
                                p["w_gate"].astype(x.dtype)))
         * jnp.einsum("ecd,edf->ecf", exp_in, p["w_up"].astype(x.dtype)))
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))

    # 6. alltoall back + combine
    y = y.reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3) \
        .reshape(ep, e_local * cap, d)
    back = comm.alltoall(y, ep_axes, split_axis=0, concat_axis=0)
    buf = back.reshape(e_pad, cap, d)
    gathered = buf[jnp.where(keep, e_flat, 0),
                   jnp.where(keep, slot, 0)]                   # (T*K, d)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    w = (topv.reshape(-1) * keep).astype(jnp.float32)[:, None]
    ys = jnp.zeros((t_local, d), jnp.float32).at[tok_idx].add(
        gathered.astype(jnp.float32) * w)

    # 7. allgather token slices back to model-replicated layout
    full = comm.allgather(ys.astype(x.dtype), comm.axes.model, concat_axis=0)
    out = full[:t_total].reshape(B, L, d)
    if mo.n_shared:
        out = out + mlp(comm, cfg, p["shared"], x)
    # aux losses (load balance) for training
    me = jnp.mean(gates, 0)
    ce = jnp.mean(
        jax.nn.one_hot(tope, mo.n_experts, dtype=jnp.float32).sum(1), 0)
    aux = mo.n_experts * jnp.sum(me * ce)
    return out, aux


# ---------------------------------------------------------------------------
# Mamba2 block (heads sharded over model)
# ---------------------------------------------------------------------------

def init_mamba2(key, cfg: ModelConfig, tp: int) -> Params:
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    d_in_local = d_in // tp
    nheads_local = d_in_local // s.head_dim
    conv_dim = d_in_local + 2 * s.n_groups * s.state
    ks = jax.random.split(key, 5)

    def nrm(k, shape, fan):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)

    return {
        # [z, x, B, C, dt] fused in-proj; B/C replicated groups per shard
        "w_in": nrm(ks[0], (d, 2 * d_in_local + 2 * s.n_groups * s.state
                            + nheads_local), d),
        "conv_w": nrm(ks[1], (s.conv_width, conv_dim), s.conv_width),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nheads_local)),
        "dt_bias": jnp.zeros((nheads_local,), jnp.float32),
        "d_skip": jnp.ones((nheads_local,), jnp.float32),
        "norm_w": jnp.zeros((d_in_local,), jnp.float32),
        "w_out": nrm(ks[2], (d_in_local, d), d_in),
    }


def _mamba_split(cfg: ModelConfig, tp: int):
    s = cfg.ssm
    d_in_local = s.expand * cfg.d_model // tp
    nheads_local = d_in_local // s.head_dim
    gdim = s.n_groups * s.state
    return d_in_local, nheads_local, gdim


def mamba2(comm: Comm, cfg: ModelConfig, p: Params, x):
    """Full-sequence Mamba2 (train/prefill). One allreduce at out-proj."""
    s = cfg.ssm
    tp = comm.axis_size(comm.axes.model)
    B, L, d = x.shape
    d_in_local, nheads_local, gdim = _mamba_split(cfg, tp)

    zxbcdt = _dense(x, p["w_in"])
    z = zxbcdt[..., :d_in_local]
    xbc = zxbcdt[..., d_in_local:d_in_local * 2 + 2 * gdim]
    dt = zxbcdt[..., -nheads_local:]

    # depthwise causal conv over [x, B, C]
    w = p["conv_w"].astype(xbc.dtype)
    acc = xbc * w[-1]
    for i in range(1, s.conv_width):
        acc = acc + jnp.pad(xbc, ((0, 0), (i, 0), (0, 0)))[:, :L] * w[-1 - i]
    xbc = jax.nn.silu(acc + p["conv_b"].astype(acc.dtype))

    xs = xbc[..., :d_in_local].reshape(B, L, nheads_local, s.head_dim)
    b_mat = xbc[..., d_in_local:d_in_local + gdim] \
        .reshape(B, L, s.n_groups, s.state)
    c_mat = xbc[..., d_in_local + gdim:] \
        .reshape(B, L, s.n_groups, s.state)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    a_log = -jnp.exp(p["a_log"].astype(jnp.float32))

    y, _ = kops.ssd(xs, dt, a_log, b_mat, c_mat, chunk=s.chunk,
                    use_pallas=cfg.use_pallas, unroll=cfg.probe_unroll)
    y = y + xs * p["d_skip"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(B, L, d_in_local)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["norm_w"])
    out = _dense(y.astype(cfg.dtype), p["w_out"])
    return comm.allreduce(out, comm.axes.model)


def init_mamba_cache(cfg: ModelConfig, tp: int, batch_local: int):
    s = cfg.ssm
    d_in_local, nheads_local, gdim = _mamba_split(cfg, tp)
    conv_dim = d_in_local + 2 * gdim
    return {
        "conv": jnp.zeros((batch_local, s.conv_width - 1, conv_dim),
                          cfg.dtype),
        "ssm": jnp.zeros((batch_local, nheads_local, s.head_dim, s.state),
                         jnp.float32),
    }


def mamba2_decode(comm: Comm, cfg: ModelConfig, p: Params, x, cache):
    """Single-step recurrence (decode)."""
    s = cfg.ssm
    tp = comm.axis_size(comm.axes.model)
    B = x.shape[0]
    d_in_local, nheads_local, gdim = _mamba_split(cfg, tp)

    zxbcdt = _dense(x[:, 0], p["w_in"])                     # (B, ...)
    z = zxbcdt[..., :d_in_local]
    xbc = zxbcdt[..., d_in_local:d_in_local * 2 + 2 * gdim]
    dt = zxbcdt[..., -nheads_local:]

    conv_hist = jnp.concatenate([cache["conv"],
                                 xbc[:, None].astype(cfg.dtype)], 1)
    w = p["conv_w"].astype(jnp.float32)
    acc = jnp.einsum("bwc,wc->bc", conv_hist.astype(jnp.float32), w)
    xbc = jax.nn.silu(acc + p["conv_b"].astype(jnp.float32))

    xs = xbc[..., :d_in_local].reshape(B, nheads_local, s.head_dim)
    b_t = xbc[..., d_in_local:d_in_local + gdim].reshape(B, s.n_groups,
                                                         s.state)
    c_t = xbc[..., d_in_local + gdim:].reshape(B, s.n_groups, s.state)
    group = nheads_local // s.n_groups
    b_h = jnp.repeat(b_t, group, 1)
    c_h = jnp.repeat(c_t, group, 1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    a = jnp.exp(-jnp.exp(p["a_log"].astype(jnp.float32))[None] * dt)
    state = cache["ssm"] * a[..., None, None] + (
        dt[..., None, None] * xs[..., None].astype(jnp.float32)
        * b_h[..., None, :].astype(jnp.float32))
    y = jnp.einsum("bhn,bhpn->bhp", c_h.astype(jnp.float32), state)
    y = y + xs.astype(jnp.float32) * p["d_skip"][None, :, None]
    y = y.reshape(B, d_in_local)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), p["norm_w"])
    out = _dense(y[:, None].astype(cfg.dtype), p["w_out"])
    out = comm.allreduce(out, comm.axes.model)
    new_cache = {"conv": conv_hist[:, 1:], "ssm": state}
    return out, new_cache
