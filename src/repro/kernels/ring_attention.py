"""Per-KV-block attention partials for the fused ring-attention path.

The monolithic flash kernel (`flash_attention.py`) streams the WHOLE KV
sequence through its in-kernel fori loop.  Ring attention (DESIGN.md §14)
instead sees the KV sequence one remote block at a time — each ring step
delivers the next neighbor's KV shard while the current one is consumed —
so the kernel here computes the *un-normalized* online-softmax partial
state for ONE block:

    acc = sum_j exp(s_j - m) v_j     (B, Hq, Lq, D)   f32
    m   = max_j s_j                  (B, Hq, Lq)      f32 (NEG_INF if none)
    l   = sum_j exp(s_j - m)         (B, Hq, Lq)      f32

Partial states from successive blocks merge with the standard flash
rescaling (`merge_partials`) and `finalize` applies the deferred division,
reproducing the monolithic kernel's arithmetic to f32 allclose regardless
of how the KV sequence was split.

Masking is GLOBAL-position based: the caller passes the query rows'
positions and each KV block's positions (`k_pos`, with -1 marking padded
slots) so causal / sliding-window / ragged-edge semantics survive the
sequence sharding — a block's rows mask exactly as they would have in the
monolithic kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import DEFAULT_BK, DEFAULT_BQ, NEG_INF
from .ops import _default_interpret


def _partials_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref,
                     acc_ref, m_ref, l_ref, *, lk_pad: int, bk: int,
                     causal: bool, window: int | None,
                     softcap: float | None, sm_scale: float):
    q = q_ref[...][0, 0].astype(jnp.float32) * sm_scale     # (BQ, D)
    bq, d = q.shape
    q_pos = qp_ref[...]                                     # (BQ, 1)

    n_kb = lk_pad // bk

    def body(i, carry):
        acc, m_i, l_i = carry
        start = i * bk
        k = k_ref[0, 0, pl.ds(start, bk), :].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0, pl.ds(start, bk), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (BQ, BK)
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        k_pos = kp_ref[pl.ds(i, 1), :]                           # (1, BK)
        mask = k_pos >= 0                    # -1 marks padded KV slots
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m_i, l_i = jax.lax.fori_loop(0, n_kb, body, (acc0, m0, l0))
    acc_ref[...] = acc[None, None]
    m_ref[...] = m_i[None, None]
    l_ref[...] = l_i[None, None]


def _partials_pallas(q, k, v, q_pos, k_pos, *, causal, window, softcap,
                     sm_scale, bq, bk, interpret):
    b_sz, hq, lq, d = q.shape
    _, hkv, lk, _ = k.shape
    group = hq // hkv
    kernel = functools.partial(
        _partials_kernel, lk_pad=lk, bk=bk, causal=causal, window=window,
        softcap=softcap, sm_scale=sm_scale)
    grid = (b_sz, hq, lq // bq)
    acc, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, lk, d), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, lk, d), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((bq, 1), lambda b, h, i: (i, 0)),
            pl.BlockSpec((lk // bk, bk), lambda b, h, i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b_sz, hq, lq, d), jnp.float32),
            jax.ShapeDtypeStruct((b_sz, hq, lq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b_sz, hq, lq, 1), jnp.float32),
        ),
        interpret=interpret,
    )(q, k, v, q_pos.reshape(lq, 1), k_pos.reshape(lk // bk, bk))
    return acc, m[..., 0], l[..., 0]


def _partials_ref(q, k, v, q_pos, k_pos, *, causal, window, softcap,
                  sm_scale):
    """XLA reference — identical arithmetic to the kernel, one KV block."""
    hq, hkv = q.shape[1], k.shape[1]
    group = hq // hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    qf = q.astype(jnp.float32) * sm_scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, k.astype(jnp.float32))
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    mask = kp >= 0
    if causal:
        mask = mask & (kp <= qp)
    if window is not None:
        mask = mask & (kp > qp - window)
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return acc, m, l


def attn_block_partials(q, k, v, q_pos, k_pos, *, causal: bool = True,
                        window: int | None = None,
                        softcap: float | None = None,
                        sm_scale: float | None = None,
                        use_pallas: bool = False,
                        bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                        interpret: bool | None = None):
    """Un-normalized flash partials of q against ONE KV block.

    q: (B, Hq, Lq, D); k, v: (B, Hkv, Lk, D); q_pos: (Lq,) int32 global
    query positions; k_pos: (Lk,) int32 global key positions (-1 = padded
    slot, always masked).  Returns (acc f32 (B,Hq,Lq,D), m f32 (B,Hq,Lq),
    l f32 (B,Hq,Lq)) — merge with `merge_partials`, then `finalize`.
    The Pallas path runs in interpret mode unless the backend is a TPU."""
    d = q.shape[-1]
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if not use_pallas:
        return _partials_ref(q, k, v, q_pos, k_pos, causal=causal,
                             window=window, softcap=softcap,
                             sm_scale=sm_scale)
    if interpret is None:
        interpret = _default_interpret()
    lq, lk = q.shape[2], k.shape[2]
    pq = (-lq) % bq
    pk = (-lk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pk), constant_values=-1)
    acc, m, l = _partials_pallas(q, k, v, q_pos, k_pos, causal=causal,
                                 window=window, softcap=softcap,
                                 sm_scale=sm_scale, bq=bq, bk=bk,
                                 interpret=interpret)
    return acc[:, :, :lq], m[:, :, :lq], l[:, :, :lq]


def merge_partials(a, b):
    """Combine two un-normalized partial states (associative and, up to
    f32 rounding, order-insensitive — the flash rescaling rule)."""
    acc_a, m_a, l_a = a
    acc_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    wa = jnp.exp(m_a - m)
    wb = jnp.exp(m_b - m)
    acc = acc_a * wa[..., None] + acc_b * wb[..., None]
    l = l_a * wa + l_b * wb
    return acc, m, l


def finalize(state, dtype=None):
    """Apply the deferred softmax division: out = acc / max(l, 1e-30),
    the same epsilon-guarded division the monolithic kernel performs.
    Fully-masked rows come out exactly 0."""
    acc, _, l = state
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out if dtype is None else out.astype(dtype)
