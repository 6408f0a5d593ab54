"""Blockwise (flash) attention Pallas kernel for the model zoo's hot spot.

Covers every attention variant the assigned architectures need:
  * GQA (kv-head groups)           — internlm2 / qwen2 / gemma2 / danube
  * causal masking                 — all decoders
  * sliding-window                 — h2o-danube, gemma2 local layers
  * logit soft-capping (tanh)      — gemma2
  * non-causal                     — hubert encoder, phi-3-vision image part

TPU adaptation notes: Q is tiled (BQ, D) into VMEM per grid step, the KV
sequence streams through an in-kernel fori loop at (BK, D) granularity with
f32 online-softmax accumulators — the standard MXU-friendly flash schedule
(block sizes multiples of 128 lanes / 8 sublanes).  The HBM->VMEM streaming
plays the role Epiphany SRAM staging played for the paper's copy loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, lk_pad: int, lk_valid: int,
                 bk: int, causal: bool, window: int | None,
                 softcap: float | None, sm_scale: float, q_start_map):
    qb = pl.program_id(2)
    q = q_ref[...][0, 0].astype(jnp.float32) * sm_scale  # (BQ, D)
    bq, d = q.shape
    q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    n_kb = lk_pad // bk

    def body(i, carry):
        acc, m_i, l_i = carry
        start = i * bk
        k = k_ref[0, 0, pl.ds(start, bk), :].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0, pl.ds(start, bk), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (BQ, BK)
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = k_pos < lk_valid
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
    o_ref[...] = (acc / jnp.maximum(l_i, 1e-30)).astype(
        o_ref.dtype)[None, None]


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float | None = None, sm_scale: float | None = None,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    lk_valid: int | None = None, interpret: bool = False):
    """q: (B, Hq, Lq, D); k, v: (B, Hkv, Lk, D); Lq % bq == Lk % bk == 0
    (ops.py pads and passes lk_valid for the ragged edge)."""
    b_sz, hq, lq, d = q.shape
    _, hkv, lk, _ = k.shape
    assert lq % bq == 0 and lk % bk == 0, (lq, lk, bq, bk)
    group = hq // hkv
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    lk_valid = lk if lk_valid is None else lk_valid

    kernel = functools.partial(
        _attn_kernel, lk_pad=lk, lk_valid=lk_valid, bk=bk, causal=causal,
        window=window, softcap=softcap, sm_scale=sm_scale, q_start_map=None)
    grid = (b_sz, hq, lq // bq)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, lk, d), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, lk, d), lambda b, h, i: (b, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
