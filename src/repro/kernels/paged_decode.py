"""Paged decode attention: one new token per slot against its KV pages.

The serving engine's decode step (DESIGN.md §15) attends each slot's new
query row to every earlier position of its sequence, which lives in
fixed-size pages of a pool shared by all slots.  This kernel reads those
pages where they lie, in the stacked all-layer pool, and reads only the
pages a slot has filled:

  * the grid runs over slots; the page table, each slot's first page and
    page count, the layer index and the next slot with pages to read are
    scalar-prefetched into SMEM;
  * a slot's pages stream HBM -> VMEM in blocks of `pages_per_block`,
    one DMA per page, double-buffered: the block after the one being
    consumed, of this slot or of the next slot that has pages, is in
    flight while the current block is attended;
  * the running softmax starts from the slot's own new key/value row,
    handed in from registers (the pool holds positions < pos only; the
    caller writes the new rows into the pool after every layer has run),
    and folds each block in with f32 online-softmax rescaling;
  * a slot at position 0 (the engine's inactive slots) reads no page.

Pool layout: (layers, pages, page_size, K * hd) — a page of one layer is
one contiguous (page_size, K * hd) tile, so one DMA moves both K heads.
Queries are laid out block-diagonally, (B, Hq, K * hd) with query head
q of KV group g holding its hd values at lanes g*hd..(g+1)*hd and zeros
elsewhere, so a single (Hq, K*hd) x (K*hd, T) product scores every query
head against its own KV head (the zero lanes add exact zeros).  The
output comes back in the same layout; `paged_decode_attention` picks each
head's own lanes.

Latent mode (MLA's absorbed decode, `v_lanes`): the pool holds one latent
row [c_kv | k_rope] a position, (layers, pages, page_size, C), and every
query head scores against that one "head"; a key's value is its first
`v_lanes` lanes, so each page moves in one DMA and there is no V pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
PAGES_PER_BLOCK = 16


def page_span(positions, page_size: int, window: int | None = None):
    """(first page, page count) a decode at each position reads: the keys
    at positions [max(0, pos - window + 1), pos), i.e. every earlier
    position the causal(+window) mask admits.  Works on numpy and jax
    integer arrays alike."""
    lo = positions * 0 if window is None else \
        (positions - window + 1).clip(0)
    first = lo // page_size
    count = ((positions - 1) // page_size - first + 1) * (lo < positions)
    return first, count


def _kernel(layer_ref, first_ref, count_ref, next_ref, table_ref, pos_ref,
            *refs, page_size: int, pages_per_block: int, max_pages: int,
            window: int | None, softcap: float | None,
            v_lanes: int | None):
    if v_lanes is None:
        (q_ref, kn_ref, vn_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, ksem, vsem, buf_ref) = refs
        streams = ((k_hbm, kbuf, ksem), (v_hbm, vbuf, vsem))
    else:                         # latent: values are the keys' first lanes
        q_ref, kn_ref, k_hbm, o_ref, kbuf, ksem, buf_ref = refs
        streams = ((k_hbm, kbuf, ksem),)
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    P = pages_per_block
    layer = layer_ref[0]

    def page_copies(s, j, buf, act):
        """Start (or wait for) the DMAs of block j of slot s into buffer
        `buf`: one per page the slot has in that block."""
        for i in range(P):
            @pl.when(j * P + i < count_ref[s])
            def _():
                page = table_ref[s * max_pages + first_ref[s] + j * P + i]
                for hbm, vmem, sem in streams:
                    cp = pltpu.make_async_copy(hbm.at[layer, page],
                                               vmem.at[buf, i], sem.at[buf])
                    cp.start() if act == "start" else cp.wait()

    @pl.when(b == 0)
    def _():
        buf_ref[0] = 0
        s0 = next_ref[0]

        @pl.when(s0 < n_slots)
        def _():
            page_copies(s0, 0, 0, "start")

    pos = pos_ref[b]
    q = q_ref[...]                                       # (Hp, C) f32
    kn = kn_ref[...].astype(jnp.float32)                 # (1, C)
    vn = (vn_ref[...].astype(jnp.float32) if v_lanes is None
          else kn[:, :v_lanes])

    def cap(s):
        return s if softcap is None else softcap * jnp.tanh(s / softcap)

    # the slot's own row starts the running softmax: m = its logit, l = 1
    m0 = cap(jnp.sum(q * kn, axis=1, keepdims=True))     # (Hp, 1)
    l0 = jnp.ones_like(m0)
    acc0 = jnp.broadcast_to(vn, (q.shape[0], vn.shape[1]))
    n_blocks = (count_ref[b] + P - 1) // P
    T = P * page_size

    def body(j, carry):
        m_i, l_i, acc = carry
        buf = buf_ref[0]
        last = j + 1 == n_blocks
        ns = jnp.where(last, next_ref[b + 1], b)
        nj = jnp.where(last, 0, j + 1)

        @pl.when(ns < n_slots)
        def _():
            page_copies(ns, nj, 1 - buf, "start")

        page_copies(b, j, buf, "wait")
        buf_ref[0] = 1 - buf
        k = kbuf[buf].astype(jnp.float32).reshape(T, -1)  # (T, C)
        v = (vbuf[buf].astype(jnp.float32).reshape(T, -1) if v_lanes is None
             else k[:, :v_lanes])
        k_pos = (first_ref[b] + j * P) * page_size
        valid = k_pos + lax.broadcasted_iota(jnp.int32, (1, T), 1) < pos
        valid_rows = k_pos + lax.broadcasted_iota(jnp.int32, (T, 1), 0) < pos
        if window is not None:
            valid &= k_pos + lax.broadcasted_iota(
                jnp.int32, (1, T), 1) > pos - window
        s = cap(lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32))
        s = jnp.where(valid, s, NEG_INF)                 # (Hp, T)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=1, keepdims=True)
        # pages past the slot's count are never loaded: zero their rows
        # so stale VMEM cannot reach the product through 0 * NaN
        v = jnp.where(valid_rows, v, 0.0)
        acc = alpha * acc + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    _, l_i, acc = lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    o_ref[...] = acc / l_i


def paged_decode_attention(q, k_new, v_new, k_pool, v_pool, layer,
                           page_table, positions, *, page_size: int,
                           window: int | None = None,
                           softcap: float | None = None,
                           pages_per_block: int = PAGES_PER_BLOCK,
                           interpret: bool = False,
                           v_lanes: int | None = None):
    """Decode attention of one new row per slot against its paged KV.

    q: (B, Hq, hd) float32, already scaled by 1/sqrt(hd); k_new, v_new:
    (B, K, hd) the slot's new key/value row (rounded to the pool's dtype
    here, as the pool would store it); k_pool, v_pool: (layers, pages,
    page_size, K * hd); layer: int32 scalar; page_table: (B, max_pages)
    int32; positions: (B,) int32, the new row's position.  Keys are the
    pool's positions [max(0, pos - window + 1), pos) of `layer` plus the
    new row.  Returns (B, Hq, hd) float32.

    Latent mode (`v_lanes`, MLA's absorbed decode): one KV "head" of C
    lanes, k_pool the latent pool (layers, pages, page_size, C) and k_new
    (B, 1, C) the new latent row; v_new and v_pool are None.  A key's
    value is its first `v_lanes` lanes, read from the same page, so each
    page moves in one DMA.  q: (B, Hq, C); returns (B, Hq, v_lanes)."""
    if v_lanes is not None:
        return _latent(q, k_new, k_pool, layer, page_table, positions,
                       page_size=page_size, window=window, softcap=softcap,
                       pages_per_block=pages_per_block, interpret=interpret,
                       v_lanes=v_lanes)
    B, Hq, hd = q.shape
    C = k_pool.shape[-1]
    K = C // hd
    G = Hq // K
    Hp = -(-Hq // 8) * 8
    max_pages = page_table.shape[1]
    eye = jnp.eye(K, dtype=jnp.float32)
    q_bd = (q.reshape(B, K, G, 1, hd) * eye[None, :, None, :, None]) \
        .reshape(B, Hq, C)
    q_bd = jnp.pad(q_bd, ((0, 0), (0, Hp - Hq), (0, 0)))
    kn = k_new.astype(k_pool.dtype).reshape(B, 1, C)
    vn = v_new.astype(v_pool.dtype).reshape(B, 1, C)

    row = pl.BlockSpec((None, Hp, C), lambda b, *_: (b, 0, 0))
    new = pl.BlockSpec((None, 1, C), lambda b, *_: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, pages_per_block, page_size, C), k_pool.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size,
                          pages_per_block=pages_per_block,
                          max_pages=max_pages, window=window,
                          softcap=softcap, v_lanes=None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(B,),
            in_specs=[row, new, new, hbm, hbm], out_specs=row,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hp, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(*_scalars(layer, page_table, positions, page_size, window),
      q_bd, kn, vn, k_pool, v_pool)
    # each query head keeps the lanes of its own KV head
    out = out[:, :Hq].reshape(B, K, G, K, hd)
    return jnp.stack([out[:, g, :, g] for g in range(K)], axis=1) \
        .reshape(B, Hq, hd)


def _scalars(layer, page_table, positions, page_size, window):
    """The scalar-prefetch operands: layer, first page and page count of
    each slot, the next slot with pages to read, the flat table and the
    positions."""
    B = positions.shape[0]
    first, count = page_span(positions, page_size, window)
    # next_slot[b]: the first slot >= b with pages to read (B if none)
    idx = jnp.where(count > 0, jnp.arange(B, dtype=jnp.int32), B)
    next_slot = jnp.append(lax.cummin(idx, reverse=True), B)
    return (jnp.reshape(layer, (1,)).astype(jnp.int32),
            first.astype(jnp.int32), count.astype(jnp.int32),
            next_slot.astype(jnp.int32),
            page_table.reshape(-1).astype(jnp.int32),
            positions.astype(jnp.int32))


def _latent(q, k_new, pool, layer, page_table, positions, *, page_size,
            window, softcap, pages_per_block, interpret, v_lanes):
    """The latent mode of `paged_decode_attention`: q (B, Hq, C) against
    one KV head of C lanes whose values are its first v_lanes lanes."""
    B, Hq, C = q.shape
    Hp = -(-Hq // 8) * 8
    max_pages = page_table.shape[1]
    q = jnp.pad(q, ((0, 0), (0, Hp - Hq), (0, 0)))
    kn = k_new.astype(pool.dtype).reshape(B, 1, C)
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size,
                          pages_per_block=pages_per_block,
                          max_pages=max_pages, window=window,
                          softcap=softcap, v_lanes=v_lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(B,),
            in_specs=[pl.BlockSpec((None, Hp, C), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((None, 1, C), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, Hp, v_lanes),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages_per_block, page_size, C), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hp, v_lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention_latent",
    )(*_scalars(layer, page_table, positions, page_size, window), q, kn,
      pool)
    return out[:, :Hq]
