"""Mamba2 SSD (state-space duality) chunked scan as a Pallas kernel.

The hot spot of the `mamba2-2.7b` / `zamba2-1.2b` architectures.  The SSD
trick: split the sequence into chunks of Q steps; inside a chunk the SSM is
a (masked, decay-weighted) attention-like matmul that feeds the MXU, and
only the chunk boundary states recur — the sequential dependency shrinks
from L steps to L/Q.

For each (batch, head) the grid walks the chunks in order through VMEM,
carrying the (P, N) state in an f32 accumulator:

  decay     s_t   = cumsum(A * dt)                within chunk
  intra     y    += ((C B^T) * exp(s_t - s_u) * dt_u, masked u<=t) @ x
  inter     y    += exp(s_t) * (C @ state^T)
  state     h'    = exp(s_Q) h + (x * dt * exp(s_Q - s_u))^T @ B

All matmuls are (Q x N)(N x Q), (Q x Q)(Q x P), (P x Q)(Q x N) with
Q = N = 128 by default — MXU-shaped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_CHUNK = 128


def _ssd_kernel(x_ref, dt_ref, adt_ref, b_ref, c_ref, h0_ref, y_ref, h_ref):
    # one (batch, head, chunk) grid cell; h_ref's block index ignores the
    # chunk axis, so it stays resident in VMEM and carries the (P, N)
    # state from chunk to chunk
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    x = x_ref[0, 0].astype(jnp.float32)                       # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                     # (1, Q)
    a_dt = adt_ref[0, 0]                                      # (1, Q) <= 0
    bm = b_ref[0, 0].astype(jnp.float32)                      # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)                      # (Q, N)
    state = h_ref[0, 0]                                       # (P, N)
    chunk = x.shape[0]

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    u_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = t_idx >= u_idx
    diag = t_idx == u_idx
    # s_t = cumsum(A dt) as a column, then as a row; masked reductions
    # stand in for cumsum and for (Q,1) <-> (1,Q) transposes
    s_col = jnp.sum(jnp.where(tri, a_dt, 0.0), axis=1, keepdims=True)
    s_row = jnp.sum(jnp.where(diag, s_col, 0.0), axis=0, keepdims=True)
    dt_col = jnp.sum(jnp.where(diag, dt, 0.0), axis=1, keepdims=True)
    s_last = jnp.sum(a_dt, axis=1, keepdims=True)             # (1, 1)

    # intra-chunk: M[t,u] = exp(s_t - s_u) * dt_u * (C_t . B_u), u <= t
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    # mask in the exponent: exp(+large) in the t<u triangle is inf
    decay = jnp.exp(jnp.where(tri, s_col - s_row, -1e30))
    m = cb * decay * dt
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,P)

    # inter-chunk: exp(s_t) * C_t . state (state: (P,N))
    y += jnp.exp(s_col) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update
    w = x * (dt_col * jnp.exp(s_last - s_col))                # (Q,P)
    h_ref[0, 0] = jnp.exp(s_last) * state + jax.lax.dot_general(
        w, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # (P,N)
    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, a_log, b_mat, c_mat, h0=None, *,
             chunk: int = DEFAULT_CHUNK, interpret: bool = False):
    """x: (B, L, H, P); dt: (B, L, H); a_log: (H,) (negative);
    b_mat, c_mat: (B, L, G, N) with H % G == 0; h0: (B, H, P, N) or None.
    L % chunk == 0 (ops.py pads).  Returns (y, h_final).

    The kernel sees head-major layouts, (B, H, L, P) and (B, H, 1, L), so
    every block's last two axes are whole or tile-aligned."""
    bsz, length, h, p = x.shape
    _, _, g, n = b_mat.shape
    group = h // g
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    dt_h = dt.transpose(0, 2, 1)[:, :, None, :]               # (B,H,1,L)
    a_dt = a_log.astype(jnp.float32)[None, :, None, None] \
        * dt_h.astype(jnp.float32)

    grid = (bsz, h, length // chunk)
    seq = lambda w: pl.BlockSpec((1, 1, chunk, w),
                                 lambda b, hh, c: (b, hh, c, 0))
    row = pl.BlockSpec((1, 1, 1, chunk), lambda b, hh, c: (b, hh, 0, c))
    grp = pl.BlockSpec((1, 1, chunk, n),
                       lambda b, hh, c: (b, hh // group, c, 0))
    state = pl.BlockSpec((1, 1, p, n), lambda b, hh, c: (b, hh, 0, 0))
    y, hout = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[seq(p), row, row, grp, grp, state],
        out_specs=[seq(p), state],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, length, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt_h, a_dt, b_mat.transpose(0, 2, 1, 3),
      c_mat.transpose(0, 2, 1, 3), h0)
    return y.transpose(0, 2, 1, 3), hout
