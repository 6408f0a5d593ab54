"""Property tests for the paper's collective algorithms (sim backend ==
numpy semantics), including the non-power-of-two and subset cases the
paper notes eLib's 2D indexing cannot express."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import collectives as coll, sim_ctx
from repro.core.netops import SimNetOps

NS = st.integers(min_value=1, max_value=17)
WIDTHS = st.integers(min_value=1, max_value=9)


def _x(n, w, seed=0, dtype=np.float32):
    return jnp.asarray(
        np.random.RandomState(seed).randn(n, w).astype(dtype))


@settings(max_examples=40, deadline=None)
@given(NS, WIDTHS, st.integers(0, 16))
def test_broadcast_any_n_any_root(n, w, root_raw):
    root = root_raw % n
    x = _x(n, w)
    out = sim_ctx(n).broadcast(x, root)
    np.testing.assert_allclose(np.asarray(out),
                               np.tile(np.asarray(x)[root], (n, 1)))


@settings(max_examples=40, deadline=None)
@given(NS, WIDTHS)
def test_fcollect_matches_concat(n, w):
    x = _x(n, w)
    out = sim_ctx(n).fcollect(x)
    ref = np.tile(np.asarray(x).reshape(-1), (n, 1))
    np.testing.assert_allclose(np.asarray(out), ref)


@settings(max_examples=40, deadline=None)
@given(NS, WIDTHS)
def test_collect_ring_matches_concat(n, w):
    x = _x(n, w)
    out = sim_ctx(n).collect(x)
    ref = np.tile(np.asarray(x).reshape(-1), (n, 1))
    np.testing.assert_allclose(np.asarray(out), ref)


@settings(max_examples=60, deadline=None)
@given(NS, WIDTHS, st.sampled_from(["sum", "max", "min", "prod"]))
def test_allreduce_ops(n, w, op):
    x = _x(n, w)
    if op == "prod":
        x = jnp.abs(x) * 0.5 + 0.5
    out = sim_ctx(n).to_all(x, op)
    fn = {"sum": np.sum, "max": np.max, "min": np.min,
          "prod": np.prod}[op]
    ref = np.tile(fn(np.asarray(x), 0), (n, 1))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5)


@settings(max_examples=30, deadline=None)
@given(NS, WIDTHS)
def test_allreduce_ring_vs_rd_agree(n, w):
    """The paper's algorithm switch (dissemination pow2 / ring otherwise)
    must be invisible to the caller."""
    x = _x(n, w)
    ring = sim_ctx(n).to_all(x, "sum", algorithm="ring")
    ref = np.tile(np.asarray(x).sum(0), (n, 1))
    np.testing.assert_allclose(np.asarray(ring), ref, rtol=2e-5)
    if n & (n - 1) == 0:
        rd = sim_ctx(n).to_all(x, "sum", algorithm="rd")
        np.testing.assert_allclose(np.asarray(rd), ref, rtol=2e-5)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 9), st.integers(1, 5))
def test_alltoall_transpose(n, blk):
    x = jnp.asarray(np.random.RandomState(1).randn(n, n * blk)
                    .astype(np.float32))
    out = sim_ctx(n).alltoall(x)
    ref = np.asarray(x).reshape(n, n, blk).transpose(1, 0, 2) \
        .reshape(n, n * blk)
    np.testing.assert_allclose(np.asarray(out), ref)


@settings(max_examples=30, deadline=None)
@given(NS)
def test_exclusive_scan_sum(n):
    x = jnp.ones((n,), jnp.float32)
    out = coll.exclusive_scan(SimNetOps(n), x, "sum")
    np.testing.assert_allclose(np.asarray(out).reshape(-1), np.arange(n))


@settings(max_examples=20, deadline=None)
@given(NS)
def test_barrier_token_counts_rounds(n):
    tok = coll.barrier(SimNetOps(n))
    # dissemination: token accumulates 2^rounds - 1 contributions... the
    # important invariant is it ran ceil(log2 n) rounds and is uniform
    assert tok.shape[0] == n
    assert len(set(np.asarray(tok).tolist())) == 1


def test_reduce_scatter_roundtrip():
    for n in (2, 3, 4, 6, 8):
        x = _x(n, 12, seed=3)
        own, info = coll.reduce_scatter(SimNetOps(n), x, "sum")
        back = coll._allgather_unpad(SimNetOps(n), own, info)
        ref = np.tile(np.asarray(x).sum(0), (n, 1))
        np.testing.assert_allclose(np.asarray(back), ref, rtol=2e-5)


def test_dtype_coverage():
    for dtype in (np.float32, np.float64, np.int32):
        x = jnp.asarray((np.arange(6 * 4) % 7).reshape(6, 4).astype(dtype))
        out = sim_ctx(6).to_all(x, "sum")
        np.testing.assert_allclose(np.asarray(out),
                                   np.tile(np.asarray(x).sum(0), (6, 1)))


def test_put_get_patterns():
    n = 8
    ctx = sim_ctx(n)
    x = _x(n, 4, seed=5)
    ring = [(i, (i + 1) % n) for i in range(n)]
    out = ctx.put(x, ring)
    np.testing.assert_allclose(np.asarray(out),
                               np.roll(np.asarray(x), 1, axis=0))
    # get: every PE requests from its right neighbor == roll the other way
    out = ctx.get(x, [(i, (i + 1) % n) for i in range(n)])
    np.testing.assert_allclose(np.asarray(out),
                               np.roll(np.asarray(x), -1, axis=0))


def test_collective_bytes_parser():
    """The dry-run HLO collective parser sums operand bytes correctly."""
    from repro.launch.hlo import collective_bytes
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %x), dimensions={0}
  %ar = f32[256]{0} all-reduce(f32[256]{0} %y), to_apply=%add
  %cp = f32[16]{0} collective-permute(f32[16]{0} %z), source_target_pairs={{0,1}}
  %a2a = s8[64]{0} all-to-all(s8[64]{0} %w), dimensions={0}
  %done = f32[4]{0} all-reduce-done(f32[4]{0} %h)
"""
    out = collective_bytes(hlo)
    # payload proxy: the op's OUTPUT shape bytes (done-ops excluded)
    assert out["bytes"]["all-gather"] == 8 * 128 * 2
    assert out["bytes"]["all-reduce"] == 256 * 4
    assert out["bytes"]["collective-permute"] == 16 * 4
    assert out["bytes"]["all-to-all"] == 64
    assert out["counts"]["all-gather"] == 1
    assert out["counts"]["all-reduce"] == 1   # -done line skipped


def test_allreduce_auto_size_switch():
    """'auto' must pick ring beyond the byte threshold and stay RD below
    (pow-2 PE count), both numerically identical."""
    from repro.core import collectives as coll
    from repro.core.netops import SimNetOps
    n = 8
    small = jnp.ones((n, 16), jnp.float32)
    big = jnp.ones((n, coll.RING_BYTES_THRESHOLD // 4 + 8), jnp.float32)
    net = SimNetOps(n)
    for x in (small, big):
        auto = coll.allreduce(net, x, "sum", algorithm="auto")
        ref = np.tile(np.asarray(x).sum(0), (n, 1))
        np.testing.assert_allclose(np.asarray(auto), ref, rtol=1e-6)
