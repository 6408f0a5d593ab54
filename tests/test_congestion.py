"""Congestion-aware routing model, mesh-embedded collectives, rank remap.

Covers DESIGN.md §12: XY route enumeration and its invariants, per-link
load accounting (the acceptance inequality: snake ring strictly less
congested than the logical ring on the paper's 4x4), the congestion-priced
cost model, the wave-serial NoC simulator's bit-identity, the embedded
ring/collect executors (bitwise for data movement and int reductions,
allclose for floats), selector property tests on odd/non-square meshes,
and the greedy rank-remap pass.  SPMD coverage runs in a subprocess like
test_team/test_overlap.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax.numpy as jnp

from repro.core import abmodel, collectives as coll, sim_ctx
from repro.core import team as team_mod
from repro.core.netops import NocSimNetOps, SimNetOps
from repro.core.pattern import Stage, compile_pattern, ring_pattern
from repro.core.topology import MeshTopology, epiphany3, v5e_pod

TOPO = epiphany3()
N = TOPO.n_pes

MESHES = [
    epiphany3(),
    MeshTopology((3, 5), torus=(False, False)),
    MeshTopology((2, 7), torus=(False, False)),
    MeshTopology((1, 8), torus=(False, False)),
]
MESH_IDS = ["4x4", "3x5", "2x7", "1x8"]


# ---------------------------------------------------------------------------
# topology: validation (the zip-truncation bugfix), routes, snake orders
# ---------------------------------------------------------------------------

def test_topology_validation_rejects_mismatched_tuples():
    with pytest.raises(ValueError, match="torus"):
        MeshTopology((4, 4), torus=(False,))
    with pytest.raises(ValueError, match="link_cost"):
        MeshTopology((4, 4), link_cost=(1.0,))
    with pytest.raises(ValueError, match="extent"):
        MeshTopology((0, 4))
    with pytest.raises(ValueError, match="extent"):
        MeshTopology(())
    MeshTopology((4, 4), torus=(False, True), link_cost=(1.0, 2.0))  # ok


@pytest.mark.parametrize("topo", MESHES, ids=MESH_IDS)
def test_route_is_neighbor_steps_summing_to_hops(topo):
    for a in range(0, topo.n_pes, 3):
        for b in range(0, topo.n_pes, 2):
            r = topo.route(a, b)
            # contiguous: starts at a, ends at b, neighbor steps
            if a == b:
                assert r == ()
                continue
            assert r[0][0] == a and r[-1][1] == b
            for (u, v), (u2, _) in zip(r, r[1:]):
                assert v == u2
            for u, v in r:
                assert topo.hops(u, v) == topo.link_weight(u, v)
            assert sum(topo.link_weight(u, v) for u, v in r) \
                == pytest.approx(topo.hops(a, b))


def test_route_torus_takes_short_way_around():
    t = v5e_pod()
    wrap = t.route(t.rank((0, 15)), t.rank((0, 0)))
    assert len(wrap) == 1                 # one wrap hop, not 15 interior


def test_route_is_cached():
    assert TOPO.route(0, 15) is TOPO.route(0, 15)


@pytest.mark.parametrize("topo", MESHES + [v5e_pod()],
                         ids=MESH_IDS + ["16x16torus"])
def test_snake_order_is_hamiltonian(topo):
    order = topo.snake_order()
    assert sorted(order) == list(range(topo.n_pes))
    hops = [topo.hops(order[i], order[i + 1])
            for i in range(topo.n_pes - 1)]
    assert all(h == 1.0 for h in hops)    # interior edges: one physical hop


def test_snake_order_closes_cycle_when_possible():
    # 4x4 (even extent) and the full torus admit Hamiltonian cycles
    for topo in (epiphany3(), MeshTopology((2, 7), torus=(False, False)),
                 v5e_pod()):
        order = topo.snake_order()
        assert topo.hops(order[-1], order[0]) == 1.0, topo


# ---------------------------------------------------------------------------
# link loads: the congestion metric (and the acceptance inequality)
# ---------------------------------------------------------------------------

def test_link_loads_counts_funneled_flows():
    # i -> i+8 moves every PE two rows down its own column: successive
    # flows overlap on the middle vertical links in both directions
    p = ring_pattern(N, 8)
    loads = p.link_loads(TOPO)
    assert max(loads.values()) == 4.0     # two directions x two flows
    assert p.max_link_load(TOPO) == 4.0
    assert p.link_loads(TOPO) is loads    # interned per (pattern, topo)


def test_disjoint_neighbor_flows_are_load_one():
    p = compile_pattern([(i, i + 1) for i in range(0, N, 2)], N)
    assert p.max_link_load(TOPO) == 1.0


def test_flat_network_load_is_one():
    assert ring_pattern(N).max_link_load(None) == 1.0


def test_link_loads_are_unweighted_multiplicity():
    # a single uncontended flow over an expensive cross-pod link is still
    # load 1 — per-dimension costs belong to the hop term only
    t = MeshTopology((2, 4), torus=(False, False), link_cost=(10.0, 1.0))
    assert compile_pattern([(0, 4)], 8).max_link_load(t) == 1.0


def test_fcollect_explicit_ring_emb_defaults_to_snake(monkeypatch):
    """Explicit algorithm="ring_emb" without the knob embeds (snake), as
    allreduce does — asserted structurally (embedded vs logical fcollect
    are bitwise identical, so output equality alone would be vacuous)."""
    calls = []
    real = coll._collect_ring_embedded

    def spy(net, x, axis, order, n_chunks=1):
        calls.append(tuple(order))
        return real(net, x, axis, order, n_chunks=n_chunks)

    monkeypatch.setattr(coll, "_collect_ring_embedded", spy)
    ctx2 = sim_ctx(N, TOPO)
    x = jnp.asarray(np.random.RandomState(4).randn(N, 8).astype(np.float32))
    out = np.asarray(ctx2.fcollect(x, algorithm="ring_emb"))
    np.testing.assert_array_equal(
        out, np.asarray(ctx2.fcollect(x, algorithm="ring")))
    assert calls == [TOPO.snake_order()]


def test_snake_ring_strictly_less_congested_than_logical():
    """The acceptance inequality on the paper's chip: the snake-embedded
    ring touches every physical link at most once; the logical rank+1
    ring contends on the row-wrap columns."""
    logical = ring_pattern(N)
    embedded = logical.relabel(TOPO.snake_order(), N)
    assert embedded.max_link_load(TOPO) < logical.max_link_load(TOPO)
    assert embedded.max_link_load(TOPO) == 1.0
    # and the congestion-priced model predicts the embedded ring faster
    emb_sched = coll.allreduce_schedule(N, float(1 << 20), "ring_emb",
                                        embedding=TOPO.snake_order())
    log_sched = coll.allreduce_schedule(N, float(1 << 20), "ring")
    link = abmodel.EPIPHANY_NOC
    assert emb_sched.time(TOPO, link) < log_sched.time(TOPO, link)


def test_team_topology_routes_price_like_lifted():
    rows = team_mod.split_2d(team_mod.team_world(16), TOPO, -1)
    row1 = rows.teams[1]
    tt = row1.topo_view(TOPO)
    sched = coll.allreduce_schedule(4, 4096.0, "ring")
    assert sched.time(tt, abmodel.EPIPHANY_NOC) == pytest.approx(
        row1.lift_schedule(sched).time(TOPO, abmodel.EPIPHANY_NOC))


# ---------------------------------------------------------------------------
# cost model: the congestion term
# ---------------------------------------------------------------------------

def test_stage_cost_carries_link_load():
    st = Stage(ring_pattern(N), 1024.0)
    b, h, load = st.cost(TOPO)
    assert (b, load) == (1024.0, 2.0)
    assert st.cost(None)[2] == 1.0


def test_linkmodel_prices_serialization():
    link = abmodel.LinkModel(alpha_s=0.0, hop_s=0.0, bw_Bps=1e9)
    assert link.time(1e6, 1.0, 2.0) == pytest.approx(2 * link.time(1e6, 1.0))
    half = abmodel.LinkModel(alpha_s=0.0, hop_s=0.0, bw_Bps=1e9,
                             contention=0.5)
    assert half.time(1e6, 1.0, 3.0) == pytest.approx(2 * half.time(1e6, 1.0))


def test_model_accepts_legacy_two_tuples():
    stages2 = [(100.0, 1.0), (200.0, 2.0)]
    stages3 = [(100.0, 1.0, 1.0), (200.0, 2.0, 1.0)]
    assert abmodel.modeled_collective_time(stages2) == pytest.approx(
        abmodel.modeled_collective_time(stages3))
    assert abmodel.modeled_pipelined_time(stages2, 4) == pytest.approx(
        abmodel.modeled_pipelined_time(stages3, 4))


def test_fit_contention_recovers_gamma():
    for gamma in (0.0, 0.4, 1.0):
        loads = [1.0, 2.0, 4.0]
        times = [1e-3 * (1 + gamma * (l - 1)) for l in loads]
        assert abmodel.fit_contention(loads, times) == pytest.approx(
            gamma, abs=1e-9)


# ---------------------------------------------------------------------------
# NocSimNetOps: wave-serial execution is bit-identical
# ---------------------------------------------------------------------------

def test_link_waves_cover_pattern_disjointly():
    p = ring_pattern(N)
    waves = p.link_waves(TOPO)
    assert len(waves) == 2                # == max_link_load on the 4x4
    seen = sorted(pair for w in waves for pair in w.pairs)
    assert seen == sorted(p.pairs)
    emb = p.relabel(TOPO.snake_order(), N)
    assert len(emb.link_waves(TOPO)) == 1


def test_nocsim_bit_identical_to_sim():
    rng = np.random.RandomState(0)
    sim, noc = SimNetOps(N), NocSimNetOps(N, topo=TOPO)
    x = jnp.asarray(rng.randn(N, 13).astype(np.float32))
    xb = jnp.asarray(rng.rand(N, 7) > 0.5)
    for p in (ring_pattern(N), ring_pattern(N, 8),
              ring_pattern(N).relabel(TOPO.snake_order(), N)):
        np.testing.assert_array_equal(np.asarray(sim.ppermute(x, p)),
                                      np.asarray(noc.ppermute(x, p)))
        np.testing.assert_array_equal(np.asarray(sim.ppermute(xb, p)),
                                      np.asarray(noc.ppermute(xb, p)))


def test_nocsim_empty_pattern_returns_zeros():
    noc = NocSimNetOps(N, topo=TOPO)
    x = jnp.ones((N, 3), jnp.float32)
    out = np.asarray(noc.ppermute(x, []))
    np.testing.assert_array_equal(out, np.zeros_like(out))


def test_nocsim_preserves_narrow_dtypes():
    rng = np.random.RandomState(2)
    sim, noc = SimNetOps(N), NocSimNetOps(N, topo=TOPO)
    for dtype in (np.int8, np.uint8, np.int16):
        x = jnp.asarray(rng.randint(0, 100, (N, 9)).astype(dtype))
        a, b = sim.ppermute(x, ring_pattern(N)), noc.ppermute(x, ring_pattern(N))
        assert b.dtype == a.dtype == dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nocsim_full_collectives_match():
    rng = np.random.RandomState(1)
    xi = jnp.asarray(rng.randint(-99, 99, (N, 33)).astype(np.int32))
    a = sim_ctx(N, TOPO)
    b = sim_ctx(N, TOPO, noc=True)
    for algo in ("ring", "rd", "ring_emb"):
        np.testing.assert_array_equal(
            np.asarray(a.to_all(xi, "sum", algorithm=algo)),
            np.asarray(b.to_all(xi, "sum", algorithm=algo)))


# ---------------------------------------------------------------------------
# mesh-embedded collectives
# ---------------------------------------------------------------------------

@pytest.fixture
def ctx():
    return sim_ctx(N, TOPO)


def _f32(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


def _i32(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed)
                       .randint(-99, 99, shape).astype(np.int32))


def test_embedded_allreduce_int_bit_identical(ctx):
    """Integer reductions are associative exactly: the embedded ring must
    be BITWISE equal to the logical ring and the plain sum."""
    x = _i32((N, 41))
    ref = np.asarray(ctx.to_all(x, "sum", algorithm="ring"))
    for chunks in (None, 4):
        out = np.asarray(ctx.to_all(x, "sum", algorithm="ring_emb",
                                    pipeline_chunks=chunks))
        np.testing.assert_array_equal(out, ref)


def test_embedded_allreduce_float_allclose(ctx):
    x = _f32((N, 129))
    ref = np.broadcast_to(np.asarray(x).sum(0), x.shape)
    for chunks in (None, 8):
        out = np.asarray(ctx.to_all(x, "sum", algorithm="ring_emb",
                                    pipeline_chunks=chunks))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_embedded_fcollect_collect_bitwise(ctx):
    """Pure data movement: embedded and logical rings must agree BITWISE
    (block order restored by the static post-permutation)."""
    x = _f32((N, 3, 5))
    np.testing.assert_array_equal(
        np.asarray(ctx.fcollect(x, algorithm="ring")),
        np.asarray(ctx.fcollect(x, algorithm="ring_emb")))
    emb_ctx = sim_ctx(N, TOPO, embedding="snake")
    np.testing.assert_array_equal(
        np.asarray(ctx.collect(x)),
        np.asarray(emb_ctx.collect(x)))


def test_embedded_fcollect_collect_chunked_bitwise(ctx):
    """pipeline_chunks reaches the embedded ring too (the embedding team
    covers the world, so the chunked pipeline applies) and stays bitwise
    identical to the monolithic logical ring."""
    x = _f32((N, 12), seed=9)
    ref = np.asarray(ctx.fcollect(x, algorithm="ring"))
    np.testing.assert_array_equal(
        np.asarray(ctx.fcollect(x, algorithm="ring_emb",
                                pipeline_chunks=4)), ref)
    emb_ctx = sim_ctx(N, TOPO, embedding="snake")
    np.testing.assert_array_equal(
        np.asarray(emb_ctx.collect(x, pipeline_chunks=3)),
        np.asarray(ctx.collect(x)))


def test_fcollect_auto_with_team_is_team_priced(ctx):
    """algorithm='auto' under a team must price (and run) team-relative
    candidates — result equals the fixed-algorithm team fcollect."""
    x = _f32((N, 2, 4), seed=11)
    t = team_mod.make_team((0, 1, 4, 5), N)
    out = np.asarray(coll.fcollect(ctx.net, x, algorithm="auto", team=t,
                                   topo=TOPO, link=abmodel.EPIPHANY_NOC))
    fixed = np.asarray(coll.fcollect(ctx.net, x, algorithm="rd", team=t))
    np.testing.assert_allclose(out, fixed, rtol=1e-6, atol=1e-6)


def test_team_fcollect_collect_embedded_bitwise(ctx):
    """Team-scoped embedded fcollect/collect run the ring over the
    snake-reordered team but restore the ORIGINAL team-rank block order —
    bitwise identical to the plain team path, non-members still zero."""
    x = _f32((N, 2, 3), seed=13)
    cols = team_mod.split_2d(team_mod.team_world(N), TOPO, 0)
    t = cols.teams[0]
    # column 0 is genuinely reordered by the snake (0,12,8,4) — the
    # static block-order restore is exercised, not the identity fallback
    assert coll.embed_team(t, TOPO) is not t
    ref = np.asarray(coll.fcollect(ctx.net, x, team=t))
    np.testing.assert_array_equal(
        np.asarray(coll.fcollect(ctx.net, x, algorithm="ring_emb",
                                 team=t, topo=TOPO)), ref)
    np.testing.assert_array_equal(
        np.asarray(coll.collect(ctx.net, x, team=t, topo=TOPO,
                                embedding="snake")),
        np.asarray(coll.collect(ctx.net, x, team=t)))


def test_embedding_knob_on_context(ctx):
    x = _i32((N, 17), seed=3)
    ref = np.asarray(ctx.to_all(x, "sum"))
    for emb in ("snake", "auto", tuple(TOPO.snake_order())):
        ectx = sim_ctx(N, TOPO, embedding=emb)
        np.testing.assert_array_equal(
            np.asarray(ectx.to_all(x, "sum", algorithm="ring")), ref)
        # default policy embeds the ring; explicit "ring" stays logical
        np.testing.assert_array_equal(np.asarray(ectx.to_all(x, "sum")), ref)


def test_bad_embedding_rejected(ctx):
    with pytest.raises(ValueError, match="permutation"):
        coll.allreduce(ctx.net, _i32((N, 4)), embedding=(0,) * N, topo=TOPO)
    with pytest.raises(ValueError, match="unknown embedding"):
        coll.allreduce(ctx.net, _i32((N, 4)), embedding="zigzag", topo=TOPO)


def test_embedded_team_allreduce(ctx):
    """Teams compose: the embedding reorders members in TEAM coordinates
    (embed_team), non-members stay untouched."""
    x = _f32((N, 21), seed=5)
    cols = team_mod.split_2d(team_mod.team_world(N), TOPO, 0)
    col0 = cols.teams[0]
    out = np.asarray(coll.allreduce(ctx.net, x, team=col0,
                                    algorithm="auto", topo=TOPO,
                                    embedding="auto"))
    ref = np.asarray(x).copy()
    ref[list(col0.members)] = np.asarray(x)[list(col0.members)].sum(0)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_explicit_ring_emb_defaults_to_snake(ctx):
    """algorithm="ring_emb" without the embedding knob must still embed
    (snake default) — on both the flat path (any pipeline depth, chunk
    count priced on the embedded stages) and the team path."""
    x = _i32((N, 19), seed=7)
    ref = np.asarray(ctx.to_all(x, "sum"))
    for chunks in (None, "auto", 4):
        np.testing.assert_array_equal(
            np.asarray(ctx.to_all(x, "sum", algorithm="ring_emb",
                                  pipeline_chunks=chunks)), ref)
    cols = team_mod.split_2d(team_mod.team_world(N), TOPO, 0)
    col0 = cols.teams[0]
    out = np.asarray(coll.allreduce(ctx.net, x, team=col0,
                                    algorithm="ring_emb", topo=TOPO))
    # must equal the explicitly reordered team's ring bitwise
    view = coll.embed_team(col0, TOPO)
    fixed = np.asarray(coll.allreduce(ctx.net, x, team=view,
                                      algorithm="ring"))
    np.testing.assert_array_equal(out, fixed)


def test_embedded_hier_allreduce(ctx):
    x = _f32((N, 37), seed=6)
    rows = team_mod.split_2d(team_mod.team_world(N), TOPO, -1)
    ref = np.broadcast_to(np.asarray(x).sum(0), x.shape)
    out = np.asarray(coll.allreduce(ctx.net, x, algorithm="hier",
                                    partition=rows, topo=TOPO,
                                    embedding="snake"))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_hier_honors_explicit_embedding_order(ctx):
    """An explicit world order reaches the hierarchical path's member
    teams (not silently replaced by the snake), and the result stays
    correct."""
    rows = team_mod.split_2d(team_mod.team_world(N), TOPO, -1)
    rev = tuple(reversed(TOPO.snake_order()))
    emb_part = coll._embed_partition(rows, TOPO, embedding=rev)
    pos = {pe: i for i, pe in enumerate(rev)}
    for orig, emb in zip(rows.teams, emb_part.teams):
        assert sorted(emb.members) == sorted(orig.members)
        assert list(emb.members) == sorted(orig.members,
                                           key=lambda p: pos[p])
    x = _f32((N, 23), seed=15)
    out = np.asarray(coll.allreduce(ctx.net, x, algorithm="hier",
                                    partition=rows, topo=TOPO,
                                    embedding=rev))
    ref = np.broadcast_to(np.asarray(x).sum(0), x.shape)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_choose_barrier_prices_lifted_team_schedules():
    """Team barrier "auto" must price the world flows that execute, not
    team ranks read as world PEs."""
    t = team_mod.split_strided(team_mod.team_world(N), 0, 5, 4)
    link = abmodel.EPIPHANY_NOC
    pick = coll.choose_barrier(t.size, TOPO, link, team=t)
    priced = {a: t.lift_schedule(coll.barrier_schedule(t.size, a))
              .time(TOPO, link) for a in ("dissem", "tree")}
    assert priced[pick] == min(priced.values())


def test_tree_barrier_token_matches_dissemination(ctx):
    one = jnp.ones((N,), jnp.int32)
    tok_tree = np.asarray(ctx.barrier(token=one, algorithm="tree"))
    assert (tok_tree == N).all()          # gather+bcast: everyone sees all
    tok_auto = np.asarray(ctx.barrier(token=one, algorithm="auto"))
    assert tok_auto.shape == tok_tree.shape
    with_team = team_mod.make_team((0, 3, 5, 9), N)
    tok_team = np.asarray(ctx.barrier(token=one, team=with_team,
                                      algorithm="tree"))
    assert len({int(tok_team[m]) for m in with_team.members}) == 1


# ---------------------------------------------------------------------------
# selector property tests on odd / non-square / degenerate meshes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo", MESHES[1:], ids=MESH_IDS[1:])
@pytest.mark.parametrize("nbytes", [64.0, float(1 << 16), float(1 << 21)])
def test_choose_schedule_execution_equivalent_on_odd_meshes(topo, nbytes):
    """Whatever (algorithm, chunks) the congestion-priced selector picks
    on an odd/non-square mesh, executing it must equal the eager flat
    allreduce — exactly for ints, allclose for floats."""
    n = topo.n_pes
    link = abmodel.EPIPHANY_NOC
    algo, chunks = coll.choose_schedule(n, nbytes, topo, link,
                                        embedding="auto")
    ctx2 = sim_ctx(n, topo)
    xi = _i32((n, 29), seed=int(nbytes) % 97)
    refi = np.broadcast_to(np.asarray(xi).sum(0), xi.shape)
    outi = np.asarray(ctx2.to_all(xi, "sum", algorithm=algo,
                                  pipeline_chunks=chunks))
    np.testing.assert_array_equal(outi, refi)
    xf = _f32((n, 29), seed=int(nbytes) % 89)
    reff = np.broadcast_to(np.asarray(xf).sum(0), xf.shape)
    outf = np.asarray(ctx2.to_all(xf, "sum", algorithm=algo,
                                  pipeline_chunks=chunks))
    np.testing.assert_allclose(outf, reff, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("topo", MESHES, ids=MESH_IDS)
def test_choose_algorithm_pick_is_cheapest_candidate(topo):
    n = topo.n_pes
    link = abmodel.EPIPHANY_NOC
    for nbytes in (8.0, float(1 << 20)):
        emb = coll.choose_embedding(n, topo, link)
        algo = coll.choose_algorithm(n, nbytes, topo, link,
                                     embedding="auto")
        priced = {"ring": coll.allreduce_schedule(n, nbytes, "ring")
                  .time(topo, link)}
        if n & (n - 1) == 0:
            priced["rd"] = coll.allreduce_schedule(n, nbytes, "rd") \
                .time(topo, link)
        if emb is not None:
            priced["ring_emb"] = coll.allreduce_schedule(
                n, nbytes, "ring_emb", embedding=emb).time(topo, link)
        assert priced[algo] == min(priced.values())


def test_choose_schedule_picks_embedded_ring_large_on_epiphany():
    """The acceptance configuration: on the 4x4 at large payloads the
    congestion-priced selector must take the embedded ring."""
    algo, chunks = coll.choose_schedule(N, float(1 << 20), TOPO,
                                        abmodel.EPIPHANY_NOC,
                                        embedding="auto")
    assert algo == "ring_emb"
    small_algo, _ = coll.choose_schedule(N, 64.0, TOPO,
                                         abmodel.EPIPHANY_NOC,
                                         embedding="auto")
    assert small_algo in ("rd", "ring")


# ---------------------------------------------------------------------------
# rank remapping
# ---------------------------------------------------------------------------

def test_optimize_embedding_monotone_and_valid():
    sched = coll.allreduce_schedule(N, float(1 << 20), "ring")
    link = abmodel.EPIPHANY_NOC
    remapped, perm = coll.optimize_embedding(sched, TOPO, link)
    assert sorted(perm) == list(range(N))
    assert remapped.time(TOPO, link) <= sched.time(TOPO, link) + 1e-15
    assert max(st.pattern.max_link_load(TOPO) for st in remapped.stages) \
        <= max(st.pattern.max_link_load(TOPO) for st in sched.stages)


def test_choose_embedding_beats_identity_on_epiphany():
    order = coll.choose_embedding(N, TOPO, abmodel.EPIPHANY_NOC)
    assert order is not None
    ring = ring_pattern(N).relabel(order, N)
    assert ring.max_link_load(TOPO) == 1.0
    # 1D line: identity IS the snake; no embedding to pick
    line = MeshTopology((8,), torus=(False,))
    assert coll.choose_embedding(8, line, abmodel.EPIPHANY_NOC) is None


def test_embedding_cache_interns_teams():
    t1 = coll.embedding_team("snake", TOPO, N)
    t2 = coll.embedding_team("snake", TOPO, N)
    assert t1 is t2 and t1.members == TOPO.snake_order()


# ---------------------------------------------------------------------------
# SPMD backend (subprocess, 8 host devices, 2x4 mesh)
# ---------------------------------------------------------------------------

SPMD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import collectives as coll, spmd_ctx
from repro.core.topology import MeshTopology
from repro.parallel.comm import AxisSpec, Comm

topo = MeshTopology((2, 4), torus=(False, False))
mesh = jax.make_mesh((8,), ("pe",))
x = np.arange(8 * 6, dtype=np.int32).reshape(8, 6)
xf = np.random.RandomState(0).randn(8, 6).astype(np.float32)

def run(fn, v):
    g = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P("pe"),),
                              out_specs=P("pe"), check_vma=False))
    return np.asarray(g(v))

def emb_int(v):
    ctx = spmd_ctx("pe", topo, embedding="snake")
    return ctx.to_all(v, "sum", algorithm="ring_emb")

def log_int(v):
    ctx = spmd_ctx("pe", topo)
    return ctx.to_all(v, "sum", algorithm="ring")

a, b = run(emb_int, x), run(log_int, x)
assert np.array_equal(a, b), (a, b)

def emb_fc(v):
    ctx = spmd_ctx("pe", topo, embedding="auto")
    return ctx.fcollect(v)

def log_fc(v):
    ctx = spmd_ctx("pe", topo)
    return ctx.fcollect(v)

a, b = run(emb_fc, xf), run(log_fc, xf)
assert np.array_equal(a, b), "embedded fcollect must be bitwise identical"

def comm_emb(v):
    c = Comm(AxisSpec(data="pe", model=None), "shmem",
             allreduce_algo="auto", topo=topo, embedding="auto")
    return c.allreduce(v, "pe")

out = run(comm_emb, xf)
ref = np.broadcast_to(xf.sum(0), xf.shape)
assert np.allclose(out, ref, rtol=1e-5, atol=1e-5)

# grad sync in embedded coordinates: the reduce-scatter + allgather pair
# and the bucketed interleave (incl. _hier_wins' embedded-flat pricing)
def gs_emb(v):
    c = Comm(AxisSpec(data="pe", model=None), "shmem", grad_rs=True,
             topo=topo, embedding="snake")
    return c.grad_sync(v, mean=True)

def gs_bucketed(v):
    c = Comm(AxisSpec(data="pe", model=None), "shmem",
             allreduce_algo="auto", topo=topo, embedding="snake")
    return tuple(c.grad_sync_bucketed([v, v * 2.0], mean=True))

mref = np.broadcast_to(xf.mean(0), xf.shape)
assert np.allclose(run(gs_emb, xf), mref, rtol=1e-5, atol=1e-5)
b1, b2 = jax.jit(jax.shard_map(gs_bucketed, mesh=mesh, in_specs=(P("pe"),),
                               out_specs=(P("pe"), P("pe")),
                               check_vma=False))(xf)
assert np.allclose(np.asarray(b1), mref, rtol=1e-5, atol=1e-5)
assert np.allclose(np.asarray(b2), 2.0 * mref, rtol=1e-5, atol=1e-5)

def tree_barrier(v):
    ctx = spmd_ctx("pe", topo)
    tok = ctx.barrier(token=jnp.ones((), jnp.int32), algorithm="tree")
    return v + tok[None].astype(v.dtype) * 0

assert run(tree_barrier, x).shape == x.shape

# an explicit data-axis rank order must NOT leak to the pod axis (whose
# PE count it is not a permutation of) — grad sync crosses both axes
mesh2 = jax.make_mesh((2, 4), ("pod", "data"))
topo4 = MeshTopology((2, 2), torus=(False, False))

def gs_pod(v):
    c = Comm(AxisSpec(data="data", model=None, pod="pod"), "shmem",
             grad_rs=True, topo=topo4, embedding=(0, 1, 3, 2))
    return c.grad_sync(v, mean=True)

g = jax.jit(jax.shard_map(gs_pod, mesh=mesh2,
                          in_specs=(P(("pod", "data")),),
                          out_specs=P(("pod", "data")), check_vma=False))
out2 = np.asarray(g(xf))
assert np.allclose(out2, np.broadcast_to(xf.mean(0), xf.shape),
                   rtol=1e-5, atol=1e-5)
print("SPMD_CONGESTION_OK")
"""


def test_spmd_embedded_collectives_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SPMD_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SPMD_CONGESTION_OK" in r.stdout
