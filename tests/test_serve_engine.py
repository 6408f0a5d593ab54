"""Continuous-batching serving engine (serve/engine.py).

Three layers, cheapest first: the pure-host `Scheduler` policy as a
deterministic state machine (no devices), the single-device engine's
core invariant — a request's greedy tokens and logits are BIT-identical
whether it runs alone or joins a batch mid-flight — and the same
invariant plus the `sample_greedy` tie-break under a real tp=2 SPMD
mesh in a subprocess."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.heap import SymmetricHeap
from repro.serve import PagedKV, PagePool, PagePoolError

PAGE_BYTES = 64
PAGE_TOKENS = 8


def make_sched(n_pages, max_slots=2, max_pages=8):
    from repro.serve.engine import Scheduler
    pool = PagePool(SymmetricHeap((n_pages + 1) * PAGE_BYTES), PAGE_BYTES)
    return Scheduler(PagedKV(pool, max_slots, max_pages), PAGE_TOKENS)


def drive(sched, trace, decode_per_step=1):
    """Run the scheduler against a synthetic trace.

    `trace[t]` is a list of (prompt_len, max_new) submissions arriving at
    step t.  Active slots "decode" `decode_per_step` tokens per step.
    Returns the flat event log [("admit"|"evict", step, rid), ...]."""
    events = []
    t = 0
    while t < len(trace) or not sched.idle():
        for plen, mnew in (trace[t] if t < len(trace) else []):
            sched.submit(np.arange(1, plen + 1), mnew)
        for slot, st in sched.step_evict():
            events.append(("evict", t, st.rid))
        for slot, st in sched.step_admit():
            events.append(("admit", t, st.rid))
        for i in sched.active_slots():
            st = sched.slots[i]
            st.out.extend([0] * decode_per_step)
            st.pos += decode_per_step
            if len(st.out) >= st.max_new:
                st.done = True
        t += 1
        assert t < 10_000, "scheduler livelock"
    for slot, st in sched.step_evict():
        events.append(("evict", t, st.rid))
    return events


# ---------------------------------------------------------------------------
# Scheduler: deterministic policy, pure host
# ---------------------------------------------------------------------------

def test_scheduler_event_order_is_deterministic():
    trace = [[(8, 4), (8, 2)], [], [(8, 3)], [(16, 2), (8, 1)]]
    ev1 = drive(make_sched(n_pages=4, max_slots=2), list(trace))
    ev2 = drive(make_sched(n_pages=4, max_slots=2), list(trace))
    assert ev1 == ev2
    # admissions happen in rid (FIFO) order
    admits = [rid for kind, _, rid in ev1 if kind == "admit"]
    assert admits == sorted(admits) == [0, 1, 2, 3, 4]
    # every admitted request is eventually evicted exactly once
    evicts = sorted(rid for kind, _, rid in ev1 if kind == "evict")
    assert evicts == [0, 1, 2, 3, 4]


def test_strict_fifo_big_request_is_not_starved():
    # 4-page heap, 2 slots.  A 4-page request sits at the head while
    # 1-page requests stream in behind it: FIFO admission must never
    # skip the head, so the big one gets in as soon as pages free up.
    sched = make_sched(n_pages=4, max_slots=2, max_pages=4)
    sched.submit(np.arange(1, 9), 8)        # rid 0: 2 pages
    sched.submit(np.arange(1, 25), 8)       # rid 1: 4 pages (the big one)
    for _ in range(6):                      # rids 2..7: 1 page each
        sched.submit(np.arange(1, 5), 4)
    events = drive(sched, [])
    admits = [rid for kind, _, rid in events if kind == "admit"]
    assert admits == list(range(8))         # strict FIFO, nobody skipped
    # while rid 1 waits for pages nothing behind it may jump the queue:
    # rid 1 is admitted strictly before rids 2..7
    t_big = next(t for k, t, r in events if k == "admit" and r == 1)
    t_small = [t for k, t, r in events if k == "admit" and r >= 2]
    assert all(t_big <= t for t in t_small)


def test_admission_backpressure_waits_without_errors():
    # heap holds 2 pages; every request needs 2 -> one in flight at a
    # time, the rest wait.  No PagePoolError/HeapError surfaces.
    sched = make_sched(n_pages=2, max_slots=4, max_pages=4)
    for _ in range(3):
        sched.submit(np.arange(1, 9), 8)    # 16 tokens -> 2 pages
    events = drive(sched, [])
    admits = [(t, rid) for k, t, rid in events if k == "admit"]
    assert [rid for _, rid in admits] == [0, 1, 2]
    # serialized: each admission waits for the previous eviction
    evict_t = {rid: t for k, t, rid in events if k == "evict"}
    assert admits[1][0] >= evict_t[0] and admits[2][0] >= evict_t[1]
    assert sched.kv.pool.live_pages() == 0  # drained clean


def test_submit_validates_against_max_pages():
    sched = make_sched(n_pages=16, max_slots=2, max_pages=2)
    with pytest.raises(ValueError):
        sched.submit(np.arange(1, 18), 8)   # 25 tokens > 2 pages
    with pytest.raises(ValueError):
        sched.submit(np.asarray([], np.int32), 4)


def test_scheduler_stamps_submit_and_admission():
    """Two clock reads per request: at submit and at admission, so
    admission minus submission is the request's queue wait."""
    from repro.serve.engine import Scheduler
    now = [10.0]
    pool = PagePool(SymmetricHeap((2 + 1) * PAGE_BYTES), PAGE_BYTES)
    sched = Scheduler(PagedKV(pool, 1, 8), PAGE_TOKENS, clock=lambda: now[0])
    sched.submit(np.arange(1, 9), 8)           # rid 0: 2 pages
    now[0] = 10.5
    sched.submit(np.arange(1, 9), 8)           # rid 1 waits for the slot
    now[0] = 11.0
    (_, st0), = sched.step_admit()
    assert (st0.t_submit, st0.t_admit) == (10.0, 11.0)
    st0.done = True
    now[0] = 12.25
    sched.step_evict()
    (_, st1), = sched.step_admit()
    assert st1.t_admit - st1.t_submit == 12.25 - 10.5


# ---------------------------------------------------------------------------
# Engine on SIM (single device): batched == alone, bitwise
# ---------------------------------------------------------------------------

ARCH = "qwen2-0.5b"


def _make_engine(params=None, **kw):
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeEngine
    kw.setdefault("max_slots", 3)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prompt_bucket", 16)
    return ServeEngine(smoke_config(ARCH), make_mesh(1, 1), params=params,
                       capture_logits=True, **kw)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(7)
    return [rng.integers(1, 1000, size=n).astype(np.int32)
            for n in (5, 9, 3, 12)]


def test_engine_batched_equals_alone_bitwise(prompts):
    eng = _make_engine()
    rids = [eng.submit(p, 6) for p in prompts]
    eng.run()
    assert eng.scheduler.idle()

    solo = _make_engine(params=eng.params)
    for rid, p in zip(rids, prompts):
        srid = solo.submit(p, 6)
        solo.run()
        assert np.array_equal(eng.results[rid], solo.results[srid]), rid
        # stronger than the tokens: the per-step logits are bitwise equal
        for a, b in zip(eng.logits_trace[rid], solo.logits_trace[srid]):
            assert np.array_equal(a, b)


def test_engine_mid_batch_join_is_bitwise_transparent(prompts):
    """A request that joins while others are mid-decode gets the same
    tokens as the same request submitted up front."""
    eng = _make_engine()
    r0 = eng.submit(prompts[0], 8)
    eng.step(); eng.step(); eng.step()        # r0 is 3 tokens in
    r1 = eng.submit(prompts[1], 6)            # joins mid-batch
    eng.run()

    ref = _make_engine(params=eng.params)
    q1 = ref.submit(prompts[1], 6)
    ref.run()
    assert np.array_equal(eng.results[r1], ref.results[q1])
    q0 = ref.submit(prompts[0], 8)
    ref.run()
    assert np.array_equal(eng.results[r0], ref.results[q0])


def test_engine_batched_equals_alone_bitwise_on_kernel(prompts, on_kernel):
    test_engine_batched_equals_alone_bitwise(prompts)


def test_engine_mid_batch_join_is_bitwise_transparent_on_kernel(prompts,
                                                                on_kernel):
    test_engine_mid_batch_join_is_bitwise_transparent(prompts)


def test_engine_kernel_path_tokens_match_gather_path(prompts, on_kernel,
                                                     monkeypatch):
    """The kernel's tokens and logits match the gather path's: same bf16
    K/V, f32 scores, softmax and weighted sum; only the f32 summation
    order differs."""
    from repro.models import layers

    eng = _make_engine()
    assert eng.decode_path == "kernel"
    rids = [eng.submit(p, 6) for p in prompts]
    eng.run()
    monkeypatch.setattr(layers, "_on_tpu", lambda: False)
    ref = _make_engine(params=eng.params)
    assert ref.decode_path == "gather"
    qids = [ref.submit(p, 6) for p in prompts]
    ref.run()
    for r, q in zip(rids, qids):
        assert np.array_equal(eng.results[r], ref.results[q]), r
        for a, b in zip(eng.logits_trace[r], ref.logits_trace[q]):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_engine_decode_span_names_path_and_pages(prompts, tmp_path,
                                                 on_kernel):
    """Each serve.decode span says which path the attention took and how
    many KV pages one layer read: the pages holding each live slot's
    earlier positions.  ServeMetrics counts the decode steps by path."""
    import glob

    import jax
    from jax.profiler import ProfileData
    from repro.serve.metrics import ServeMetrics

    eng = _make_engine(metrics=ServeMetrics())
    for p in prompts[:2]:
        eng.submit(p, 4)
    want = []
    real = eng._djit

    def seen(params, pool, table, tokens, positions):
        want.append(int(np.sum(-(-np.asarray(positions) // eng.page_size))))
        return real(params, pool, table, tokens, positions)
    eng._djit = seen
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    stats = [dict(e.stats)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "serve.decode"]
    assert [s["path"] for s in stats] == ["kernel"] * len(want)
    assert [s["kv_pages"] for s in stats] == want
    assert want[0] == 1 + 2                   # positions 5 and 9, pages of 8
    m = eng.metrics
    assert m.decode_steps_by_path["kernel"].value == len(want)
    assert m.decode_steps_by_path["gather"].value == 0


def test_engine_heap_backpressure_still_serves_everyone(prompts):
    # heap sized for ~one worst-case sequence: requests serialize through
    # admission backpressure but all finish, and nothing leaks
    probe = _make_engine()
    tight = probe.page_bytes * (4 + 1)        # 4 live pages + null
    eng = _make_engine(params=probe.params, kv_heap_bytes=tight)
    rids = [eng.submit(p, 6) for p in prompts[:3]]
    eng.run()
    assert sorted(eng.results) == sorted(rids)
    assert all(len(eng.results[r]) == 6 for r in rids)
    assert eng.scheduler.n_admitted == 3
    assert eng.kv.pool.live_pages() == 0
    # tokens unaffected by the serialization
    ref = _make_engine(params=probe.params)
    for rid, p in zip(rids, prompts[:3]):
        q = ref.submit(p, 6)
        ref.run()
        assert np.array_equal(eng.results[rid], ref.results[q])


def test_engine_eos_stops_early(prompts):
    eng = _make_engine()
    r = eng.submit(prompts[0], 8)
    eng.run()
    toks = eng.results[r]
    eos = int(toks[2])                        # force eos by the 3rd token
    stop = int(np.argmax(toks == eos)) + 1    # its first occurrence ends it
    eng2 = _make_engine(params=eng.params, eos_id=eos)
    r2 = eng2.submit(prompts[0], 8)
    eng2.run()
    assert len(eng2.results[r2]) == stop <= 3
    assert np.array_equal(eng2.results[r2], toks[:stop])


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma2-9b"])
def test_engine_logits_match_teacher_forced_forward(arch):
    """Every step's logits, prefill and decode, against
    `transformer.forward` over the prompt and the tokens served before
    it.  gemma2's local/global pairs (window 16, softcap) read pages past
    the window, so its local layers mask earlier positions."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.configs import smoke_config
    from repro.launch import build
    from repro.launch.mesh import make_mesh
    from repro.models import layers as L
    from repro.models import transformer
    from repro.parallel.comm import Comm
    from repro.serve.engine import ServeEngine

    cfg, mesh = smoke_config(arch), make_mesh(1, 1)
    eng = ServeEngine(cfg, mesh, max_slots=3, page_size=8, max_seq=48,
                      prompt_bucket=32, capture_logits=True)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 21, 30)]
    rids = [eng.submit(p, 12) for p in prompts]
    eng.run()

    def fwd(params, tokens):
        comm = Comm(build.axis_spec(mesh), "shmem")
        h, _ = transformer.forward(comm, eng.cfg, params, tokens)
        return L.lm_logits(comm, eng.cfg, params["embed"], h)
    _, pspecs = build.abstract_params(eng.cfg, mesh)
    with jax.set_mesh(mesh):
        forward = jax.jit(build.shard_mapped(fwd, mesh, (pspecs, P()), P()))
        for rid, p in zip(rids, prompts):
            toks = eng.results[rid]
            seq = np.concatenate([p, toks[:-1]])
            want = np.asarray(forward(eng.params, jnp.asarray(seq)[None])[0],
                              np.float32)[len(p) - 1:]
            got = np.stack(eng.logits_trace[rid])
            assert got.shape == want.shape
            err = np.abs(got - want).max()
            assert err < 5e-2, (arch, rid, err)   # bf16 compute


def test_engine_phases_on_profiler_clock(prompts, tmp_path):
    """The engine's phases are host spans of the profiler's own trace,
    nested under serve.step: one serve.prefill per admission, one
    serve.decode per step with live slots."""
    import glob

    import jax
    from jax.profiler import ProfileData

    eng = _make_engine(max_slots=2)
    for p in prompts[:3]:
        eng.submit(p, 4)
    with jax.profiler.trace(str(tmp_path)):
        outs = []
        while not eng.scheduler.idle():
            outs.append(eng.step())
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    steps = [(s, e) for s, e, n in spans if n == "serve.step"]
    by = {n: [(s, e) for s, e, m in spans if m == n]
          for n in ("serve.evict", "serve.admit", "serve.prefill",
                    "serve.decode.prepare", "serve.decode", "serve.emit")}
    assert len(steps) == len(outs)
    assert len(by["serve.prefill"]) == sum(len(o["admitted"]) for o in outs)
    assert len(by["serve.decode"]) == sum(bool(o["decoded"]) for o in outs)
    assert len(by["serve.evict"]) == len(by["serve.admit"]) == len(outs)
    for name, evs in by.items():
        for s, e in evs:
            assert any(s0 <= s and e <= e0 for s0, e0 in steps), name


def test_program_texts_hold_every_scope():
    """The compiled prefill and decode programs carry the model's named
    scopes in their instructions' metadata.  Only decode gathers pages:
    prefill attends the prompt's own rows."""
    import re

    texts = _make_engine().program_texts()
    assert sorted(texts) == ["decode_fn", "prefill_fn"]
    for name, text in texts.items():
        for scope in ("kv_update", "attend", "attn_proj", "mlp", "lm_head",
                      "sample") + (("kv_gather",) if name == "decode_fn"
                                   else ()):
            assert re.search(rf'op_name="[^"]*/{scope}/', text), scope


# ---------------------------------------------------------------------------
# sample_greedy tie-breaking
# ---------------------------------------------------------------------------

def test_sample_greedy_tie_matches_argmax_unsharded():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch import build
    from repro.launch.mesh import make_mesh
    from repro.parallel.comm import Comm
    from repro.serve import step as sstep

    mesh = make_mesh(1, 1)
    logits = np.zeros((3, 16), np.float32)
    logits[0, [2, 9, 14]] = 5.0               # three-way tie -> 2
    logits[1, :] = 1.0                        # all tied -> 0
    logits[2, 11] = 3.0                       # unique max -> 11
    with jax.set_mesh(mesh):
        def f(lg):
            comm = Comm(build.axis_spec(mesh), "shmem")
            return sstep.sample_greedy(comm, lg)
        out = np.asarray(jax.jit(build.shard_mapped(
            f, mesh, (P(),), P()))(jnp.asarray(logits)))
    assert out.tolist() == np.argmax(logits, -1).tolist() == [2, 0, 11]


# ---------------------------------------------------------------------------
# tp=2 SPMD: engine invariant + cross-shard tie-break, in a subprocess
# ---------------------------------------------------------------------------

SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs import smoke_config
    from repro.launch import build
    from repro.launch.mesh import make_mesh
    from repro.parallel.comm import Comm
    from repro.serve import step as sstep
    from repro.serve.engine import ServeEngine

    mesh = make_mesh(1, 2)

    # -- cross-shard greedy tie-break: lowest GLOBAL index wins ---------
    V = 16                                     # 8 per shard
    logits = np.zeros((4, V), np.float32)
    logits[0, [3, 11]] = 5.0     # tie straddles the shard boundary -> 3
    logits[1, [9, 13]] = 5.0     # both on shard 1 -> 9
    logits[2, :] = 2.0           # all tied -> 0
    logits[3, 12] = 7.0          # unique max on shard 1 -> 12
    with jax.set_mesh(mesh):
        def f(lg):
            comm = Comm(build.axis_spec(mesh), "shmem")
            return sstep.sample_greedy(comm, lg)
        out = np.asarray(jax.jit(build.shard_mapped(
            f, mesh, (P(None, "model"),), P()))(jnp.asarray(logits)))
    ref = np.argmax(logits, -1)
    assert out.tolist() == ref.tolist() == [3, 9, 0, 12], out
    print("TIE-OK")

    # -- engine: batched == alone, bitwise, on the SAME tp=2 mesh -------
    cfg = smoke_config("qwen2-0.5b")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 1000, size=n).astype(np.int32)
               for n in (5, 9, 3)]
    kw = dict(max_slots=3, page_size=8, max_seq=32, prompt_bucket=16,
              capture_logits=True)
    eng = ServeEngine(cfg, mesh, **kw)
    rids = [eng.submit(p, 5) for p in prompts]
    eng.run()
    solo = ServeEngine(cfg, mesh, params=eng.params, **kw)
    for rid, p in zip(rids, prompts):
        s = solo.submit(p, 5)
        solo.run()
        assert np.array_equal(eng.results[rid], solo.results[s]), rid
        for a, b in zip(eng.logits_trace[rid], solo.logits_trace[s]):
            assert np.array_equal(a, b)
    print("SPMD-ENGINE-OK")
""")


def test_spmd_engine_and_tiebreak():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SPMD_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "TIE-OK" in r.stdout and "SPMD-ENGINE-OK" in r.stdout


SPMD_KERNEL_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import dataclasses
    import numpy as np
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import layers
    from repro.serve.engine import ServeEngine

    # two KV heads over a model axis of 2: one per chip, not replicated,
    # so the kernel reads each chip's half of the pool's lanes
    cfg = dataclasses.replace(smoke_config("qwen2-0.5b"), n_heads=4,
                              n_kv_heads=2)
    mesh = make_mesh(1, 2)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 100, size=n).astype(np.int32)
               for n in (5, 9, 3)]
    kw = dict(max_slots=3, page_size=8, max_seq=32, prompt_bucket=16,
              capture_logits=True)

    def serve(kernel, params, one_at_a_time=False):
        layers._on_tpu = lambda: kernel
        eng = ServeEngine(cfg, mesh, params=params, **kw)
        assert eng.decode_path == ("kernel" if kernel else "gather")
        out = []
        for p in prompts:
            out.append(eng.submit(p, 5))
            if one_at_a_time:
                eng.run()
        eng.run()
        return eng, [(eng.results[r], eng.logits_trace[r]) for r in out]

    eng, batched = serve(True, None)
    _, alone = serve(True, eng.params, one_at_a_time=True)
    _, gather = serve(False, eng.params)
    for (t, lg), (ta, lga), (tg, lgg) in zip(batched, alone, gather):
        assert np.array_equal(t, ta) and np.array_equal(t, tg)
        for a, b, c in zip(lg, lga, lgg):
            assert np.array_equal(a, b)
            np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-4)
    print("SPMD-KERNEL-OK")
""")


def test_spmd_engine_on_kernel():
    """tp=2 with the KV heads sharded: the kernel path is bitwise the
    same batched and alone, and serves the gather path's tokens."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SPMD_KERNEL_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "SPMD-KERNEL-OK" in r.stdout
