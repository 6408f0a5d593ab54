"""Observability layer (DESIGN.md §16): Tracer events, quiet/fence
stall attribution, sink hardening, serving metrics, and the tracereport
schema gate."""
from __future__ import annotations

import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Profiler, Tracer, epiphany3, sim_ctx
from repro.core.trace import LEVEL_FULL, PID_HOST, PID_PE
from repro.tools.tracereport import validate_metrics, validate_trace


def _events(t, **match):
    return [e for e in t._events
            if all(e.get(k) == v for k, v in match.items())]


# ---------------------------------------------------------------------------
# Tracer: levels, spans, chrome export
# ---------------------------------------------------------------------------

def test_disabled_tracer_records_nothing():
    t = Tracer(level=0)
    with t.span("x") as s:
        assert s is None
    t.instant("i")
    t.begin_async("req", 1, "r")
    t.end_async("req", 1, "r")
    assert t._events == [] and t.samples == []


def test_span_nesting_and_meta_args():
    t = Tracer(level=2)
    with t.span("outer"):
        with t.span("inner", nbytes=64.0, custom="tag"):
            pass
    names = [e["name"] for e in _events(t, ph="X")]
    assert names == ["inner", "outer"]      # inner commits first
    inner = _events(t, ph="X")[0]
    assert inner["args"]["custom"] == "tag"
    assert inner["args"]["nbytes"] == 64.0
    # nesting by time: inner contained in outer
    outer = _events(t, ph="X")[1]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]


def test_level1_counts_but_no_events():
    t = Tracer(level=1)
    with t.span("x"):
        pass
    t.instant("i")
    assert t._events == []
    assert "span.x" in t.counters()


def test_async_request_track_roundtrip():
    t = Tracer(level=2)
    t.begin_async("request", 7, "req 7", prompt_len=5)
    t.instant_async("request", 7, "admit")
    t.end_async("request", 7, "req 7", n_tokens=3)
    phs = [e["ph"] for e in _events(t, cat="request")]
    assert phs == ["b", "n", "e"]
    assert validate_trace(t.to_chrome()) == []


def test_eager_collective_renders_stages_flows_heatmap():
    t = Tracer(level=LEVEL_FULL)
    ctx = sim_ctx(16, epiphany3(), profile=t)
    ctx.to_all(jnp.ones((16, 256), jnp.float32), algorithm="rd")
    # host-track op span with the algorithm in the name
    ops = _events(t, ph="X", pid=PID_HOST)
    assert any(e["name"] == "allreduce[rd]" for e in ops)
    # per-PE stage spans: rd on 16 PEs = 4 stages, every PE participates
    stages = _events(t, cat="stage")
    assert len(stages) == 4 * 16
    assert {e["tid"] for e in stages} == set(range(16))
    assert {e["pid"] for e in stages} == {PID_PE}
    # flow links pair up by id, src != dst
    starts = {e["id"]: e for e in _events(t, ph="s")}
    finishes = {e["id"]: e for e in _events(t, ph="f")}
    assert starts and set(starts) == set(finishes)
    for fid, s in starts.items():
        assert s["tid"] != finishes[fid]["tid"]
    # heatmap accumulated on the 4x4 topology
    hm = t.heatmap()
    assert len(hm) == 1 and hm[0]["shape"] == [4, 4]
    assert hm[0]["total_bytes"] > 0
    assert hm[0]["links"][0]["bytes"] == hm[0]["max_bytes"]
    assert validate_trace(t.to_chrome()) == []


def test_flow_cap_bounds_events():
    t = Tracer(level=LEVEL_FULL, flows_per_op=3)
    ctx = sim_ctx(16, epiphany3(), profile=t)
    ctx.to_all(jnp.ones((16, 64), jnp.float32), algorithm="ring")
    assert len(_events(t, ph="s")) <= 3


def test_event_cap_counts_drops():
    t = Tracer(level=2, max_events=2)
    for i in range(5):
        t.instant(f"i{i}")
    assert len(t._events) == 2 and t.events_dropped == 3


def test_traced_collective_uses_predicted_duration():
    """A Comm-in-jit collective commits at trace time with wall~0; its
    stage spans must still have nonzero duration."""
    import jax

    t = Tracer(level=LEVEL_FULL)
    ctx = sim_ctx(16, epiphany3(), profile=t)

    jax.jit(lambda v: ctx.to_all(v, algorithm="rd"))(
        jnp.ones((16, 256), jnp.float32))
    stages = _events(t, cat="stage")
    assert stages, "staged collective rendered no stage spans"
    assert all(e["dur"] > 0 for e in stages)
    assert all(e["args"].get("traced") for e in stages)


# ---------------------------------------------------------------------------
# quiet/fence stall attribution
# ---------------------------------------------------------------------------

def test_quiet_splits_stall_from_issue():
    prof = Profiler(level=2)
    ctx = sim_ctx(4, profile=prof)
    c = ctx.ctx_create()
    c.put_nbi(jnp.ones((4, 128)), [(i, (i + 1) % 4) for i in range(4)])
    c.quiet()
    sync = [s for s in prof.samples if s.kind == "sync"]
    assert len(sync) == 1 and sync[0].collective == "quiet"
    s = sync[0]
    assert s.issue_s > 0 and s.stall_s >= 0
    assert s.wall_s == pytest.approx(s.issue_s + s.stall_s)
    c2 = prof.counters()["sync.quiet"]
    assert c2["issue_s"] == pytest.approx(s.issue_s)
    assert c2["stall_s"] == pytest.approx(s.stall_s)


def test_fence_reports_issue_only():
    prof = Profiler(level=2)
    ctx = sim_ctx(4, profile=prof)
    c = ctx.ctx_create()
    c.put_nbi(jnp.ones((4, 32)), [(i, (i + 1) % 4) for i in range(4)])
    c.fence()
    sync = [s for s in prof.samples if s.kind == "sync"]
    assert len(sync) == 1 and sync[0].collective == "fence"
    assert sync[0].issue_s > 0 and sync[0].stall_s == 0.0
    c.quiet()       # queue still drains normally after the fence


def test_quiet_sync_renders_stall_child_span():
    t = Tracer(level=2)
    ctx = sim_ctx(4, profile=t)
    c = ctx.ctx_create()
    c.put_nbi(jnp.ones((4, 4096)), [(i, (i + 1) % 4) for i in range(4)])
    c.quiet()
    qevs = _events(t, ph="X", cat="sync")
    assert len(qevs) == 1
    a = qevs[0]["args"]
    assert a["issue_us"] >= 0 and a["stall_us"] >= 0
    stall = _events(t, cat="stall")
    if a["stall_us"] > 0:
        assert len(stall) == 1
        # the stall child starts where issue ends
        assert stall[0]["ts"] == pytest.approx(
            qevs[0]["ts"] + a["issue_us"])


def test_quiet_untimed_inside_jit():
    """Under jit tracing, quiet must not call block_until_ready (no sync
    sample — wall time there is meaningless)."""
    import jax

    prof = Profiler(level=2)
    ctx = sim_ctx(4, profile=prof)

    def f(x):
        c = ctx.ctx_create()
        c.put_nbi(x, [(i, (i + 1) % 4) for i in range(4)])
        return c.quiet()

    jax.jit(f)(jnp.ones((4, 16)))
    assert not any(s.kind == "sync" for s in prof.samples)


# ---------------------------------------------------------------------------
# satellite: sink hardening + mid-run pcontrol transitions
# ---------------------------------------------------------------------------

def test_raising_sink_does_not_abort_op_and_is_dropped():
    prof = Profiler(level=2)
    good: list = []

    def bad_sink(s):
        raise RuntimeError("observer bug")

    prof.add_sink(bad_sink)
    prof.add_sink(good.append)
    for i in range(5):
        with prof.op(f"op{i}"):
            pass
    # every op completed; the good sink saw them all
    assert len(good) == 5
    assert len(prof.samples) == 5
    # the bad sink failed MAX times then was dropped
    assert prof.sink_errors == Profiler.SINK_MAX_FAILURES
    assert prof.sinks_dropped == 1
    assert bad_sink not in prof._sinks and good.append in prof._sinks
    j = prof.to_json()
    assert j["sink_errors"] == Profiler.SINK_MAX_FAILURES
    assert j["sinks_dropped"] == 1


def test_flaky_sink_survives_with_consecutive_reset():
    prof = Profiler(level=1)
    calls = {"n": 0}

    def flaky(s):
        calls["n"] += 1
        if calls["n"] % 2:          # fails every other call
            raise ValueError("flaky")

    prof.add_sink(flaky)
    for i in range(6):
        with prof.op("x"):
            pass
    # never SINK_MAX_FAILURES consecutive failures -> never dropped
    assert prof.sinks_dropped == 0 and flaky in prof._sinks
    assert prof.sink_errors == 3


def test_pcontrol_transition_while_op_open():
    prof = Profiler(level=2)
    with prof.op("a") as s:
        assert s is not None
        prof.pcontrol(0)            # disabled mid-op
    # the op opened under level 2 was dropped at commit (disabled)
    assert prof.samples == [] and prof.counters() == {}
    with prof.op("b") as s:
        assert s is None            # fully off now
        prof.pcontrol(2)            # re-enabled mid-op
    # "b" opened disabled: no sample; the next op records normally
    assert prof.samples == []
    with prof.op("c"):
        pass
    assert [s.collective for s in prof.samples] == ["c"]


def test_pcontrol_toggle_during_eager_collectives():
    prof = Profiler(level=2)
    ctx = sim_ctx(8, profile=prof)
    x = jnp.ones((8, 64))
    ctx.to_all(x)
    prof.pcontrol(0)
    ctx.to_all(x)
    prof.pcontrol(2)
    ctx.to_all(x)
    recorded = [s for s in prof.samples if s.kind == "collective"]
    assert len(recorded) == 2


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_histogram_buckets_and_percentiles():
    from repro.serve.metrics import Histogram
    h = Histogram("lat", lo=1e-4, hi=1.0, n_buckets=8)
    for v in (1e-5, 1e-3, 1e-2, 0.5, 2.0):
        h.observe(v)
    assert h.count == 5
    assert h.buckets[0] == 1              # underflow
    assert h.buckets[-1] == 1             # overflow
    assert sum(h.buckets) == h.count
    assert h.percentile(50) == 1e-2
    assert h.percentile(0) == 1e-5 and h.percentile(100) == 2.0
    assert h.mean == pytest.approx(sum((1e-5, 1e-3, 1e-2, 0.5, 2.0)) / 5)
    assert math.isnan(Histogram("e").percentile(50))


def test_histogram_reservoir_samples_the_whole_run():
    """Past the reservoir's size the kept observations stay a uniform
    sample of all of them, not the run's first ones."""
    from repro.serve.metrics import Histogram
    h = Histogram("lat", reservoir=8192)
    for i in range(20_000):
        h.observe(0.001 if i < 10_000 else 1.0)
    assert h.count == 20_000 and len(h._raw) == 8192
    late = sum(v == 1.0 for v in h._raw) / len(h._raw)
    assert 0.45 < late < 0.55
    assert h.percentile(10) == 0.001 and h.percentile(90) == 1.0


def test_registry_types_and_export(tmp_path):
    from repro.serve.metrics import MetricsRegistry
    r = MetricsRegistry()
    r.counter("c").inc(3)
    r.gauge("g").set(1.5)
    r.histogram("h").observe(0.25)
    assert r.counter("c") is r["c"]       # idempotent get
    with pytest.raises(TypeError):
        r.gauge("c")                      # type mismatch
    p = tmp_path / "m.json"
    r.dump(p)
    doc = json.loads(p.read_text())
    assert validate_metrics(doc) == []
    assert doc["metrics"]["c"]["value"] == 3
    assert doc["metrics"]["g"]["min"] == 1.5
    assert doc["metrics"]["h"]["count"] == 1


def test_serve_metrics_lifecycle_math():
    import time

    from repro.serve.engine import SlotState
    from repro.serve.metrics import ServeMetrics
    m = ServeMetrics()
    t_submit = time.perf_counter() - 0.5
    st = SlotState(rid=0, prompt=np.arange(3), max_new=3, pos=3,
                   t_submit=t_submit, t_admit=t_submit + 0.25)
    m.on_submit()
    m.on_admit(st)
    m.on_first_token(st)
    m.on_decode_step(1, 0.002)
    m.on_decode_step(1, 0.004)
    m.on_evict(st)
    m.on_backpressure()
    assert m.requests_completed.value == 1
    assert m.tokens_generated.value == 3          # first + 2 decode
    assert m.ttft_s.count == 1 and m.e2e_s.count == 1
    assert m.admission_wait_s.percentile(50) == pytest.approx(0.25)
    assert 0.5 <= m.ttft_s.percentile(50) <= m.e2e_s.percentile(50)
    assert m.per_token_s.percentile(50) in (0.002, 0.004)
    assert m.backpressure_waits.value == 1


# ---------------------------------------------------------------------------
# engine + launcher integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_engine_run():
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeEngine
    from repro.serve.metrics import ServeMetrics

    tracer = Tracer(level=LEVEL_FULL)
    metrics = ServeMetrics()
    metrics.attach(tracer)
    eng = ServeEngine(smoke_config("qwen2-0.5b"), make_mesh(1, 1),
                      max_slots=2, page_size=8, max_seq=32,
                      prompt_bucket=16, profile=tracer, metrics=metrics)
    rng = np.random.default_rng(3)
    rids = [eng.submit(rng.integers(1, 500, size=n, dtype=np.int32), 4)
            for n in (5, 9, 3)]
    eng.run()
    return eng, tracer, metrics, rids


def test_engine_emits_request_lifecycle(traced_engine_run):
    eng, tracer, metrics, rids = traced_engine_run
    req = [e for e in tracer._events if e.get("cat") == "request"]
    begins = [e for e in req if e["ph"] == "b"]
    ends = [e for e in req if e["ph"] == "e"]
    assert len(begins) == len(rids) and len(ends) == len(rids)
    assert {e["id"] for e in begins} == {str(r) for r in rids}
    marks = {e["name"] for e in req if e["ph"] == "n"}
    assert {"admit", "first_token"} <= marks
    spans = {e["name"] for e in tracer._events if e.get("ph") == "X"}
    assert {"serve.step", "serve.prefill", "serve.decode"} <= spans


def test_engine_metrics_consistent(traced_engine_run):
    eng, tracer, metrics, rids = traced_engine_run
    n = len(rids)
    assert metrics.requests_submitted.value == n
    assert metrics.requests_completed.value == n
    assert metrics.ttft_s.count == n
    assert metrics.e2e_s.count == n
    # 4 tokens per request: 1 prefill + 3 decode each
    assert metrics.tokens_generated.value == 4 * n
    assert metrics.kv_pages_live.value == 0       # drained clean
    assert metrics.kv_occupancy.value == 0.0
    doc = metrics.to_json()
    assert validate_metrics(doc) == []
    assert "heatmap" in doc and "wire" in doc     # tracer attached


def test_trace_document_validates(traced_engine_run, tmp_path):
    _, tracer, _, _ = traced_engine_run
    p = tmp_path / "trace.json"
    tracer.dump_chrome(p)
    doc = json.loads(p.read_text())
    assert validate_trace(doc) == []
    assert doc["repro"]["level"] == LEVEL_FULL


def test_tracereport_cli(traced_engine_run, tmp_path, capsys):
    from repro.tools import tracereport
    _, tracer, metrics, _ = traced_engine_run
    tp, mp = tmp_path / "t.json", tmp_path / "m.json"
    tracer.dump_chrome(tp)
    metrics.dump(mp)
    tracereport.main([str(tp), "--metrics", str(mp), "--check"])
    out = capsys.readouterr().out
    assert "schema check OK" in out
    assert "serve.step" in out
    assert "serve.per_token_s" in out


def test_validate_catches_corruption(traced_engine_run, tmp_path):
    _, tracer, _, _ = traced_engine_run
    doc = tracer.to_chrome()
    doc["traceEvents"].append({"ph": "X", "name": "bad"})  # no ts/dur
    assert validate_trace(doc)
    assert validate_metrics({"schema": 2, "metrics": {}})
    assert validate_metrics(
        {"schema": 1, "metrics": {"x": {"type": "wat"}}})


def test_pagepool_occupancy_fragmentation():
    from repro.serve.kv import PagePool
    pool = PagePool(8 * 4096, 4096)               # 8 pages incl. null
    assert pool.occupancy() == 0.0
    assert pool.fragmentation() == 0.0
    got = pool.alloc(3)
    assert pool.occupancy() == pytest.approx(3 / 7)
    pool.free([got[-1]])
    assert pool.fragmentation() == pytest.approx(1 / 5)
    pool.free(reversed(got[:-1]))
    assert pool.occupancy() == 0.0 and pool.fragmentation() == 0.0


def test_launch_serve_trace_flags(tmp_path):
    from repro.launch import serve as serve_launch
    tout = tmp_path / "trace.json"
    mout = tmp_path / "metrics.json"
    serve_launch.main([
        "--arch", "qwen2-0.5b", "--smoke", "--batch", "2",
        "--prompt-len", "8", "--tokens", "4",
        "--trace-out", str(tout), "--metrics-out", str(mout)])
    tdoc = json.loads(tout.read_text())
    mdoc = json.loads(mout.read_text())
    assert validate_trace(tdoc) == []
    assert validate_metrics(mdoc) == []
    assert mdoc["metrics"]["serve.requests_completed"]["value"] == 2
