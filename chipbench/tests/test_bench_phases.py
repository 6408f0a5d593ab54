"""The phase and scope reduction (`phase_reduce.py`, `hlo_scopes.py`), the
per-layer readers over it, and the tool that runs them
(`tools/phases.py`)."""
import dataclasses
import pathlib

import pytest

from chipbench import harness, hlo_scopes, phase_reduce
from chipbench import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data"

HLO = """\
HloModule jit_decode_fn, entry_computation_layout={()->()}

%fused_computation.1 (param_0: bf16[4,2]) -> bf16[4,2] {
  %param_0 = bf16[4,2]{1,0} parameter(0)
  ROOT %gather.3 = bf16[4,2]{1,0} gather(%param_0), metadata={op_name="jit(decode_fn)/while/body/closed_call/kv_gather/jit(_take)/gather" stack_frame_id=3}
}

ENTRY %main.9 (p: bf16[4,2]) -> bf16[4,2] {
  %p = bf16[4,2]{1,0} parameter(0)
  %fusion.187 = bf16[4,2]{1,0} fusion(%p), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(decode_fn)/while/body/closed_call/kv_gather/jit(_take)/gather" stack_frame_id=3}
  %copy.77 = bf16[4,2]{0,1} copy(%fusion.187)
  %copy.74 = bf16[4,2]{0,1} copy(%copy.77), metadata={op_name="jit(decode_fn)/while/body/dynamic_slice" stack_frame_id=1}
  %dot.5 = bf16[4,2]{1,0} dot(%copy.74, %p), metadata={op_name="jit(decode_fn)/while/body/closed_call/mlp/attend/dot_general"}
  ROOT %add.2 = bf16[4,2]{1,0} add(%dot.5, %p), metadata={op_name="jit(decode_fn)/sample/add"}
}
"""


def test_instruction_scopes():
    sc = hlo_scopes.instruction_scopes(HLO)
    assert sc["fusion.187"] == "kv_gather"
    assert sc["copy.77"] == hlo_scopes.UNSCOPED       # no metadata
    assert sc["copy.74"] == hlo_scopes.OTHER          # op name, no scope
    assert sc["dot.5"] == "attend"                    # the innermost
    assert sc["add.2"] == "sample"                    # a ROOT line
    assert sc["gather.3"] == "kv_gather"              # fused computations
    assert "fused_computation.1" not in sc and "main.9" not in sc


def _trace():
    """Device 0: two programs, prefill 0-30 (ops 0-10, 15-30) and decode
    40-80 (ops 40-60 in a loop 40-70, 60-70, 75-80); idle 10-15, 30-40,
    70-75, 80-100.  Host: steps 0-50 and 50-100; a prefill 0-35 in the
    first and a decode 35-50, and a decode 55-90 in the second."""
    return tr.Trace(
        ops={"/device:TPU:0": [(0, 10, "fusion.1"), (15, 30, "copy.2"),
                               (40, 70, "while.1"), (40, 60, "fusion.1"),
                               (60, 70, "copy.2"), (75, 80, "fusion.9")]},
        modules={"/device:TPU:0": [(0, 30, "jit_prefill_fn(1)"),
                                   (40, 80, "jit_decode_fn(2)")]},
        host=[(0, 100, tr.WINDOW_ANNOTATION), (0, 50, "serve.step"),
              (0, 35, "serve.prefill"), (35, 50, "serve.decode"),
              (50, 100, "serve.step"), (55, 90, "serve.decode"),
              (95, 120, "serve.step")])


def test_reduce_adds_host_spans_and_program_ops():
    red = phase_reduce.reduce(_trace())
    assert red.host_spans["serve.step"] == (3, pytest.approx(125e-9))
    assert red.host_spans["serve.prefill"] == (1, pytest.approx(35e-9))
    assert red.module_op_s == {
        ("jit_prefill_fn(1)", "fusion.1"): pytest.approx(10e-9),
        ("jit_prefill_fn(1)", "copy.2"): pytest.approx(15e-9),
        ("jit_decode_fn(2)", "fusion.1"): pytest.approx(20e-9),
        ("jit_decode_fn(2)", "copy.2"): pytest.approx(10e-9),
        ("jit_decode_fn(2)", "fusion.9"): pytest.approx(5e-9)}


def test_idle_by_phase():
    idle = phase_reduce.idle_by_phase(_trace())
    assert idle == {"serve.prefill": pytest.approx(5e-9),
                    "serve.decode": pytest.approx(10e-9 + 5e-9),
                    "serve.step": pytest.approx(20e-9)}
    gap_outside = tr.Trace(ops={"/device:TPU:0": [(0, 10, "fusion.1")]},
                           modules={}, host=[(0, 20, tr.WINDOW_ANNOTATION)])
    assert phase_reduce.idle_by_phase(gap_outside) == {
        phase_reduce.OUTSIDE: pytest.approx(10e-9)}


def test_scope_seconds_counts_ops_missing_from_the_text():
    red = phase_reduce.reduce(_trace())
    got = phase_reduce.scope_seconds(red.module_op_s, {
        "decode_fn": {"fusion.1": "kv_gather", "copy.2": "unscoped"}})
    assert got == {"decode_fn": {
        "kv_gather": pytest.approx(20e-9), "unscoped": pytest.approx(10e-9),
        phase_reduce.UNMATCHED: pytest.approx(5e-9)}}


def test_recorded_trace_keeps_the_accepted_fields():
    trace = tr.load(str(DATA / "tpu_v5e_1chip.xplane.pb"))
    base, red = tr.reduce(trace), phase_reduce.reduce(trace)
    assert {f.name: getattr(red, f.name)
            for f in dataclasses.fields(tr.Reduction)} == vars(base)
    assert red.host_spans[tr.WINDOW_ANNOTATION][0] == 1
    programs = {prog for prog, _ in red.module_op_s}
    assert len(programs) == 1 and programs.pop().startswith("jit_step")
    assert sum(red.module_op_s.values()) == pytest.approx(sum(
        base.op_s.values()))


def test_compile_counter():
    import time

    import jax
    import jax.numpy as jnp

    x = jnp.arange(7.0).block_until_ready()
    with phase_reduce.CompileCounter() as c:
        t0 = time.perf_counter()
        jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
        t1 = time.perf_counter()
    jax.jit(lambda x: x - 2)(x).block_until_ready()
    assert c.between(t0, t1)["compiles"] == 1
    assert c.between(t1, time.perf_counter()) == {"compiles": 0,
                                                   "cache_loads": 0}


def _reading(red=None, **counters):
    return harness.Reading(red, counters, {}, {}, 1)


def _read(name, reading):
    return harness.load_module(harness.BENCH_DIR / "metrics"
                               / f"{name}.py").read(reading)


def test_queue_wait_reader():
    name = "queue_wait_ms.serve"
    assert _read(name, _reading(queue_wait_s=[0.1, 0.2, 0.6])) == \
        pytest.approx(300.0)
    assert _read(name, _reading(queue_wait_s=[])) is None
    assert _read(name, _reading()) is None


def test_prefill_per_step_reader():
    name = "prefill_ms_per_step.serve"
    red = phase_reduce.reduce(_trace())
    assert _read(name, _reading(red)) == pytest.approx(35e-9 / 3 * 1e3)
    no_prefill = dataclasses.replace(red, host_spans={
        "serve.step": (2, 1.0)})
    assert _read(name, _reading(no_prefill)) == 0.0
    assert _read(name, _reading(tr.reduce(_trace()))) is None


def test_kv_paging_share_reader():
    name = "kv_paging_share.serve"
    red = phase_reduce.reduce(_trace())
    scopes = {"decode_fn": {"fusion.1": "kv_update", "copy.2": "unscoped",
                            "fusion.9": "kv_gather"}}
    assert _read(name, _reading(red, op_scopes=scopes)) == \
        pytest.approx(100.0 * 25 / 35)
    assert _read(name, _reading(red)) is None
    assert _read(name, _reading(tr.reduce(_trace()),
                                op_scopes=scopes)) is None


def test_tool_measures_an_untraced_run(serve_ctx):
    """The tool's untraced run at a tiny size: steps, queue waits and no
    compile inside the window (the traced run needs a TPU's trace)."""
    phases = harness.load_module(harness.BENCH_DIR / "tools" / "phases.py")
    drv = harness.load_module(serve_ctx.cell.driver)
    row = phases.measure(serve_ctx.cell, drv, serve_ctx.seed, 1.0, False)
    assert row["engine_steps"] > 0 and row["admitted"] > 0
    assert row["queue_wait_ms_mean"] >= 0
    assert row["compiles_in_window"] == {"compiles": 0, "cache_loads": 0}
    assert row["span_cost_us"]["engine_span"] > 0
