"""The serving cell's engine phases and model scopes, read off the device
trace, and the cost of the tracing that puts them there.

  python3 chipbench/tools/phases.py --workload qwen2-serve-chat \
      --seeds 1,2,3 [--seconds 8]

For each seed, two runs of the cell's open loop, each on a fresh engine
after the mix's warm-up: one untraced, then one under the profiler.
Each prints one JSON line: engine steps in the window and the mean step
time (window over steps), backend compiles and cache loads inside the
window, and the mean queue wait of the requests admitted in it.  The
traced line adds the per-layer metrics that read the program's spans and
scopes (`queue_wait_ms.serve`, `prefill_ms_per_step.serve`,
`kv_paging_share.serve`) beside the cell's accepted ones, the window's
idle seconds by engine phase (`idle_by_phase`), device seconds by named
scope per program (`scope_s`), and the share of `decode_fn` device time
matched to an instruction of its compiled text.  Every line gives the
cost of one engine span with no profiler session open, taken on the
run's engine before its loop starts.

One process for every seed, so the programs compile once.  Refuses to
run anywhere but on the chips the cell asks for.
"""
import argparse
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import (generator, harness, hlo_scopes,  # noqa: E402
                       phase_reduce, trace_reduce)

METRICS = ("queue_wait_ms.serve", "prefill_ms_per_step.serve",
           "kv_paging_share.serve")
SPAN_REPEATS = 100_000


def span_cost_us(eng) -> dict:
    """Microseconds per engine span and per bare TraceAnnotation, with no
    profiler session open and nothing attached to the engine."""
    import jax

    out = {}
    for name, make in (("engine_span", lambda: eng._span("serve.cost")),
                       ("trace_annotation",
                        lambda: jax.profiler.TraceAnnotation("serve.cost"))):
        t0 = time.perf_counter()
        for _ in range(SPAN_REPEATS):
            with make():
                pass
        out[name] = (time.perf_counter() - t0) / SPAN_REPEATS * 1e6
    return out


def measure(cell, drv, seed: int, seconds: float, trace: bool,
            peak: dict | None = None) -> dict:
    """One run of the cell's loop; the traced one reads the trace."""
    conf, mix = cell.config, cell.traffic
    eng, _ = drv.build_engine(conf, seed)
    admitted = []                   # (admission stamp, queue wait)
    step_admit = eng.scheduler.step_admit

    def stamped_admit():
        out = step_admit()
        admitted.extend((st.t_admit, st.t_admit - st.t_submit)
                        for _, st in out)
        return out
    eng.scheduler.step_admit = stamped_admit
    span_cost = span_cost_us(eng)
    reqs = generator.chat_requests(mix, seed, conf["vocab_size"], seconds)
    with phase_reduce.CompileCounter() as compiles, \
            harness.trace_dir() as tdir:
        t0 = time.perf_counter()
        w0 = t0 + float(mix["warmup_s"])
        win, _, _ = drv.serve(eng, reqs, t0=t0, w0=w0, w1=w0 + seconds,
                              drain_s=float(mix["drain_limit_s"]),
                              trace_dir=tdir if trace else None)
        tr = (trace_reduce.load(trace_reduce.find_xplane(tdir)) if trace
              else None)
    waits = [w for t, w in admitted if win.w0 <= t < win.w1]
    row = {"seed": seed, "traced": trace, "window_s": seconds,
           "engine_steps": win.steps,
           "mean_step_ms": 1e3 * seconds / max(win.steps, 1),
           "compiles_in_window": compiles.between(win.w0, win.w1),
           "span_cost_us": span_cost,
           "admitted": len(waits),
           "queue_wait_ms_mean": (1e3 * sum(waits) / len(waits)
                                  if waits else None)}
    if trace:
        red = phase_reduce.reduce(tr)
        op_scopes = {k: hlo_scopes.instruction_scopes(t)
                     for k, t in eng.program_texts().items()}
        counters = {"engine_steps": win.steps, "engine_step_s": win.step_s,
                    "prefill_prompt_lens": win.prefill_prompt_lens,
                    "decode_calls": win.decode_calls,
                    "decode_rows": win.decode_rows,
                    "decode_context": win.decode_context,
                    "window_s": seconds, "queue_wait_s": waits,
                    "op_scopes": op_scopes}
        reading = harness.Reading(red, counters, conf, peak, cell.chips)
        names = [m["name"] for m in cell.per_layer] + list(METRICS)
        row["metrics"] = {m: harness.reader(cell, m).read(reading)
                          for m in names}
        row["idle_by_phase"] = phase_reduce.idle_by_phase(tr)
        row["scope_s"] = phase_reduce.scope_seconds(red.module_op_s,
                                                    op_scopes)
        dec = row["scope_s"].get("decode_fn", {})
        total = sum(dec.values())
        row["decode_fn_matched_share"] = (
            1.0 - dec.get(phase_reduce.UNMATCHED, 0.0) / total
            if total else None)
        row["busy_s"], row["trace_window_s"] = red.busy_s, red.window_s
    eng.params = eng.pool = None
    del eng
    gc.collect()
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="qwen2-serve-chat")
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 1,2,3")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: the mix's trace window)")
    args = ap.parse_args(argv)
    peaks = harness.load_json(harness.BENCH_DIR / "peaks.json")
    try:
        cell = harness.resolve(harness.ROOT, args.workload)
        device = harness.device_check(cell.chips, peaks)
    except harness.Refused as e:
        print(f"phases: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    drv = harness.load_module(cell.driver)
    seconds = args.seconds or float(cell.traffic["trace_window_s"])
    print(json.dumps({"device": device}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",") if s):
        for trace in (False, True):
            print(json.dumps(measure(cell, drv, seed, seconds, trace,
                                     peaks[device["kind"]])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
