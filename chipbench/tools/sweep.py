"""Knee sweep of a serving cell: the chat mix's open loop at several fixed
rates on one engine's weights, one fresh engine per rate.  Prints one
JSON line per rate.  The knee is the highest rate at which the backlog
does not grow over the window (this and every lower rate): every request
due in it answered, and the median time to first token of the window's
last third at most 1.25x that of its first third.  The last line gives
the knee and 0.8x the knee, the rate a cell below the knee offers.

  python3 chipbench/tools/sweep.py --workload qwen2-serve-chat \
      --rates 3,4,5 --seconds 51 --seed 11
"""
import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import generator, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="qwen2-serve-chat")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.ROOT, args.workload)
    harness.device_check(cell.chips,
                         harness.load_json(harness.BENCH_DIR / "peaks.json"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import numpy as np
    import time

    serve = harness.load_module(cell.driver)
    conf = cell.config
    params, knee = None, None
    rates = sorted(float(r) for r in args.rates.split(","))
    for rate in rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        eng, _ = serve.build_engine(conf, args.seed, params)
        params = eng.params
        reqs = generator.chat_requests(mix, args.seed, conf["vocab_size"],
                                       args.seconds)
        t0 = time.perf_counter()
        w0 = t0 + mix["warmup_s"]
        win, rids, late = serve.serve(eng, reqs, t0=t0, w0=w0,
                                      w1=w0 + args.seconds, drain_s=30.0)
        due = [j for j, r in enumerate(reqs) if r.in_window]
        ttft = np.array([win.first.get(rids[j], np.inf) - (t0 + reqs[j].arrival_s)
                         if j < len(rids) else np.inf for j in due])
        third = len(due) // 3
        row = {"rate": rate, "due": len(due),
               "unanswered": int(np.sum(~np.isfinite(ttft))),
               "tokens_per_s": win.tokens / args.seconds,
               "ttft_p50_ms": float(np.median(ttft) * 1e3),
               "ttft_p95_ms": float(np.percentile(ttft, 95) * 1e3),
               "ttft_first_third_p50_ms": float(np.median(ttft[:third]) * 1e3),
               "ttft_last_third_p50_ms": float(np.median(ttft[-third:]) * 1e3),
               "itl_p50_ms": float(np.median(win.itl) * 1e3),
               "itl_p95_ms": float(np.percentile(win.itl, 95) * 1e3),
               "engine_step_ms": 1e3 * win.step_s / max(win.steps, 1),
               "live_slots_mean": win.decode_rows / max(win.decode_calls, 1),
               "live_slots_peak": win.peak_slots,
               "live_kv_positions_peak": win.peak_context,
               "late_p95_ms": float(np.percentile(late, 95) * 1e3),
               "queue_at_close": win.queue_at_close}
        print(json.dumps(row), flush=True)
        holds = row["unanswered"] == 0 and (row["ttft_last_third_p50_ms"]
                                            <= 1.25 * row["ttft_first_third_p50_ms"])
        if holds and knee == (None if rate == rates[0] else prev):
            knee = rate
        prev = rate
        eng.pool = None
        del eng
    print(json.dumps({"knee": knee, "offer": None if knee is None
                      else round(0.8 * knee, 2)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
