"""Readings from which a cell's limits are set (PERF.md gives them): the
compared numbers of sound program runs on many seeds, and of the
control (the reference in fp8 in the program's place).  One process for
every seed, so the programs compile once.  Prints one JSON line per seed
and kind.

  python3 chipbench/tools/readings.py --workload qwen2-serve-chat \
      --seeds 1,2,3 --control-seeds 1 --seconds 51

The benchmark's own runs never run the control.
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

from chipbench import harness  # noqa: E402


def ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def serve_readings(cell, drv, seed: int, seconds: float, control: bool):
    import numpy as np

    mix = cell.traffic
    eng, reqs, rids, win, late, _ = drv.serve_once(cell.config, mix, seed,
                                                   seconds)
    seqs = drv.sample(eng, reqs, rids, win, np.random.default_rng(seed + 1),
                      mix["sample"])
    eng.params = eng.pool = None
    del eng
    gc.collect()
    gaps, cgaps = drv.reference_gaps(cell.config, seed, seqs,
                                     control=control)
    row = {"seed": seed, "kind": "program", "tokens": int(len(gaps)),
           "requests": len(seqs),
           "last_position": max(len(p) + len(x) - 1 for p, x in seqs),
           "max_logit_gap": float(np.max(gaps)),
           "p99_gap": float(np.percentile(gaps, 99)),
           "argmax_equal": float(np.mean(gaps == 0))}
    rows = [row]
    if control:
        rows.append({"seed": seed, "kind": "control_fp8",
                     "tokens": int(len(cgaps)),
                     "max_logit_gap": float(np.max(cgaps)),
                     "p99_gap": float(np.percentile(cgaps, 99)),
                     "argmax_equal": float(np.mean(cgaps == 0))})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.ROOT, args.workload)
    harness.device_check(cell.chips,
                         harness.load_json(harness.BENCH_DIR / "peaks.json"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    drv = harness.load_module(cell.driver)
    control = set(ints(args.control_seeds))
    for seed in ints(args.seeds):
        t = time.perf_counter()
        rows = serve_readings(cell, drv, seed, args.seconds, seed in control)
        for row in rows:
            row["wall_s"] = time.perf_counter() - t
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
