"""Readings from which `deepseek-v3-serve-chat2k`'s limits are set
(PERF.md section 2 gives them): per seed, one run of the cell's open loop,
its sample teacher-forced through the plain reference and through the
reference in fp8 (the control, in the program's place).  For each it
prints the gap's 99th percentile and widest, the widest over the tokens
at or above a ladder of routing margins (where bf16 flips of a held
expert lie), the share of tokens under each, and `compare_p99`'s verdict
under the cell's limits.  One process for every seed, so the
programs compile once.

  python3 chipbench/tools/readings_mla_moe.py --seeds 1,2,3 --seconds 15 \
      --out chiprun_out/readings

With `--out`, each seed's per-token gaps, control gaps and margins are
kept as `<out>/<seed>.npz`.  The benchmark's own runs never run the
control.
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

WORKLOAD = "deepseek-v3-serve-chat2k"
BOUNDS = (0.0, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2)


def summary(kind: str, gaps, margins, limits: dict, drv) -> dict:
    import numpy as np

    checks = drv.compare_p99(limits, gaps, 0, 0, 0)
    return {"kind": kind, "tokens": int(len(gaps)),
            "p99_gap": float(np.percentile(gaps, 99)),
            "max_gap": float(np.max(gaps)),
            "by_bound": [{"bound": b, "share_under": float(np.mean(margins < b)),
                          "max_gap_at_or_above": float(
                              np.max(gaps[margins >= b], initial=0.0))}
                         for b in BOUNDS],
            "checks": {c.name: [c.value, c.ok] for c in checks},
            "correct": all(c.ok for c in checks)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.ROOT, WORKLOAD)
    harness.device_check(cell.chips,
                         harness.load_json(harness.BENCH_DIR / "peaks.json"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import numpy as np

    drv = harness.load_module(cell.driver)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    for seed in (int(x) for x in args.seeds.split(",") if x):
        t = time.perf_counter()
        eng, reqs, rids, win, _, _ = drv.serve_once(
            cell.config, cell.traffic, seed, args.seconds)
        seqs = drv.sample(eng, reqs, rids, win,
                          np.random.default_rng(seed + 1),
                          cell.traffic["sample"])
        eng.params = eng.pool = None
        del eng
        gc.collect()
        gaps, margins, cgaps = drv.reference_readings(cell.config, seed,
                                                      seqs, control=True)
        if args.out:
            np.savez(os.path.join(args.out, f"{seed}.npz"), gaps=gaps,
                     margins=margins, control_gaps=cgaps)
        for kind, g in (("program", gaps), ("control_fp8", cgaps)):
            row = summary(kind, g, margins, cell.limits, drv)
            row.update(seed=seed, requests=len(seqs),
                       last_position=max(len(p) + len(x) - 1
                                         for p, x in seqs),
                       wall_s=time.perf_counter() - t)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
