"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (paper Figs. 3-9 + the fidelity
acceptance rows + kernel layer), then the schedule/congestion/substrate/
tuner/fused/serve/trace/fault reports and the revived roofline bench
(profiled steps on compute/memory/NoC rooflines — no artifacts needed).

``--json OUT`` additionally writes every bench's rows as one
machine-readable ``BENCH_*.json`` document (standardized
size/measured/predicted/picked fields parsed from each row — the CI
perf-trajectory artifact) stamped with this machine's fingerprint, so
``check_regression.py`` can warn on cross-machine comparisons;
``--only a,b`` restricts which benches run.

  PYTHONPATH=src python -m benchmarks.run
  PYTHONPATH=src python -m benchmarks.run --only patterns,tuner \\
      --json bench-reports/BENCH_smoke.json
"""
import argparse
import json
import os
import pathlib
import platform
import re
import socket
import sys
import time
import traceback

sys.path.insert(0, "src")

# Best-effort extractors for the standardized JSON rows.  Every bench
# module prints (name, us, derived) triples; sizes live in ``_<N>B`` name
# suffixes, predictions in ``fit=``/``noc=``/``pred=`` derived fields,
# picks in ``picked=``/``picks=`` fields or auto_pick rows.
_SIZE_RE = re.compile(r"_(\d+)B(?:_|$)")
_PRED_RE = re.compile(r"(?:fit|noc|pred(?:icted)?)=([\d.eE+-]+)us")
_PICK_RE = re.compile(r"pick(?:ed|s)?=([\w/|.-]+)")


def _std_row(bench: str, name: str, us, derived: str) -> dict:
    size = _SIZE_RE.search(name)
    pred = _PRED_RE.search(derived)
    pick = _PICK_RE.search(derived)
    if pick is None and "pick" in name:
        m = re.match(r"([a-z_]\w*)", derived)
        pick = m
    return {
        "bench": bench,
        "name": name,
        "measured_us": float(us),
        "derived": derived,
        "size_bytes": int(size.group(1)) if size else None,
        "predicted_us": float(pred.group(1)) if pred else None,
        "picked": pick.group(1) if pick else None,
    }


def machine_fingerprint() -> dict:
    """Hostname/CPU/jax-stack identity stamped into every BENCH_*.json
    header — wall times are only comparable within one fingerprint
    (check_regression warns loudly when they differ)."""
    import jax
    import jaxlib
    return {
        "hostname": socket.gethostname(),
        "cpus": os.cpu_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "xla_backend": jax.default_backend(),
        "device_count": jax.device_count(),
    }


def _run_paper():
    from . import paper_benches
    print("name,us_per_call,derived")
    for bench in paper_benches.ALL:
        bench()
    return paper_benches


def _module_runner(modname: str, header: str):
    def run():
        print(f"\n== {header} ==")
        import importlib
        mod = importlib.import_module(f".{modname}", __package__)
        mod.main()
        return mod
    return run


# Ordered registry: (key, fatal?, runner).  A fatal bench's failure stops
# the harness; a non-fatal one is reported, the rest still run, and the
# harness exits non-zero at the end.
BENCHES = [
    ("paper", True, _run_paper),
    ("patterns", False, _module_runner(
        "bench_patterns",
        "compiled CommPattern schedules: predicted vs measured")),
    ("congestion", False, _module_runner(
        "bench_congestion",
        "congestion model: predicted vs measured under contention")),
    ("tuner", False, _module_runner(
        "bench_tuner",
        "measured-performance autotuner: sweep + tuned-selector checks")),
    ("substrate", False, _module_runner(
        "bench_substrate", "substrate A/B (ARL shmem vs XLA 'eLib')")),
    ("fused", False, _module_runner(
        "bench_fused",
        "fused comm-compute: ring attention + RS->AdamW (bytes + time)")),
    ("serve", False, _module_runner(
        "bench_serve",
        "serving engine: per-token p50/p99 + tok/s vs offered load")),
    ("trace", False, _module_runner(
        "bench_trace",
        "observability: tracing-level overhead ladder + export costs")),
    ("fault", False, _module_runner(
        "bench_fault",
        "fault tolerance: async-ckpt overlap overhead + recovery time")),
    ("roofline", False, _module_runner(
        "roofline",
        "roofline: profiled train/decode steps vs compute/memory/NoC "
        "ceilings")),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="",
                    help="write all rows as one machine-readable "
                         "BENCH_*.json (per-row size/measured/predicted/"
                         "picked fields)")
    ap.add_argument("--only", default="",
                    help="comma-separated bench keys to run "
                         f"({','.join(k for k, _, _ in BENCHES)}); "
                         "default: all")
    args = ap.parse_args(argv)
    only = {k.strip() for k in args.only.split(",") if k.strip()}
    unknown = only - {k for k, _, _ in BENCHES}
    if unknown:
        raise SystemExit(f"unknown bench keys: {sorted(unknown)}")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows: list[dict] = []
    failed: list[str] = []
    for key, fatal, runner in BENCHES:
        if only and key not in only:
            continue
        try:
            mod = runner()
        except Exception:
            if fatal:
                raise
            traceback.print_exc()
            print(f"{key} bench FAILED")
            failed.append(key)
            continue
        for name, us, derived in getattr(mod, "ROWS", []):
            rows.append(_std_row(key, name, us, str(derived)))

    if args.json:
        out = pathlib.Path(args.json)
        out.parent.mkdir(parents=True, exist_ok=True)
        doc = {"schema": 1,
               "generated_unix": time.time(),
               "machine": machine_fingerprint(),
               "benches": sorted({r["bench"] for r in rows}),
               "rows": rows}
        out.write_text(json.dumps(doc, indent=1))
        print(f"\n[run] wrote {len(rows)} rows to {out}")
    if failed:
        raise SystemExit(f"[run] benches failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
