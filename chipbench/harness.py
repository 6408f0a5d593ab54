"""The harness: finds a cell's configuration, traffic mix, driver, limits
and per-layer metric readers by the names in `BENCHMARK.json`, refuses
to run anywhere but on the chips the cell asks for, runs the driver, and
prints the result.

Everything that belongs to one configuration, mix, cell or metric is a
file of its own, found by name:

  <file of the configuration entry>        the model's sizes and settings
  chipbench/traffic/<traffic>.json          the mix; names its driver
  chipbench/drivers/<driver>.py             run(ctx) -> Outcome
  chipbench/limits/<cell>.json              limits of the compared numbers
  chipbench/metrics/<per-layer name>.py     read(reading) -> value or None

Standard output ends with one JSON line: correct, attempted, failed,
metrics, device, (with --trace 1) breakdown, and checks last.  Standard
error ends with the same checks, one per line.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import pathlib
import sys
import tempfile
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Refused(Exception):
    """The run cannot be measured here."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict             # the configuration file's contents
    traffic: dict            # the mix file's contents
    driver: pathlib.Path
    limits: dict
    end_to_end: list         # BENCHMARK.json metric entries of this cell
    per_layer: list
    root: pathlib.Path


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(root: pathlib.Path, name: str) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    bench = root / BENCH_DIR.name
    traffic = load_json(bench / "traffic" / f"{wl['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=wl["chips"],
                config=load_json(root / entry["file"]), traffic=traffic,
                driver=bench / "drivers" / f"{traffic['driver']}.py",
                limits=load_json(bench / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, root=root)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(cell: Cell, metric: str):
    return load_module(cell.root / BENCH_DIR.name / "metrics"
                       / f"{metric}.py")


def device_check(chips: int, peaks: dict) -> dict:
    """The devices JAX found, or Refused unless they are TPUs of a kind in
    the peak table, at least `chips` of them."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise Refused(f"needs a TPU; JAX found platform {d.platform!r} "
                      f"({d.device_kind})")
    if len(devs) < chips:
        raise Refused(f"cell needs {chips} chips; JAX found {len(devs)}")
    if d.device_kind not in peaks:
        raise Refused(f"device kind {d.device_kind!r} is not in "
                      f"peaks.json ({sorted(peaks)})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


@dataclasses.dataclass
class Context:
    """What a driver gets."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float           # process start, perf_counter clock
    peak: dict               # this device kind's row of peaks.json


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    ok: bool


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""
    attempted: int
    failed: int
    checks: list                     # [Check]
    end_to_end: dict                 # metric name -> value
    memory_peak_bytes: int
    counters: dict = dataclasses.field(default_factory=dict)
    reduction: object = None         # trace_reduce.Reduction with --trace 1


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets."""
    reduction: object                # trace_reduce.Reduction
    counters: dict
    config: dict
    peak: dict
    chips: int


def trace_dir():
    """A profiler log directory under TMPDIR, removed on exit."""
    return tempfile.TemporaryDirectory(prefix="chipbench-trace-")


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def result_line(cell: Cell, out: Outcome, device: dict, trace: bool,
                reading: Reading | None) -> dict:
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = reader(cell, m["name"]).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=out.memory_peak_bytes)
    line = {"correct": all(c.ok for c in out.checks),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": dev}
    if trace:
        from chipbench import trace_reduce

        red = out.reduction
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        line["breakdown"] = {
            "device_ops": trace_reduce.top_ops(red),
            "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def parse(argv):
    ap = argparse.ArgumentParser(description="chip benchmark: one run of "
                                 "one cell of BENCHMARK.json")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    if args.seed < 0:
        print(f"chipbench: --seed must be >= 0, got {args.seed}",
              file=sys.stderr)
        return 2
    peaks = load_json(BENCH_DIR / "peaks.json")
    try:
        cell = resolve(ROOT, args.workload)
        device = device_check(cell.chips, peaks)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    peak = peaks[device["kind"]]
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), t_start=t_start, peak=peak)
    out = load_module(cell.driver).run(ctx)
    reading = (Reading(out.reduction, out.counters, cell.config, peak,
                       cell.chips) if args.trace else None)
    line = result_line(cell, out, device, bool(args.trace), reading)
    for c in out.checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
