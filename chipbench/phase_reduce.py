"""The trace reduction of `trace_reduce.py`, extended by what the program's
own spans and named scopes put on the profiler's clock.

- host_spans: name -> (count, seconds) of the host events that start in
  the window, among them the serving engine's `serve.*` phases;
- module_op_s: (program, op) -> device seconds, each leaf operation
  assigned to the `XLA Modules` event (one run of a program) that
  contains it, averaged over the devices that ran an operation;
- idle_by_phase: the window's idle device time, grouped by the innermost
  `serve.*` span covering the middle of each gap;
- scope_seconds: module_op_s grouped by the named scope of each
  operation (`hlo_scopes.py`);
- CompileCounter: backend compiles and persistent-cache loads, each with
  the host time it was recorded at.

The fields `trace_reduce.reduce` gives are left exactly as it gives them.
"""
from __future__ import annotations

import dataclasses
import time

from chipbench import trace_reduce as tr

PHASE_PREFIX = "serve."
OUTSIDE = "outside serve.step"
UNMATCHED = "unmatched"              # an op the program's text lacks


@dataclasses.dataclass
class Reduction(tr.Reduction):
    host_spans: dict = dataclasses.field(default_factory=dict)
    module_op_s: dict = dataclasses.field(default_factory=dict)


def _device_events(trace: tr.Trace, lo: float, hi: float):
    """(device, clipped ops) of the devices that ran an op in the window,
    in the order `trace_reduce.reduce` takes them."""
    out = []
    for dev, evs in sorted(trace.ops.items()):
        clipped = tr.clip(evs, lo, hi)
        if clipped:
            out.append((dev, clipped))
    return out


def host_spans(trace: tr.Trace, lo: float, hi: float) -> dict:
    out = {}
    for s, e, name in trace.host:
        if lo <= s < hi:
            n, sec = out.get(name, (0, 0.0))
            out[name] = (n + 1, sec + (e - s) * 1e-9)
    return out


def module_op_s(trace: tr.Trace, lo: float, hi: float) -> dict:
    """Leaf ops outside every program run count under program ""."""
    devices = _device_events(trace, lo, hi)
    out = {}
    for dev, evs in devices:
        mods = sorted(tr.clip(trace.modules.get(dev, []), lo, hi))
        k = 0
        for s, e, op in tr.leaves(evs):            # sorted by start
            while k < len(mods) and mods[k][1] <= s:
                k += 1
            prog = mods[k][2] if k < len(mods) and mods[k][0] <= s else ""
            out[(prog, op)] = out.get((prog, op), 0.0) + (e - s)
    n = max(len(devices), 1)
    return {key: v / n * 1e-9 for key, v in out.items()}


def reduce(trace: tr.Trace, window: tuple[float, float] | None = None,
           top: int = 10) -> Reduction:
    lo, hi = window or tr.window_of(trace)
    base = tr.reduce(trace, (lo, hi), top)
    return Reduction(**vars(base), host_spans=host_spans(trace, lo, hi),
                     module_op_s=module_op_s(trace, lo, hi))


def idle_by_phase(trace: tr.Trace,
                  window: tuple[float, float] | None = None) -> dict:
    """Idle seconds of the first device that ran an op in the window, by
    the innermost `serve.*` host span covering each gap's middle (the
    latest to start; of two that start together, the first to end)."""
    lo, hi = window or tr.window_of(trace)
    devices = _device_events(trace, lo, hi)
    if not devices:
        return {}
    merged = tr.union(devices[0][1])
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    spans = [ev for ev in trace.host if ev[2].startswith(PHASE_PREFIX)
             and ev[1] > lo and ev[0] < hi]
    out = {}
    for k in range(0, len(edges), 2):
        g0, g1 = edges[k], edges[k + 1]
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        inner = max(((s, -e, name) for s, e, name in spans
                     if s <= mid < e), default=None)
        label = inner[2] if inner else OUTSIDE
        out[label] = out.get(label, 0.0) + (g1 - g0) * 1e-9
    return out


def scope_seconds(module_op: dict, op_scopes: dict) -> dict:
    """{program key: {scope: device seconds}} for each key of `op_scopes`
    ({"decode_fn": {op: scope}, ...}) found in a program's trace name;
    ops missing from that program's text count as `unmatched`."""
    out = {}
    for (prog, op), sec in module_op.items():
        for key, scopes in op_scopes.items():
            if key in prog:
                by = out.setdefault(key, {})
                scope = scopes.get(op, UNMATCHED)
                by[scope] = by.get(scope, 0.0) + sec
                break
    return out


class CompileCounter:
    """Listens to `jax.monitoring` while open: each backend compile
    (a persistent-cache load included) and each cache load, stamped with
    `time.perf_counter()` when recorded."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles, self.cache_loads = [], []

    def _on_duration(self, event, duration, **kw):
        if event == self.BACKEND_COMPILE:
            self.compiles.append(time.perf_counter())

    def _on_event(self, event, **kw):
        if event == self.CACHE_HIT:
            self.cache_loads.append(time.perf_counter())

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def between(self, t0: float, t1: float) -> dict:
        return {"compiles": sum(t0 <= t < t1 for t in self.compiles),
                "cache_loads": sum(t0 <= t < t1 for t in self.cache_loads)}
