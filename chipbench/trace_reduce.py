"""Reduce a profiler trace (`.xplane.pb`) to device busy and idle time,
time by operation and by program, and collective time with its exposed
part.

Device planes are `/device:TPU:<n>`.  Their "XLA Ops" line holds one
event per executed HLO operation, named by its HLO text; a loop's event
(`while`) spans the events of its body, so only events that contain no
other are counted per operation.  The "Async XLA Ops" line spans each
asynchronous operation from its start to its done, and the "XLA Modules"
line holds one event per program run.  Host planes (`/host:...`) hold
the benchmark's own annotations, among them `chipbench.window`, which
bounds the measured interval.  On a v5e the device timeline sits about a
millisecond from the host's, which the window of seconds does not feel.

Definitions, per device and then averaged over the devices that ran an
operation:

- busy: the length of the union of operation intervals in the window;
- collective time: the length of the union of collective operations
  (all-reduce, all-gather, reduce-scatter, collective-permute,
  all-to-all), their async spans from start to done included;
- exposed collective time: that union minus the union of every other
  operation.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_ANNOTATION = "chipbench.window"
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all",
                       "collective-broadcast", "ragged-all-to-all")


def is_collective(op_name: str) -> bool:
    return op_name.split(".", 1)[0].startswith(COLLECTIVE_PREFIXES)


@dataclasses.dataclass
class Trace:
    """Events as (start_ns, end_ns, name) tuples; operations are named by
    their HLO instruction ("fusion.12", "all-reduce-start.3")."""
    ops: dict            # device plane name -> [event]
    modules: dict        # device plane name -> [event]
    host: list           # [event] of every host plane, zero-length dropped
    async_ops: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Reduction:
    window_s: float
    n_devices: int
    busy_s: float                  # averaged over devices
    collective_s: float            # averaged over devices
    exposed_collective_s: float    # averaged over devices
    op_s: dict                     # op name -> seconds, device average
    module_s: dict                 # program name -> seconds, device avg
    idle_gaps: list                # [(host label, seconds)], longest first


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under "
                                f"{log_dir}")
    return found[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, async_ops, modules, host = {}, {}, {}, []
    lines = {"XLA Ops": ops, "Async XLA Ops": async_ops,
             "XLA Modules": modules}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].setdefault(plane.name, []).extend(
                        (e.start_ns, e.end_ns, op_name(e.name))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.end_ns, e.name)
                            for e in line.events if e.end_ns > e.start_ns)
    return Trace(ops=ops, modules=modules, host=host, async_ops=async_ops)


def op_name(hlo_text: str) -> str:
    """ "%fusion.12 = bf16[..] fusion(..)" -> "fusion.12"."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def leaves(events) -> list:
    """The events that contain no other event (a loop's body ops, not the
    loop)."""
    evs = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    parent, stack = [False] * len(evs), []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][1]:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(evs, parent) if not p]


def union(intervals) -> list:
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def minus(a, b) -> float:
    """Length of merged intervals `a` not covered by merged `b`."""
    covered, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total(a) - covered


def clip(events, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def window_of(trace: Trace) -> tuple[float, float]:
    """The last `chipbench.window` annotation, else the span of every
    device operation."""
    marks = [(s, e) for s, e, n in trace.host if n == WINDOW_ANNOTATION]
    if marks:
        return max(marks)
    evs = [ev for evs in trace.ops.values() for ev in evs]
    if not evs:
        raise ValueError("trace holds no device operation")
    return min(s for s, _, _ in evs), max(e for _, e, _ in evs)


def _label(host, lo: float, hi: float) -> str:
    """The innermost host event covering the middle of [lo, hi]."""
    mid = (lo + hi) / 2
    best = None
    for s, e, n in host:
        if s <= mid < e and (best is None or s > best[0]):
            best = (s, n)
    return best[1] if best else "no host event"


def reduce(trace: Trace, window: tuple[float, float] | None = None,
           top: int = 10) -> Reduction:
    lo, hi = window or window_of(trace)
    devices = [d for d, evs in sorted(trace.ops.items())
               if clip(evs, lo, hi)]
    if not devices:
        raise ValueError("no device operation inside the window")
    n = len(devices)
    busy = coll = exposed = 0.0
    op_s, module_s, gaps = {}, {}, []
    for i, dev in enumerate(devices):
        evs = clip(trace.ops[dev], lo, hi)
        merged = union(evs)
        busy += total(merged)
        leaf = leaves(evs)
        c = union([ev for ev in leaf + clip(trace.async_ops.get(dev, []),
                                            lo, hi)
                   if is_collective(ev[2])])
        coll += total(c)
        exposed += minus(c, union(
            [ev for ev in leaf if not is_collective(ev[2])]))
        for s, e, name in leaf:
            op_s[name] = op_s.get(name, 0.0) + (e - s)
        for s, e, name in clip(trace.modules.get(dev, []), lo, hi):
            module_s[name] = module_s.get(name, 0.0) + (e - s)
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[k], edges[k + 1])
                    for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return Reduction(
        window_s=(hi - lo) * 1e-9, n_devices=n, busy_s=busy / n * 1e-9,
        collective_s=coll / n * 1e-9, exposed_collective_s=exposed / n * 1e-9,
        op_s={k: v / n * 1e-9 for k, v in op_s.items()},
        module_s={k: v / n * 1e-9 for k, v in module_s.items()},
        idle_gaps=[(_label(trace.host, s, e), (e - s) * 1e-9)
                   for s, e in gaps])


def top_ops(red: Reduction, k: int = 10) -> list:
    return [[name, s] for name, s in sorted(red.op_s.items(),
                                            key=lambda kv: -kv[1])[:k]]


def describe(path: str) -> str:
    """Planes, lines and event counts of a trace, for reading it by hand."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})[:6]
            rows.append(f"{plane.name} | {line.name} | {len(evs)} | {names}")
    return "\n".join(rows)
