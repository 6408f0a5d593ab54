"""Host time, in ms, the engine spends in its `serve.prefill` spans
(input build, dispatch and first-token sync of each admitted request)
per `serve.step`, both counted over the spans that start in the traced
window (`host_spans` of `phase_reduce.Reduction`, on the profiler's
clock)."""


def read(r):
    spans = getattr(r.reduction, "host_spans", None) or {}
    steps = spans.get("serve.step", (0, 0.0))[0]
    if not steps:
        return None
    return 1e3 * spans.get("serve.prefill", (0, 0.0))[1] / steps
