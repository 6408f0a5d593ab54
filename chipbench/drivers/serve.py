"""Serving driver: an open-loop request stream into the program's paged
`ServeEngine` (the path of `python -m repro.launch.serve`).

Set-up makes the weights on the chip from the seed, builds the engine,
runs one request through the prefill and decode programs (compiled or
read from the cache), and serves the mix's warm-up traffic, so the slots
are at steady occupancy when the window opens.  The window is a stretch
of the same open loop: requests are submitted when due, whatever the
engine's backlog, and every engine step's return stamps the tokens it
emitted.

- ttft_p95_ms: over the requests due in the window, first-token time
  minus scheduled arrival; a request with no first token by the end of
  the drain counts with the drain's end as its first-token time.
- itl_p95_ms: over every gap between consecutive tokens of a request
  whose later token came in the window (tokens of one step share its
  time).
- serve_tokens_per_s: tokens emitted in the window over its length.

After the window the program's state is freed, and the plain reference
scores a sample of the requests that finished in the window or its
drain (warm-up arrivals among them), drawn from the seed with the one of
longest context (prompt and answer) first: the widest gap by which a
served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import contextlib
import gc
import math
import sys
import time

import numpy as np

from chipbench import generator, harness, sut, trace_reduce, weights

# weights: gains 1 + N(0, 0.1^2) and QKV biases N(0, 0.1^2), so the
# comparison sees a wrong norm or bias convention
NORM_STD = BIAS_STD = 0.1


def build_engine(conf: dict, seed: int, params=None):
    """A warm engine on weights made from the seed (or on `params`, the
    weights of an engine built before).  Returns (engine, devices)."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeEngine

    s = conf["serve"]
    cfg = sut.model_config(conf)
    mesh = make_mesh(*s["mesh"])
    if params is None:
        params, _ = sut.make_params(conf, cfg, mesh, seed,
                                    norm_std=NORM_STD, bias_std=BIAS_STD)
    eng = ServeEngine(cfg, mesh, params=params, max_slots=s["max_slots"],
                      page_size=s["page_size"], max_seq=s["max_seq"],
                      prompt_bucket=s["prompt_bucket"])
    eng.submit(np.ones(1, np.int32), 2)        # prefill + decode programs
    eng.run()
    jax.block_until_ready(eng.pool)
    return eng, list(mesh.devices.flat)


class Window:
    """Token and step records of one serving loop."""

    def __init__(self, w0: float, w1: float):
        self.w0, self.w1 = w0, w1
        self.seen, self.first, self.last = {}, {}, {}
        self.itl = []
        self.tokens = 0
        self.steps, self.step_s = 0, 0.0
        self.prefill_prompt_lens = []      # prompt lengths admitted
        self.decode_rows = self.decode_context = self.decode_calls = 0
        self.peak_slots = self.peak_context = 0
        self.queue_at_close = None         # requests waiting at w1

    def in_window(self, t: float) -> bool:
        return self.w0 <= t < self.w1

    def record(self, eng, out: dict, t_begin: float, t: float,
               prompt_len: dict) -> None:
        counted = self.in_window(t_begin)
        if counted:
            self.steps += 1
            self.step_s += t - t_begin
            if out["decoded"]:
                self.decode_calls += 1
        admitted = set(out["admitted"])
        for st in eng.scheduler.slots:
            if st is None:
                continue
            prev = self.seen.get(st.rid, 0)
            new = len(st.out) - prev
            if new <= 0:
                continue
            self.seen[st.rid] = len(st.out)
            if prev == 0:
                self.first[st.rid] = t
            else:
                gap = t - self.last[st.rid]
                if self.in_window(t):
                    self.itl.append(gap)
            if self.in_window(t):
                self.itl.extend([0.0] * (new - 1))
                self.tokens += new
            self.last[st.rid] = t
            if counted:
                decoded = new - (1 if st.rid in admitted else 0)
                if st.rid in admitted:
                    self.prefill_prompt_lens.append(prompt_len[st.rid])
                if decoded:
                    self.decode_rows += 1
                    self.decode_context += st.pos
        if counted:
            live = [st for st in eng.scheduler.slots if st is not None]
            self.peak_slots = max(self.peak_slots, len(live))
            self.peak_context = max(self.peak_context,
                                    sum(st.pos for st in live))


def serve(eng, reqs, *, t0: float, w0: float, w1: float, drain_s: float,
          trace_dir: str | None = None) -> tuple[Window, list, list]:
    """Run the open loop whose time zero is t0.  Returns the window's
    records, each request's rid and each request's submission lateness."""
    import jax

    win = Window(w0, w1)
    rids, late, prompt_len = [], [], {}
    sched = eng.scheduler
    due = [i for i, r in enumerate(reqs) if r.in_window]
    i, tracing, ann = 0, False, None
    span = (lambda name: jax.profiler.TraceAnnotation(name)) \
        if trace_dir else (lambda name: contextlib.nullcontext())
    while True:
        now = time.perf_counter()
        if trace_dir and not tracing and now >= w0 and ann is None:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=harness.profile_options())
            ann = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_ANNOTATION)
            ann.__enter__()
            tracing = True
        if now >= w1 and win.queue_at_close is None:
            win.queue_at_close = len(sched.queue)
        if tracing and now >= w1:
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        while i < len(reqs) and t0 + reqs[i].arrival_s <= now:
            rid = eng.submit(reqs[i].prompt, reqs[i].max_new)
            rids.append(rid)
            prompt_len[rid] = len(reqs[i].prompt)
            late.append(now - t0 - reqs[i].arrival_s)
            i += 1
        if now >= w1 and all(rids[j] in win.first for j in due
                             if j < len(rids)) and i >= len(reqs):
            break
        if now >= w1 + drain_s:
            break
        if sched.idle():
            if i < len(reqs):
                with span("chipbench.serve.wait"):
                    time.sleep(max(0.0, t0 + reqs[i].arrival_s
                                   - time.perf_counter()))
            continue
        with span("chipbench.serve.step"):
            out = eng.step()
        win.record(eng, out, now, time.perf_counter(), prompt_len)
    if tracing:
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return win, rids, late


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def sample(eng, reqs, rids, win: Window, rng, spec: dict) -> list:
    """(prompt, served) pairs of requests that finished at or after the
    window's start: the one of longest context, then random ones until
    the spec's count and served-token floor are met."""
    done = [j for j in range(len(rids))
            if rids[j] in eng.results and win.last[rids[j]] >= win.w0]
    if not done:
        return []
    done.sort(key=lambda j: -(len(reqs[j].prompt) + reqs[j].max_new))
    pick = [done[0]]
    rest = [done[k] for k in rng.permutation(len(done) - 1) + 1]
    for j in rest:
        served = sum(len(eng.results[rids[k]]) for k in pick)
        if len(pick) > spec["random_requests"] and \
                served >= spec["min_served_tokens"]:
            break
        pick.append(j)
    return [(reqs[j].prompt, np.asarray(eng.results[rids[j]], np.int32))
            for j in pick]


def reference_gaps(conf: dict, seed: int, seqs, control: bool = False):
    """Teacher-forced reference gaps of the served tokens (and of the fp8
    control's first tokens) on weights made again from the seed."""
    import jax
    from chipbench.reference import readings

    w = jax.jit(lambda k: weights.make(conf, k, norm_std=NORM_STD,
                                       bias_std=BIAS_STD))(
        weights.seed_key(seed))
    return readings.served_gaps(conf, w, seqs, pad_to=conf["serve"]["max_seq"],
                                control=control)


def compare(limits: dict, gaps, short: int, bad_ids: int,
            unanswered: int) -> list:
    """The checks that decide `correct`."""
    gap = float(np.max(gaps))
    return [
        harness.Check("max_logit_gap", gap, limits["max_logit_gap"],
                      gap <= limits["max_logit_gap"]),
        harness.Check("short_answers", short, 0, short == 0),
        harness.Check("bad_token_ids", bad_ids, 0, bad_ids == 0),
        harness.Check("unanswered", unanswered, 0, unanswered == 0),
    ]


def serve_once(conf: dict, mix: dict, seed: int, seconds: float,
               trace_dir: str | None = None):
    """Engine, traffic and loop of one run.  Returns (eng, reqs, rids,
    window, lateness, devices)."""
    eng, devices = build_engine(conf, seed)
    reqs = generator.chat_requests(mix, seed, conf["vocab_size"], seconds)
    t0 = time.perf_counter()
    w0 = t0 + float(mix["warmup_s"])
    win, rids, late = serve(eng, reqs, t0=t0, w0=w0, w1=w0 + seconds,
                            drain_s=float(mix["drain_limit_s"]),
                            trace_dir=trace_dir)
    return eng, reqs, rids, win, late, devices


def run(ctx) -> harness.Outcome:
    conf, mix = ctx.cell.config, ctx.cell.traffic
    seconds = min(ctx.seconds, mix["trace_window_s"]) if ctx.trace \
        else ctx.seconds
    with harness.trace_dir() as tdir:
        eng, reqs, rids, win, late, devices = serve_once(
            conf, mix, ctx.seed, seconds, tdir if ctx.trace else None)
        red = (trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(tdir))) if ctx.trace else None)
    setup_s = win.w0 - ctx.t_start
    mem = harness.memory_peak_bytes(devices)
    due = [j for j, r in enumerate(reqs) if r.in_window]
    drain_end = time.perf_counter()
    ttft = []
    for j in due:
        t_arr = win.w0 - float(mix["warmup_s"]) + reqs[j].arrival_s
        got = win.first.get(rids[j]) if j < len(rids) else None
        ttft.append((got if got is not None else drain_end) - t_arr)
    failed = sum(1 for j in due
                 if j >= len(rids) or rids[j] not in win.first)
    finished = [j for j in due if j < len(rids) and rids[j] in eng.results]
    short = sum(1 for j in finished
                if len(eng.results[rids[j]]) != reqs[j].max_new)
    bad_ids = sum(int(np.sum((eng.results[rids[j]] < 0)
                             | (eng.results[rids[j]] >= conf["vocab_size"])))
                  for j in finished)
    if not win.itl:
        raise RuntimeError("no token gaps in the window")
    seqs = sample(eng, reqs, rids, win, np.random.default_rng(ctx.seed + 1),
                  mix["sample"])
    s = conf["serve"]
    print(f"serve: {len(due)} requests due in {seconds:.1f}s, {failed} "
          f"without a first token, {win.tokens} tokens, {win.steps} "
          f"engine steps; submission late p95 "
          f"{percentile(late, 95) * 1e3:.3f} ms; live slots mean "
          f"{win.decode_rows / max(win.decode_calls, 1):.1f} peak "
          f"{win.peak_slots} of {s['max_slots']}; live KV peak "
          f"{win.peak_context} of {s['max_slots'] * s['max_seq']} "
          f"positions; compared {sum(len(x) for _, x in seqs)} served "
          f"tokens of {len(seqs)} requests, last position "
          f"{max((len(p) + len(x) - 1 for p, x in seqs), default=0)}",
          flush=True, file=sys.stderr)
    eng.params = eng.pool = None
    del eng
    gc.collect()
    gaps, _ = reference_gaps(conf, ctx.seed, seqs) if seqs else (
        np.array([math.inf]), None)
    checks = compare(ctx.cell.limits, gaps, short, bad_ids, failed)
    e2e = {"setup_s": setup_s,
           "ttft_p95_ms": percentile(ttft, 95) * 1e3,
           "itl_p95_ms": percentile(win.itl, 95) * 1e3,
           "serve_tokens_per_s": win.tokens / seconds}
    counters = {"engine_steps": win.steps, "engine_step_s": win.step_s,
                "prefill_prompt_lens": win.prefill_prompt_lens,
                "decode_calls": win.decode_calls,
                "decode_rows": win.decode_rows,
                "decode_context": win.decode_context,
                "window_s": seconds}
    return harness.Outcome(attempted=len(due), failed=failed, checks=checks,
                           end_to_end=e2e, memory_peak_bytes=mem,
                           counters=counters, reduction=red)
