"""Serving driver for DeepSeek-V3 at one expert-parallel chip's share: the
open-loop request stream of `drivers/serve.py` into the program's paged
`ServeEngine`, on the MoE+MLA model of `sut_mla_moe`.

The loop, the window's records, the sample and the checks are
`drivers/serve.py`'s own (`serve`, `Window`, `sample`, `compare`,
`percentile`); this driver builds the model from its own weights, reads
the token rows the held experts computed in the window's decode steps
from the engine's `ServeMetrics`, maps the programs' instructions to the
model's scopes in trace mode, and scores the sample against the plain
DeepSeek-V3 reference (`reference/mla_moe.py`), made again layer by
layer from the seed after the engine is freed.

bf16 rounding of the program's hidden states can flip a held expert's
selection where the reference's routing lies within a hair of a tie
(`mla_moe.held_margin`, printed beside the widest gaps); the flipped
expert's output moves that token's logits and, through the latent rows
it writes, the tokens after it.  Random routing weights leave about a
fifth of the served tokens that close to a flip, so the widest gap
follows the flips; the gap is judged at its 99th percentile over the
compared tokens instead (`p99_logit_gap`, `compare_p99`).
"""
from __future__ import annotations

import gc
import math
import sys
import time

import numpy as np

from chipbench import (generator, harness, phase_reduce, scopes_mla_moe,
                       sut_mla_moe, weights_mla_moe)
from chipbench.drivers.serve import (Window, compare, percentile,  # noqa: F401
                                     sample, serve)


def build_engine(conf: dict, seed: int, params=None):
    """A warm engine on weights made from the seed (or on `params`), with
    a `ServeMetrics`.  Returns (engine, devices)."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeEngine
    from repro.serve.metrics import ServeMetrics

    s = conf["serve"]
    cfg = sut_mla_moe.model_config(conf)
    mesh = make_mesh(*s["mesh"])
    if params is None:
        params, _ = sut_mla_moe.make_params(conf, cfg, mesh, seed)
    eng = ServeEngine(cfg, mesh, params=params, max_slots=s["max_slots"],
                      page_size=s["page_size"], max_seq=s["max_seq"],
                      prompt_bucket=s["prompt_bucket"],
                      metrics=ServeMetrics())
    eng.submit(np.ones(1, np.int32), 2)        # prefill + decode programs
    eng.run()
    jax.block_until_ready(eng.pool)
    return eng, list(mesh.devices.flat)


class ExpertRows:
    """The held experts' token rows of the decode steps that begin in
    [w0, w1): what the steps added to the engine's `ServeMetrics`
    `serve.expert_rows` (the mean over MoE layers x held experts of each
    decode step), as a total over layers and experts."""

    def __init__(self, eng, w0: float, w1: float):
        self.rows, self.steps, self.shape = 0, 0, None
        hist, step = eng.metrics.expert_rows, eng.step

        def counted():
            t, n, total = time.perf_counter(), hist.count, hist.sum
            out = step()
            if w0 <= t < w1 and hist.count > n:
                self.shape = eng.last_expert_rows.shape
                self.rows += round((hist.sum - total) * math.prod(self.shape))
                self.steps += hist.count - n
            return out
        eng.step = counted


def serve_once(conf: dict, mix: dict, seed: int, seconds: float,
               trace_dir: str | None = None):
    """Engine, traffic and loop of one run.  Returns (eng, reqs, rids,
    window, lateness, devices); the engine carries `expert_rows`."""
    eng, devices = build_engine(conf, seed)
    reqs = generator.chat_requests(mix, seed, conf["vocab_size"], seconds)
    t0 = time.perf_counter()
    w0 = t0 + float(mix["warmup_s"])
    eng.expert_rows = ExpertRows(eng, w0, w0 + seconds)
    win, rids, late = serve(eng, reqs, t0=t0, w0=w0, w1=w0 + seconds,
                            drain_s=float(mix["drain_limit_s"]),
                            trace_dir=trace_dir)
    return eng, reqs, rids, win, late, devices


def reference_readings(conf: dict, seed: int, seqs, control: bool = False):
    """(gaps, held-expert routing margins, control gaps) of the served
    tokens."""
    from chipbench.reference import mla_moe

    return mla_moe.served_gaps(conf, weights_mla_moe.seed_key(seed), seqs,
                               pad_to=conf["serve"]["max_seq"],
                               control=control)


def reference_gaps(conf: dict, seed: int, seqs, control: bool = False):
    """(gaps, control gaps), as `drivers/serve.py` gives them."""
    gaps, _, cgaps = reference_readings(conf, seed, seqs, control)
    return gaps, cgaps


def compare_p99(limits: dict, gaps, short: int, bad_ids: int,
                unanswered: int) -> list:
    """`drivers/serve.py`'s checks, with the gap judged at its 99th
    percentile over the compared tokens (`p99_logit_gap`) in place of its
    widest."""
    p99 = float(np.percentile(gaps, 99))
    rest = [c for c in compare({"max_logit_gap": math.inf}, gaps, short,
                               bad_ids, unanswered)
            if c.name != "max_logit_gap"]
    return [harness.Check("p99_logit_gap", p99, limits["p99_logit_gap"],
                          p99 <= limits["p99_logit_gap"])] + rest


def op_scopes(eng) -> dict:
    return {name: scopes_mla_moe.instruction_scopes(text)
            for name, text in eng.program_texts().items()}


def run(ctx) -> harness.Outcome:
    conf, mix = ctx.cell.config, ctx.cell.traffic
    seconds = min(ctx.seconds, mix["trace_window_s"]) if ctx.trace \
        else ctx.seconds
    with harness.trace_dir() as tdir:
        eng, reqs, rids, win, late, devices = serve_once(
            conf, mix, ctx.seed, seconds, tdir if ctx.trace else None)
        red = (phase_reduce.reduce(phase_reduce.tr.load(
            phase_reduce.tr.find_xplane(tdir))) if ctx.trace else None)
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
    counters = {"engine_steps": win.steps, "engine_step_s": win.step_s,
                "prefill_prompt_lens": win.prefill_prompt_lens,
                "decode_calls": win.decode_calls,
                "decode_rows": win.decode_rows,
                "decode_context": win.decode_context,
                "expert_rows": eng.expert_rows.rows,
                "expert_row_steps": eng.expert_rows.steps,
                "expert_rows_shape": eng.expert_rows.shape,
                "window_s": seconds}
    if ctx.trace:
        counters["op_scopes"] = op_scopes(eng)
    s = conf["serve"]
    print(f"serve_mla_moe: decode path={eng.decode_path} "
          f"kv_kind={eng.kv_kind}; {len(due)} requests due in {seconds:.1f}s, "
          f"{failed} without a first token, {win.tokens} tokens, "
          f"{win.steps} engine steps ({win.decode_calls} decodes, "
          f"{len(win.prefill_prompt_lens)} prefills); live slots mean "
          f"{win.decode_rows / max(win.decode_calls, 1):.1f} peak "
          f"{win.peak_slots} of {s['max_slots']}; live KV peak "
          f"{win.peak_context} of {s['max_slots'] * s['max_seq']} "
          f"positions; held-expert rows {eng.expert_rows.rows} over "
          f"{eng.expert_rows.steps} decodes; compared "
          f"{sum(len(x) for _, x in seqs)} served tokens of {len(seqs)} "
          f"requests, last position "
          f"{max((len(p) + len(x) - 1 for p, x in seqs), default=0)}",
          flush=True, file=sys.stderr)
    eng.params = eng.pool = None
    del eng
    gc.collect()
    if seqs:
        gaps, margins, _ = reference_readings(conf, ctx.seed, seqs)
    else:
        gaps, margins = np.array([math.inf]), np.array([math.inf])
    out_checks = compare_p99(ctx.cell.limits, gaps, short, bad_ids, failed)
    itl = np.asarray(win.itl) * 1e3
    widest = np.argsort(-gaps)[:5]
    print("serve_mla_moe: itl ms p50/p90/p95/p99 "
          + "/".join(f"{np.percentile(itl, q):.2f}" for q in (50, 90, 95, 99))
          + "; widest gaps (gap, routing margin) "
          + ", ".join(f"({gaps[i]:.4f}, {margins[i]:.5f})" for i in widest)
          + "; tokens under margin 1e-4/1e-3/1e-2: "
          + "/".join(str(int(np.sum(margins < b))) for b in (1e-4, 1e-3, 1e-2))
          + f" of {len(gaps)}", flush=True, file=sys.stderr)
    e2e = {"setup_s": setup_s,
           "ttft_p95_ms": percentile(ttft, 95) * 1e3,
           "itl_p95_ms": percentile(win.itl, 95) * 1e3,
           "serve_tokens_per_s": win.tokens / seconds}
    return harness.Outcome(attempted=len(due), failed=failed,
                           checks=out_checks, end_to_end=e2e,
                           memory_peak_bytes=mem, counters=counters,
                           reduction=red)
