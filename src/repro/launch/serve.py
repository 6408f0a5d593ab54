"""Serving launcher: the continuous-batching engine on the paged
symmetric-heap KV cache (DESIGN.md §15).

Batch mode (default) submits every request up front and drains; with
``--continuous`` a fixed-rate arrival trace streams requests in while
earlier ones decode, exercising per-step join/evict.  Both use the
paged prefill fast-path (ONE forward pass over the prompt bucket fills
the KV pages) instead of the seed launcher's teacher-forced per-token
decode loop.  Models the paged path does not run (ssm/hybrid, MoE
without MLA; `transformer.paged_supported`) fall back to the dense-cache
decode loop.

  python -m repro.launch.serve --arch qwen2-0.5b --smoke --tokens 16
  python -m repro.launch.serve --arch qwen2-0.5b --smoke --continuous \\
      --requests 16 --rate 2 --tokens 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _legacy_decode_loop(cfg, mesh, args):
    """Dense-cache teacher-forced loop, kept for non-paged families."""
    from ..models import transformer
    from ..serve import step as sstep
    from . import build

    dp, tp, _ = build.mesh_dims(mesh)
    B = args.batch
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab, size=(B, args.prompt_len),
                          dtype=np.int32)
    with jax.set_mesh(mesh):
        init_fn, pshapes, pspecs = build.make_init_fn(cfg, mesh)
        params = jax.jit(init_fn)(jax.random.key(0))
        cshapes = jax.eval_shape(lambda: transformer.init_cache(
            cfg, tp, B // dp, args.cache_len, 1))
        from ..parallel import sharding
        cspecs = sharding.cache_specs(cfg, cshapes, build.mesh_axes(mesh), 1)
        cache = jax.jit(build.shard_mapped(
            lambda: transformer.init_cache(cfg, tp, B // dp,
                                           args.cache_len, 1),
            mesh, (), cspecs))()
        decode = sstep.build_decode_step(cfg, build.axis_spec(mesh),
                                         args.comm, 1)
        bspec = {"tokens": P("data", None), "positions": P("data")}
        dstep = jax.jit(build.shard_mapped(
            decode, mesh, (pspecs, cspecs, bspec),
            (P("data", None, "model"), cspecs)))
        t0 = time.time()
        tok = prompt[:, :1]
        out_tokens = []
        for t in range(args.prompt_len + args.tokens - 1):
            batch = {"tokens": jnp.asarray(tok),
                     "positions": jnp.full((B,), t, jnp.int32)}
            logits, cache = dstep(params, cache, batch)
            nxt = np.asarray(jnp.argmax(logits[:, 0], -1)).astype(np.int32)
            if t + 1 < args.prompt_len:
                tok = prompt[:, t + 1:t + 2]
            else:
                tok = nxt[:, None]
                out_tokens.append(nxt)
        dt = time.time() - t0
        gen = np.stack(out_tokens, 1)
        print(f"[serve] (dense loop) generated {gen.shape} in {dt:.2f}s "
              f"({B * gen.shape[1] / dt:.1f} tok/s)")
        return gen


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--comm", default="shmem", choices=["shmem", "xla"])
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests (batch mode) / arrival batch")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128,
                    help="max sequence length (paged: page capacity per "
                         "sequence; dense fallback: cache length)")
    ap.add_argument("--continuous", action="store_true",
                    help="stream requests in at --rate per engine step "
                         "instead of submitting all up front")
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests in --continuous mode "
                         "(default: --batch)")
    ap.add_argument("--rate", type=int, default=1,
                    help="engine steps between arrivals (--continuous)")
    ap.add_argument("--slots", type=int, default=0,
                    help="engine batch slots (default: --batch, max 8)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size in tokens")
    ap.add_argument("--kv-heap-bytes", type=int, default=0,
                    help="cap the symmetric-heap KV region (0 = size for "
                         "all slots; smaller values exercise admission "
                         "backpressure)")
    ap.add_argument("--autotune", action="store_true",
                    help="consult the measured-performance tuning DB for "
                         "the per-step collectives (DESIGN §13)")
    ap.add_argument("--tuning-db", default="",
                    help="path of the persistent tuning database (JSON)")
    ap.add_argument("--profile-out", default="",
                    help="attach the runtime profiler and dump its "
                         "counters+timeline JSON here at exit")
    ap.add_argument("--trace-out", default="",
                    help="attach the distributed tracer (DESIGN §16) and "
                         "dump a Chrome trace-event JSON here at exit "
                         "(open in ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default="",
                    help="record serving metrics (TTFT/per-token "
                         "histograms, queue/KV gauges, wire bytes) and "
                         "dump the registry JSON here at exit")
    args = ap.parse_args(argv)

    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    from ..configs import get_config, smoke_config
    from ..models import transformer
    from .mesh import make_mesh

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(cfg, fsdp=False)
    if cfg.is_encoder:
        raise SystemExit("encoder-only arch has no decode loop")
    mesh = make_mesh(args.data, args.model)

    paged_ok = (transformer.paged_supported(cfg)
                and args.data == 1 and args.comm == "shmem")
    if not paged_ok:
        return _legacy_decode_loop(cfg, mesh, args)

    from ..serve.engine import ServeEngine
    profiler = None
    if args.trace_out:
        # one object serves both sinks: Tracer IS-A Profiler, so
        # --profile-out (counters+timeline) and --trace-out (Chrome
        # trace) can share it
        from ..core.trace import LEVEL_FULL, Tracer
        profiler = Tracer(level=LEVEL_FULL)
    elif args.profile_out:
        from ..core.profile import Profiler
        profiler = Profiler(level=2)
    metrics = None
    if args.metrics_out:
        from ..serve.metrics import ServeMetrics
        metrics = ServeMetrics()
        if profiler is not None:
            metrics.attach(profiler)
    tuner = None
    if args.autotune or args.tuning_db:
        from ..core import tuner as tuner_mod
        tuner = tuner_mod.Tuner(path=args.tuning_db or None)

    n_req = args.requests or args.batch
    slots = args.slots or min(args.batch, 8)
    max_seq = max(args.cache_len, args.prompt_len + args.tokens)
    bucket = -(-args.prompt_len // args.page_size) * args.page_size
    eng = ServeEngine(
        cfg, mesh, max_slots=slots, page_size=args.page_size,
        max_seq=max_seq, prompt_bucket=min(bucket, max_seq),
        kv_heap_bytes=args.kv_heap_bytes or None, backend=args.comm,
        tuner=(tuner if args.autotune else None), profile=profiler,
        metrics=metrics)

    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab, size=(n_req, args.prompt_len),
                           dtype=np.int32)
    t0 = time.time()
    rids = []
    if args.continuous:
        nxt = 0
        while nxt < n_req or not eng.scheduler.idle():
            if nxt < n_req and eng.steps % max(args.rate, 1) == 0:
                rids.append(eng.submit(prompts[nxt], args.tokens))
                nxt += 1
            eng.step()
        eng.run()                      # drain stragglers
    else:
        rids = [eng.submit(p, args.tokens) for p in prompts]
        eng.run()
    dt = time.time() - t0
    gen = np.stack([eng.results[r] for r in rids])
    mode = "continuous" if args.continuous else "batch"
    print(f"[serve] ({mode}, paged) generated {gen.shape} in {dt:.2f}s "
          f"({gen.size / dt:.1f} tok/s, {eng.steps} engine steps, "
          f"page={args.page_size} slots={slots})")
    print(gen[:, :8])

    if tuner is not None and args.tuning_db:
        tuner.save(args.tuning_db)
        print(f"[serve] tuning DB ({len(tuner.db)} points) saved to "
              f"{args.tuning_db}")
    if profiler is not None and args.profile_out:
        profiler.dump(args.profile_out)
        print(f"[serve] profile dumped to {args.profile_out}")
    if args.trace_out:
        profiler.dump_chrome(args.trace_out)
        print(f"[serve] Chrome trace ({len(profiler._events)} events) "
              f"written to {args.trace_out} — open in ui.perfetto.dev")
    if metrics is not None:
        metrics.dump(args.metrics_out)
        h = metrics.ttft_s
        print(f"[serve] metrics written to {args.metrics_out} "
              f"(ttft p50={h.percentile(50) * 1e3:.1f}ms, per-token "
              f"p50={metrics.per_token_s.percentile(50) * 1e3:.2f}ms)")
    return gen


if __name__ == "__main__":
    main()
