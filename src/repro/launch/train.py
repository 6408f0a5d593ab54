"""Training launcher: real steps on whatever mesh fits this host, with
checkpoint/restart, straggler hooks, and elastic resume.

  python -m repro.launch.train --arch qwen2-0.5b --steps 50 --smoke \
         --data 1 --model 1 --ckpt-dir /tmp/ckpt --resume auto
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--pod", type=int, default=0)
    ap.add_argument("--comm", default="shmem", choices=["shmem", "xla"])
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="none", choices=["none", "auto"])
    ap.add_argument("--step-deadline", type=float, default=600.0,
                    help="per-step straggler deadline (seconds): a step "
                         "exceeding it is recorded as a straggler event "
                         "— the detection edge of the elastic restart "
                         "protocol (DESIGN §17)")
    ap.add_argument("--ckpt-async", default="on", choices=["on", "off"],
                    help="off: periodic saves block the train loop "
                         "(sync); on: saves snapshot to host and "
                         "serialize on a background thread (DESIGN §17)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--allreduce-algo", default="paper",
                    choices=["paper", "auto"],
                    help="paper: the paper's PE-count switch; auto: adds "
                         "the >=1MiB ring switch (EXPERIMENTS §Perf P2)")
    ap.add_argument("--grad-rs", default="off",
                    choices=["off", "on", "auto"],
                    help="bucketed ZeRO-style reduce-scatter+allgather "
                         "gradient sync; auto switches on above "
                         "GRAD_RS_AUTO_BYTES of synced grads (DESIGN §10)")
    ap.add_argument("--pipeline-chunks", default=None,
                    help="chunked double-buffered collective execution: "
                         "an int, or 'auto' for the cost-model pick "
                         "(DESIGN §10)")
    ap.add_argument("--embedding", default="off",
                    choices=["off", "auto", "snake"],
                    help="mesh-embedded ring collectives over the data "
                         "mesh (DESIGN §12): 'snake' runs rings in snake "
                         "coordinates, 'auto' prices embeddings against "
                         "the logical ring and runs the winner")
    ap.add_argument("--topo", default=None,
                    help="physical layout of the DATA axis as RxC "
                         "(non-torus 2D mesh, e.g. 4x4); gives the cost "
                         "model (--allreduce-algo auto, --embedding) real "
                         "hop/contention costs. Without it, --embedding "
                         "falls back to a near-square guess")
    ap.add_argument("--autotune", action="store_true",
                    help="measured-performance selection (DESIGN §13): "
                         "calibrate the data-axis mesh with a small SIM "
                         "sweep when the tuning DB has no entries for it, "
                         "then let every 'auto' selection consult the "
                         "measured-best variant before the analytic model")
    ap.add_argument("--tuning-db", default="",
                    help="path of the persistent tuning database (JSON); "
                         "loaded when it exists, saved after the run — a "
                         "training run warms it, later runs inherit the "
                         "measured-best picks")
    ap.add_argument("--profile-out", default="",
                    help="attach the pcontrol-style runtime profiler and "
                         "dump its JSON (counters + per-op/step timeline) "
                         "to this path at exit (DESIGN §13)")
    ap.add_argument("--trace-out", default="",
                    help="attach the distributed tracer (DESIGN §16) and "
                         "dump a Chrome trace-event JSON here at exit "
                         "(open in ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default="",
                    help="record per-step wall-time histogram + loss "
                         "gauge and dump the registry JSON here at exit")
    ap.add_argument("--remat", default=None,
                    choices=[None, "none", "full", "selective"],
                    help="override the config remat policy (§Perf P5)")
    ap.add_argument("--shard-strategy", default=None,
                    choices=[None, "tp", "dp_only"],
                    help="dp_only replicates params and uses the model "
                         "axis as extra DP (§Perf P6)")
    args = ap.parse_args(argv)

    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    from ..configs import get_config, smoke_config
    from ..ckpt import manager as ckpt
    from ..data.pipeline import SyntheticLM
    from ..train import optimizer as opt
    from . import build
    from .mesh import make_mesh

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    over = {}
    if args.remat:
        over["remat"] = args.remat
    if args.shard_strategy:
        over["shard_strategy"] = args.shard_strategy
    if over:
        cfg = dataclasses.replace(cfg, **over)
    mesh = make_mesh(args.data, args.model, args.pod or None)
    pipe = SyntheticLM(
        cfg.vocab, args.seq_len, args.batch,
        frames_dim=cfg.d_model if cfg.frontend == "audio" else None,
        frontend_tokens=(cfg.n_frontend_tokens
                         if cfg.frontend == "vision" else 0))

    with jax.set_mesh(mesh):
        grad_rs = {"off": False, "on": True, "auto": "auto"}[args.grad_rs]
        chunks = args.pipeline_chunks
        if chunks is not None and chunks != "auto":
            chunks = int(chunks)
        embedding = None if args.embedding == "off" else args.embedding
        topo = None
        if args.topo:
            # the operator states the data axis's physical layout — use it
            # for ALL topology-aware selection (hier, embeddings, pricing)
            from ..core.topology import MeshTopology
            shape = tuple(int(p) for p in args.topo.lower().split("x"))
            if int(np.prod(shape)) != args.data:
                raise SystemExit(f"--topo {args.topo} covers "
                                 f"{int(np.prod(shape))} PEs but the data "
                                 f"axis has {args.data}")
            topo = MeshTopology(shape, torus=(False,) * len(shape))
        elif embedding is not None and not args.pod:
            # mesh-embedded rings need a physical layout to embed into:
            # fall back to a near-square non-torus guess for the DATA
            # axis (the Epiphany-style NoC the cost model prices).  With
            # a pod axis the Comm topo would also price pod-axis
            # collectives against this data-axis layout — skip rather
            # than feed the selector a mesh that describes another axis.
            from ..core.topology import MeshTopology
            d, r = args.data, int(args.data ** 0.5)
            while r > 1 and d % r:
                r -= 1
            shape = (r, d // r) if r > 1 else (d,)
            topo = MeshTopology(shape, torus=(False,) * len(shape))
            print(f"[train] --embedding without --topo: assuming data-axis "
                  f"layout {'x'.join(map(str, shape))} (pass --topo to "
                  f"state the real one)")
        elif embedding is not None:
            print("[train] --embedding ignored: with --pod, pass --topo "
                  "to state the data-axis layout explicitly")
            embedding = None
        profiler = None
        if args.trace_out:
            from ..core.trace import LEVEL_FULL, Tracer
            profiler = Tracer(level=LEVEL_FULL)
        elif args.profile_out:
            from ..core.profile import Profiler
            profiler = Profiler(level=2)
        metrics = None
        if args.metrics_out:
            from ..serve.metrics import MetricsRegistry
            metrics = MetricsRegistry()
        tuner = None
        if args.autotune or args.tuning_db:
            from ..core import sim_ctx
            from ..core import tuner as tuner_mod
            tuner = tuner_mod.Tuner(path=args.tuning_db or None)
            if args.autotune and args.data > 1:
                # warm the DB for the data-axis mesh when it holds no
                # measurements for this fingerprint yet: a small SIM
                # sweep on this host — the SPMD step then inherits the
                # measured-best picks by topology fingerprint (§13)
                fp = tuner_mod.fingerprint(topo, args.data)
                if not any(k.startswith(fp + "|")
                           for k in tuner.db.entries):
                    print(f"[train] autotune: calibrating {fp} "
                          "(small SIM sweep)")
                    summary = tuner.tune(
                        sim_ctx(args.data, topo),
                        {"collectives": ("allreduce",),
                         "sizes": (4096, 65536, 1 << 20),
                         "chunks": (1, 4), "iters": 3, "warmup": 1})
                    print(f"[train] autotune: measured "
                          f"{summary['variants']} variants; best "
                          f"{summary['best']}")
        init_fn, pshapes, pspecs = build.make_init_fn(cfg, mesh)
        wrap, _, (oshapes, ospecs), ocfg = build.make_train_step(
            cfg, mesh, args.comm, allreduce_algo=args.allreduce_algo,
            grad_rs=grad_rs, pipeline_chunks=chunks,
            topo=topo, embedding=embedding,
            autotune=tuner if args.autotune else None, profile=profiler)
        ocfg = dataclasses.replace(ocfg, lr=args.lr)

        batch0 = pipe.batch(0)
        step_fn = jax.jit(wrap(batch0), donate_argnums=(0, 1))

        params = jax.jit(init_fn)(jax.random.key(0))
        opt_state = jax.jit(build.shard_mapped(
            lambda p: opt.init_state(p, ocfg), mesh, (pspecs,), ospecs)
        )(params)

        start = 0
        ft = None
        if args.ckpt_dir:
            ft = ckpt.FaultToleranceManager(
                args.ckpt_dir, save_every=args.ckpt_every,
                step_deadline_s=args.step_deadline,
                async_save=args.ckpt_async == "on")
            if args.resume == "auto" and ft.resume_step() is not None:
                start, restored = ckpt.restore(
                    args.ckpt_dir,
                    {"params": params, "opt": opt_state})
                params, opt_state = restored["params"], restored["opt"]
                print(f"[train] resumed from step {start}")

        import contextlib
        losses = []
        for step in range(start, args.steps):
            t0 = time.time()
            batch = jax.tree.map(jnp.asarray, pipe.batch(step))
            with (profiler.op("train_step", n_pes=mesh.devices.size)
                  if profiler is not None else contextlib.nullcontext()):
                loss, params, opt_state = step_fn(params, opt_state, batch)
                loss = float(loss)        # sync: the sample times the step
            losses.append(loss)
            if metrics is not None:
                metrics.histogram("train.step_s",
                                  "full train step wall time").observe(
                    time.time() - t0)
                metrics.gauge("train.loss", "last step loss").set(loss)
            print(f"[train] step {step:5d} loss {loss:8.4f} "
                  f"({time.time() - t0:.2f}s)")
            if ft:
                ft.on_step(step, lambda: {"params": params,
                                          "opt": opt_state})
        if ft:
            ft.finalize(args.steps, lambda: {"params": params,
                                             "opt": opt_state})
            if ft.stragglers:
                print(f"[train] {len(ft.stragglers)} step(s) exceeded "
                      f"--step-deadline {args.step_deadline:g}s "
                      f"(worst {max(s['stall_s'] for s in ft.stragglers):.1f}s)")
            if metrics is not None:
                metrics.counter(
                    "train.stragglers",
                    "steps exceeding the --step-deadline").inc(
                    len(ft.stragglers))
        if tuner is not None and args.tuning_db:
            tuner.save(args.tuning_db)
            print(f"[train] tuning DB ({len(tuner.db)} points) saved to "
                  f"{args.tuning_db}")
        if profiler is not None and args.profile_out:
            profiler.dump(args.profile_out)
            print(f"[train] profile dumped to {args.profile_out}")
        if args.trace_out:
            profiler.dump_chrome(args.trace_out)
            print(f"[train] Chrome trace ({len(profiler._events)} events) "
                  f"written to {args.trace_out} — open in ui.perfetto.dev")
        if metrics is not None:
            metrics.counter("train.steps", "steps executed").inc(
                len(losses))
            metrics.dump(args.metrics_out)
            print(f"[train] metrics written to {args.metrics_out}")
        assert np.isfinite(losses).all(), "NaN/inf loss"
        if len(losses) >= 10:
            a, b = np.mean(losses[:3]), np.mean(losses[-3:])
            print(f"[train] loss {a:.4f} -> {b:.4f} "
                  f"({'improved' if b < a else 'no improvement'})")
        return losses


if __name__ == "__main__":
    main()
