"""Smoke run of the main path on TPU: qwen2-0.5b at its published widths.

  python chip_smoke.py             # one chip: train steps + paged serving
  python chip_smoke.py --chips 4   # 2x2 mesh: the shmem train step against
                                   # the xla one, and Comm collectives
                                   # against jax.lax

One chip: a few train steps through ``launch.build.make_train_step`` (the
path of ``python -m repro.launch.train --arch qwen2-0.5b``) at seq-len
1024 and batch 8, then seeded requests served to completion by the paged
``ServeEngine`` (the path of ``python -m repro.launch.serve``), with one
request's logits checked against ``transformer.forward``.  Weights,
batches and prompts are random, made from seed 0.

Every line before the last names the device.  The last line of stdout is
one JSON object, ``{"ok": true, "device": {...}}``, printed only when every
phase passed.  Without a TPU the script exits non-zero and prints no
result.  It runs in one process and starts no other.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-0.5b"

# training: the config's own microbatches=4 split each batch of 8
TRAIN = dict(seq_len=1024, batch=8, steps=4)
# serving: the launcher's engine settings
SERVE = dict(n_req=8, prompt_len=128, new_tokens=32, slots=8, page_size=16,
             max_seq=1024, prompt_bucket=128)

# Engine logits against transformer.forward: both compute in bf16 with
# f32 logits, so they agree to a few bf16 roundings carried through the
# depth (about 2% at 24 layers on the CPU at small widths).  The bound is
# relative to the largest reference logit; a wrong position, page or mask
# is off by O(1) of it.
LOGIT_RTOL = 1e-1
# shmem and xla train steps on the same seed and batches.  Step 0 runs
# on the same params, so its losses differ only by reduction order.
# Later steps follow AdamW updates, which move every weight by about lr
# however small its gradient: a near-zero gradient element whose sign
# differs between the substrates moves the other way, so the bound widens.
LOSS_RTOL_FIRST, LOSS_RTOL = 1e-4, 1e-2
# Comm collectives against jax.lax on f32 payloads: four-term sums in
# another order
COLL_RTOL, COLL_ATOL = 1e-5, 1e-5
COLL_SIZES = (4 << 10, 1 << 20, 16 << 20)


class SmokeFailure(RuntimeError):
    """A phase ran but its output was wrong."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def make_log(dev: dict):
    tag = f"[{dev['platform']} {dev['kind']} x{dev['count']}]"

    def log(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)
    return log


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def train_phase(cfg, mesh, comm: str = "shmem", *, seq_len: int, batch: int,
                steps: int, seed: int = 0, log=print) -> dict:
    """``steps`` train steps on ``mesh`` through the launcher's builders;
    the first step is warm-up.  Returns losses, compile and step times."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import SyntheticLM
    from repro.launch import build
    from repro.train import optimizer as opt

    pipe = SyntheticLM(cfg.vocab, seq_len, batch, seed=seed)
    with jax.set_mesh(mesh):
        init_fn, _, pspecs = build.make_init_fn(cfg, mesh)
        wrap, _, (_, ospecs), ocfg = build.make_train_step(cfg, mesh, comm)
        params = jax.jit(init_fn)(jax.random.key(seed))
        opt_state = jax.jit(build.shard_mapped(
            lambda p: opt.init_state(p, ocfg), mesh, (pspecs,), ospecs)
        )(params)
        batch0 = jax.tree.map(jnp.asarray, pipe.batch(0))
        t0 = time.perf_counter()
        step_fn = jax.jit(wrap(batch0), donate_argnums=(0, 1)).lower(
            params, opt_state, batch0).compile()
        compile_s = time.perf_counter() - t0
        mem = step_fn.memory_analysis()
        if mem is not None:
            log(f"train[{comm}] compiled in {compile_s:.1f}s: "
                f"args {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
                f"temps {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
                f"outputs {mem.output_size_in_bytes / 2**30:.3f} GiB")
        losses, times = [], []
        for step in range(steps):
            b = jax.tree.map(jnp.asarray, pipe.batch(step))
            t = time.perf_counter()
            loss, params, opt_state = step_fn(params, opt_state, b)
            jax.block_until_ready((loss, params, opt_state))
            times.append(time.perf_counter() - t)
            losses.append(float(loss))
            log(f"train[{comm}] step {step} loss {losses[-1]:.6f} "
                f"({times[-1]:.4f}s)")
    check(np.isfinite(losses).all(), f"train[{comm}]: non-finite loss "
          f"{losses}")
    steady = times[1:] or times
    return {"losses": losses, "compile_s": compile_s,
            "step_s": float(np.median(steady)),
            "tokens_per_s": batch * seq_len / float(np.median(steady))}


def reference_logits(cfg, mesh, params, tokens):
    """Logits of ``transformer.forward`` over ``tokens`` (L,): the plain
    full-sequence path, with no KV cache, pages or sampling."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch import build
    from repro.models import layers as L
    from repro.models import transformer
    from repro.parallel.comm import Comm

    _, _, pspecs = build.make_init_fn(cfg, mesh)

    def fwd(params, tokens):
        comm = Comm(build.axis_spec(mesh), "shmem")
        h, _ = transformer.forward(comm, cfg, params, tokens)
        return L.lm_logits(comm, cfg, params["embed"], h)

    with jax.set_mesh(mesh):
        out = jax.jit(build.shard_mapped(
            fwd, mesh, (pspecs, P()), P(None, None, "model")))(
            params, jnp.asarray(tokens)[None])
    return np.asarray(out[0], np.float32)


def serve_phase(cfg, mesh, *, n_req: int, prompt_len: int, new_tokens: int,
                slots: int, page_size: int, max_seq: int, prompt_bucket: int,
                seed: int = 0, log=print) -> dict:
    """Serve ``n_req`` seeded requests to completion on the paged engine,
    then one more with its logits captured and checked against
    ``transformer.forward`` on the same prompt and params."""
    from repro.serve.engine import ServeEngine

    eng = ServeEngine(cfg, mesh, max_slots=slots, page_size=page_size,
                      max_seq=max_seq, prompt_bucket=prompt_bucket,
                      init_key=seed)
    prompts = np.random.default_rng(seed).integers(
        1, cfg.vocab, size=(n_req, prompt_len), dtype=np.int32)

    t0 = time.perf_counter()             # compiles prefill and decode
    eng.submit(prompts[0], 2)
    eng.run()
    warm_s = time.perf_counter() - t0
    log(f"serve: warm-up request (compiles prefill + decode) "
        f"{warm_s:.1f}s")

    steps0 = eng.steps
    t0 = time.perf_counter()
    rids = [eng.submit(p, new_tokens) for p in prompts]
    eng.run()
    dt = time.perf_counter() - t0
    gen = np.stack([eng.results[r] for r in rids])
    check(gen.shape == (n_req, new_tokens),
          f"serve: generated {gen.shape}, expected {(n_req, new_tokens)}")
    check(((gen >= 0) & (gen < cfg.vocab)).all(),
          "serve: token id outside the vocab")
    tok_s = gen.size / dt
    log(f"serve: {n_req} requests x {new_tokens} tokens in {dt:.3f}s "
        f"({tok_s:.1f} tok/s, {eng.steps - steps0} engine steps)")

    eng.capture_logits = True
    rid = eng.submit(prompts[0], new_tokens)
    eng.run()
    toks = eng.results[rid]
    got = np.stack(eng.logits_trace[rid])                  # (T, V)
    # teacher-force the engine's own tokens: position prompt_len-1+k
    # yields the logits of generated token k
    seq = np.concatenate([prompts[0], toks[:-1]])
    ref = reference_logits(cfg, mesh, eng.params, seq)[prompt_len - 1:]
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    rel = err / scale
    log(f"serve: logits vs transformer.forward over {len(toks)} tokens: "
        f"max |diff| {err:.4g}, max |ref| {scale:.4g}, "
        f"relative {rel:.4g} (bound {LOGIT_RTOL})")
    check(rel <= LOGIT_RTOL, f"serve: logits off the reference by {rel:.4g} "
          f"of their scale (bound {LOGIT_RTOL})")
    check((toks == got.argmax(-1)).all(),
          "serve: greedy tokens differ from the argmax of their logits")
    # a token the reference scores within the tolerance of its best is
    # an admissible greedy pick in bf16
    ref_pick = ref[np.arange(len(toks)), toks]
    check((ref_pick >= ref.max(-1) - LOGIT_RTOL * scale).all(),
          "serve: a greedy token is not the reference's argmax")
    exact = int((toks == ref.argmax(-1)).sum())
    log(f"serve: greedy tokens equal to the reference argmax: "
        f"{exact}/{len(toks)}")
    return {"tokens_per_s": tok_s, "warm_s": warm_s, "logit_rel_err": rel,
            "argmax_exact": exact}


def comm_phase(mesh, sizes=COLL_SIZES, *, seed: int = 0, log=print) -> dict:
    """``Comm(backend="shmem")`` allreduce and allgather over the data
    axis of ``mesh``, against ``lax.psum`` and ``lax.all_gather``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch import build
    from repro.parallel.comm import AxisSpec, Comm

    n = mesh.shape["data"]
    comm = Comm(AxisSpec(), "shmem")

    def body(x):
        return (comm.allreduce(x, "data"), lax.psum(x, "data"),
                comm.allgather(x, "data"),
                lax.all_gather(x, "data", axis=0, tiled=True))

    out = {}
    with jax.set_mesh(mesh):
        fn = jax.jit(build.shard_mapped(body, mesh, (P("data", None),),
                                        (P("data", None),) * 4))
        for nbytes in sizes:
            per_pe = nbytes // 4
            x = jax.random.normal(jax.random.key(seed), (n, per_pe),
                                  jnp.float32)
            x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
            ar, ar_ref, ag, ag_ref = (np.asarray(a) for a in fn(x))
            ar_err = float(np.abs(ar - ar_ref).max())
            check(np.allclose(ar, ar_ref, rtol=COLL_RTOL, atol=COLL_ATOL),
                  f"comm: allreduce of {nbytes} B off psum by {ar_err:.3g}")
            check(np.array_equal(ag, ag_ref),
                  f"comm: allgather of {nbytes} B differs from all_gather")
            log(f"comm: {n} PEs, {nbytes} B per PE: allreduce max |diff| "
                f"vs psum {ar_err:.3g}, allgather equal to all_gather")
            out[nbytes] = ar_err
    return out


def one_chip(cfg, log) -> None:
    from repro.launch.mesh import make_mesh

    mesh = make_mesh(1, 1)
    tr = train_phase(cfg, mesh, log=log, **TRAIN)
    log(f"train: compile {tr['compile_s']:.1f}s, step {tr['step_s']:.4f}s "
        f"after warm-up ({tr['tokens_per_s']:.0f} tok/s), "
        f"peak HBM {peak_bytes()} B")
    serve_phase(cfg, mesh, log=log, **SERVE)
    log(f"serve: peak HBM {peak_bytes()} B")


def four_chip_phase(cfg, *, seq_len: int, batch: int, steps: int,
                    sizes=COLL_SIZES, seed: int = 0, log=print) -> None:
    """The train step on a 2x2 (data, model) mesh with the shmem and the
    xla substrate, then Comm collectives against jax.lax on 4 PEs."""
    import jax

    from repro.launch.mesh import make_mesh

    mesh = make_mesh(2, 2)
    ids = {d.id for d in mesh.devices.flat}
    check(len(ids) == 4 == len(jax.devices()),
          f"mesh holds devices {sorted(ids)}, expected 4 distinct")
    losses = {}
    for comm in ("shmem", "xla"):
        tr = train_phase(cfg, mesh, comm, seq_len=seq_len, batch=batch,
                         steps=steps, seed=seed, log=log)
        losses[comm] = np.asarray(tr["losses"])
        log(f"train[{comm}] 2x2: compile {tr['compile_s']:.1f}s, step "
            f"{tr['step_s']:.4f}s after warm-up")
    rel = np.abs(losses["shmem"] - losses["xla"]) / np.abs(losses["xla"])
    log(f"train 2x2: shmem vs xla loss relative diff per step "
        f"{[float(f'{r:.3g}') for r in rel]} (bounds {LOSS_RTOL_FIRST} "
        f"at step 0, {LOSS_RTOL} after)")
    check(rel[0] <= LOSS_RTOL_FIRST and (rel <= LOSS_RTOL).all(),
          f"train 2x2: shmem and xla losses disagree: {losses}")
    comm_phase(make_mesh(4, 1), sizes, seed=seed, log=log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 2x2 mesh path")
    args = ap.parse_args(argv)

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev['platform']!r} ({dev['kind']})", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX found {dev['count']}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    log = make_log(dev)
    cfg = get_config(ARCH)
    log(f"{ARCH}: {cfg.n_layers} layers, d={cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads of {cfg.hd}, "
        f"d_ff={cfg.d_ff}, vocab={cfg.vocab}, "
        f"{cfg.param_count() / 1e6:.1f}M params")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(cfg, log=log, **dict(TRAIN, steps=3))
    else:
        one_chip(cfg, log)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
