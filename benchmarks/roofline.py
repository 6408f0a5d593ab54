"""Roofline placement of PROFILED train/serve steps on the current
stack (DESIGN.md §18).

The seed-era version extrapolated from ``experiments/dryrun`` artifacts
that no longer exist and priced everything with hardcoded v5e constants.
This one needs NO pre-existing artifacts: each cell compiles and runs a
real step (smoke scale, CPU-runnable) under the tracer and derives all
three roofline terms from the stack itself —

  compute_s = HLO FLOPs of the step ACTUALLY compiled
              (``jit(...).lower().compile().cost_analysis()``) / peak
  memory_s  = HLO bytes accessed / local-memory bandwidth
  noc_s     = the step's collective payload scheduled by
              ``collectives.choose_schedule`` on the target machine's
              topology and priced by the CALIBRATED LinkModel (the
              tuning DB's refit for that topology when
              ``bench-reports/tuning_db.json`` has one, else the
              machine's default link constants)

and places the step against them: bottleneck = argmax term, MFU =
model FLOPs / (peak * modeled step time).  The measured wall time of
the smoke step rides along as the pinned regression row.  The per-cell
summary is embedded into the trace document's ``repro.roofline``
section (``Tracer.sections``) so ``tracereport`` prints it.

  PYTHONPATH=src python -m benchmarks.roofline
  PYTHONPATH=src python -m benchmarks.roofline --machine v5e-pod
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, "src")

from repro.core import abmodel, collectives as coll          # noqa: E402
from repro.core.topology import epiphany3, v5e_pod           # noqa: E402

ROWS: list[tuple] = []


def row(name, us, derived):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.2f},{derived}")


@dataclasses.dataclass(frozen=True)
class Machine:
    """The roofline ceilings of one target machine."""
    name: str
    peak_flops: float            # FLOP/s, all PEs
    mem_bw_Bps: float            # aggregate local-memory bandwidth
    link: abmodel.LinkModel      # default NoC constants
    topo: object
    n_pes: int


def machines() -> dict[str, Machine]:
    return {
        # Epiphany-III: 16 PEs x 1.2 GFLOPS (FMA @ 600 MHz); 8 B/clk
        # local-memory port per PE
        "epiphany3": Machine("epiphany3", 16 * 1.2e9, 16 * 4.8e9,
                             abmodel.EPIPHANY_NOC, epiphany3(), 16),
        "v5e-pod": Machine("v5e-pod", 197e12, 819e9, abmodel.ICI_V5E,
                           v5e_pod(), 256),
    }


def calibrated_link(machine: Machine) -> tuple[abmodel.LinkModel, str]:
    """The tuning DB's measured refit for the target topology when one
    exists (DESIGN.md §13), else the machine's default constants."""
    db_path = pathlib.Path(os.environ.get("BENCH_OUT_DIR",
                                          "bench-reports"))
    db_path = db_path / "tuning_db.json"
    if db_path.exists():
        from repro.core import tuner as tun
        db = tun.TuningDB.load(db_path)
        lm = db.link_model(tun.fingerprint(machine.topo, machine.n_pes))
        if lm is not None:
            return lm, "calibrated"
    return machine.link, "default"


def noc_term(nbytes: float, machine: Machine,
             link: abmodel.LinkModel) -> tuple[float, str]:
    """Modeled time of the cell's collective payload on the target
    machine — the same choose_schedule + pipelined pricing the
    executors run."""
    algo, chunks = coll.choose_schedule(machine.n_pes, nbytes,
                                        machine.topo, link)
    stages = coll.allreduce_stages(machine.n_pes, nbytes, machine.topo,
                                   algo if algo != "ring_emb" else None)
    if chunks > 1:
        t = abmodel.modeled_pipelined_time(stages, chunks, link)
    else:
        t = abmodel.modeled_collective_time(stages, link)
    return t, f"{algo}/c{chunks}"


def _cost_analysis(compiled) -> dict:
    return dict(compiled.cost_analysis() or {})


def _timed_us(fn, *args, iters: int = 3) -> float:
    import jax
    jax.block_until_ready(fn(*args))          # warm (compile cached)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


# ---------------------------------------------------------------------------
# cells: real profiled steps at smoke scale
# ---------------------------------------------------------------------------

def cell_train(tracer=None, arch: str = "qwen2-0.5b") -> dict:
    """One full train step (fwd+bwd+AdamW through launch.build), its
    HLO counts, and its data-parallel gradient-sync payload (the full
    parameter set — what a data mesh allreduces every step)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.launch import build
    from repro.launch.mesh import make_mesh
    from repro.train import optimizer as opt

    cfg = smoke_config(arch)
    mesh = make_mesh(1, 1)
    B, L = 2, 64
    batch = {"tokens": jnp.ones((B, L), jnp.int32),
             "targets": jnp.ones((B, L), jnp.int32)}
    with jax.set_mesh(mesh):
        init_fn, _, specs = build.make_init_fn(cfg, mesh)
        params = jax.jit(init_fn)(jax.random.key(0))
        wrap, _, (_, ospecs), ocfg = build.make_train_step(
            cfg, mesh, "shmem", profile=tracer)
        ostate = jax.jit(build.shard_mapped(
            lambda p: opt.init_state(p, ocfg), mesh, (specs,), ospecs)
        )(params)
        step = jax.jit(wrap(batch))
        compiled = step.lower(params, ostate, batch).compile()
        if tracer is not None:
            with tracer.span("roofline.train_step", n_pes=1):
                wall_us = _timed_us(step, params, ostate, batch)
        else:
            wall_us = _timed_us(step, params, ostate, batch)
    cost = _cost_analysis(compiled)
    n_params = cfg.param_count()
    return {
        "cell": f"train_{arch}",
        "wall_us": wall_us,
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": 4.0 * n_params,       # f32 grad allreduce payload
        "model_flops": 6.0 * n_params * B * L,
    }


def cell_decode(tracer=None, arch: str = "qwen2-0.5b") -> dict:
    """One serving decode step (KV-cache token step through serve.step),
    its HLO counts, and the tensor-parallel payload a 16-PE chip would
    allreduce per step (attention + MLP block outputs per layer)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.configs import smoke_config
    from repro.launch import build
    from repro.launch.mesh import make_mesh
    from repro.models import transformer
    from repro.serve import step as sstep

    cfg = smoke_config(arch)
    mesh = make_mesh(1, 1)
    B, S = 2, 64
    with jax.set_mesh(mesh):
        init_fn, _, specs = build.make_init_fn(cfg, mesh)
        params = jax.jit(init_fn)(jax.random.key(0))
        cshapes = jax.eval_shape(
            lambda: transformer.init_cache(cfg, 1, B, S, 1))
        cspecs = jax.tree.map(lambda _: P(), cshapes)
        cache = jax.jit(build.shard_mapped(
            lambda: transformer.init_cache(cfg, 1, B, S, 1),
            mesh, (), cspecs))()
        decode = sstep.build_decode_step(cfg, build.axis_spec(mesh),
                                         "shmem", 1, profile=tracer)
        djit = jax.jit(build.shard_mapped(
            decode, mesh,
            (specs, cspecs, {"tokens": P(), "positions": P()}),
            (P(), cspecs)))
        dbatch = {"tokens": jnp.ones((B, 1), jnp.int32),
                  "positions": jnp.zeros((B,), jnp.int32)}
        compiled = djit.lower(params, cache, dbatch).compile()
        if tracer is not None:
            with tracer.span("roofline.decode_step", n_pes=1):
                wall_us = _timed_us(djit, params, cache, dbatch)
        else:
            wall_us = _timed_us(djit, params, cache, dbatch)
    cost = _cost_analysis(compiled)
    n_params = cfg.param_count()
    return {
        "cell": f"decode_{arch}",
        "wall_us": wall_us,
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        # two block-output allreduces per layer, f32 activations
        "coll_bytes": 2.0 * cfg.n_layers * B * cfg.d_model * 4.0,
        "model_flops": 2.0 * n_params * B,
    }


CELLS = [("train", cell_train), ("decode", cell_decode)]


def place(cell: dict, machine: Machine,
          link: abmodel.LinkModel, link_src: str) -> dict:
    """Put one profiled cell on the machine's rooflines."""
    compute_s = cell["hlo_flops"] / machine.peak_flops
    memory_s = cell["hlo_bytes"] / machine.mem_bw_Bps
    noc_s, pick = noc_term(cell["coll_bytes"], machine, link)
    terms = {"compute": compute_s, "memory": memory_s, "noc": noc_s}
    bottleneck = max(terms, key=terms.get)
    step_s = max(terms.values())
    mfu = cell["model_flops"] / machine.peak_flops / max(step_s, 1e-12)
    return dict(cell, machine=machine.name, link=link_src,
                compute_us=compute_s * 1e6, memory_us=memory_s * 1e6,
                noc_us=noc_s * 1e6, noc_pick=pick,
                bottleneck=bottleneck, step_us=step_s * 1e6, mfu=mfu)


def run(machine_name: str = "epiphany3") -> dict:
    from repro.core.trace import Tracer
    machine = machines()[machine_name]
    link, link_src = calibrated_link(machine)
    tracer = Tracer(level=3)
    cells = []
    for key, fn in CELLS:
        placed = place(fn(tracer), machine, link, link_src)
        cells.append(placed)
        row(f"roofline_{key}_wall_us", placed["wall_us"],
            f"pred={placed['step_us']:.2f}us pick={placed['bottleneck']} "
            f"mfu={min(placed['mfu'], 9.999):.3f} noc={placed['noc_pick']} "
            f"link={link_src}")
        row(f"roofline_{key}_noc_us", placed["noc_us"],
            f"payload={placed['coll_bytes']:.0f}B "
            f"compute={placed['compute_us']:.2f}us "
            f"memory={placed['memory_us']:.2f}us")
    summary = {
        "machine": machine.name,
        "link": link_src,
        "peaks": {"flops": machine.peak_flops,
                  "mem_Bps": machine.mem_bw_Bps,
                  "link_GBs": link.bw_Bps / 1e9},
        "cells": cells,
    }
    tracer.sections["roofline"] = summary
    out_dir = os.environ.get("BENCH_OUT_DIR", "")
    if out_dir:
        out = pathlib.Path(out_dir)
        out.mkdir(parents=True, exist_ok=True)
        (out / "roofline.json").write_text(json.dumps(summary, indent=1))
        tracer.dump_chrome(out / "roofline_trace.json")
        print(f"[roofline] wrote {out}/roofline.json + roofline_trace.json")
    return summary


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--machine", default="epiphany3",
                    choices=sorted(machines()),
                    help="target machine whose rooflines the profiled "
                         "steps are placed on")
    # benchmarks.run calls main() with no argv: parse an empty list so
    # the harness's own flags are never consumed here
    args = ap.parse_args(argv if argv is not None else [])
    summary = run(args.machine)
    pk = summary["peaks"]
    print(f"# machine={summary['machine']} link={summary['link']} "
          f"peak={pk['flops'] / 1e9:.1f}GFLOP/s mem={pk['mem_Bps'] / 1e9:.1f}GB/s "
          f"noc={pk['link_GBs']:.2f}GB/s")


if __name__ == "__main__":
    main(sys.argv[1:])
