import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  * jax.jit(step).lower(**ShapeDtypeStructs).compile() must succeed on the
    16x16 production mesh AND the 2x16x16 multi-pod mesh;
  * memory_analysis() proves the working set fits per chip;
  * cost_analysis() + collective-bytes parsing feed the roofline.

Usage:
  python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
  python -m repro.launch.dryrun --all [--multipod] [--comm shmem|xla]
Results land in experiments/dryrun/<arch>__<shape>__<mesh>__<comm>.json
"""
import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .hlo import collective_bytes


# v5e-class hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # B/s
ICI_BW = 50e9                # B/s per link (sum both directions ~2x)


def roofline_terms(cost: dict, coll: dict, n_chips: int) -> dict:
    flops = cost.get("flops", 0.0)
    bytes_hbm = cost.get("bytes accessed", 0.0)
    coll_bytes = sum(coll["bytes"].values())
    return {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_hbm / HBM_BW,
        "collective_s": coll_bytes / ICI_BW,
        "hlo_flops": flops,
        "hlo_bytes": bytes_hbm,
        "collective_bytes": coll_bytes,
    }


def run_cell(arch: str, shape: str, multipod: bool, comm: str,
             outdir: pathlib.Path, verbose: bool = True) -> dict:
    from ..configs import get_config
    from ..models.config import input_specs, shape_applicable, SHAPES
    from . import build
    from .mesh import make_production_mesh

    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, shape)
    cell = f"{arch}__{shape}__{'2x16x16' if multipod else '16x16'}__{comm}"
    if not ok:
        res = {"cell": cell, "status": "skipped", "reason": why}
        _write(outdir, cell, res)
        if verbose:
            print(f"[dryrun] {cell}: SKIPPED ({why})")
        return res

    mesh = make_production_mesh(multi_pod=multipod)
    n_chips = int(np.prod(mesh.devices.shape))
    kind = SHAPES[shape]["kind"]
    t0 = time.time()
    with jax.set_mesh(mesh):
        specs_in = input_specs(cfg, shape)
        if kind == "train":
            wrap, (pshapes, pspecs), (oshapes, ospecs), _ = \
                build.make_train_step(cfg, mesh, comm)
            step = wrap(specs_in)
            gp = build.global_shape(pshapes, pspecs, mesh)
            go = build.global_shape(oshapes, ospecs, mesh)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(gp, go, specs_in)
        elif kind == "prefill":
            wp, wd, _, (pshapes, pspecs), _ = build.make_serve_steps(
                cfg, mesh, shape, comm)
            step = wp(specs_in)
            gp = build.global_shape(pshapes, pspecs, mesh)
            lowered = jax.jit(step).lower(gp, specs_in)
        else:  # decode
            wp, wd, (cshapes, cspecs), (pshapes, pspecs), seq_shards = \
                build.make_serve_steps(cfg, mesh, shape, comm)
            step = wd(specs_in)
            gp = build.global_shape(pshapes, pspecs, mesh)
            gc = build.global_shape(cshapes, cspecs, mesh)
            lowered = jax.jit(step, donate_argnums=(1,)).lower(gp, gc,
                                                               specs_in)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    terms = roofline_terms(cost, coll, n_chips)
    res = {
        "cell": cell, "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "roofline": terms,
        "collectives": coll,
    }
    _write(outdir, cell, res)
    if verbose:
        print(f"[dryrun] {cell}: OK  compile={t_compile:.0f}s  "
              f"FLOPs={terms['hlo_flops']:.3e}  "
              f"collB={terms['collective_bytes']:.3e}  "
              f"peak={res['memory']['peak_bytes']}")
        print(f"  memory_analysis: {mem}")
    return res


def _write(outdir: pathlib.Path, cell: str, res: dict):
    outdir.mkdir(parents=True, exist_ok=True)
    (outdir / f"{cell}.json").write_text(json.dumps(res, indent=2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--comm", default="shmem", choices=["shmem", "xla"])
    ap.add_argument("--outdir", default="experiments/dryrun")
    args = ap.parse_args()
    outdir = pathlib.Path(args.outdir)

    from ..configs import ARCHS
    from ..models.config import SHAPES
    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for a, s in cells:
        try:
            run_cell(a, s, args.multipod, args.comm, outdir)
        except Exception as e:  # noqa
            print(f"[dryrun] {a}__{s}: FAILED {type(e).__name__}: {e}")
            failures.append((a, s, str(e)))
    if failures:
        print(f"{len(failures)} cells failed"); sys.exit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
