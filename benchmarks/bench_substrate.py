"""Substrate A/B: the paper's ARL-vs-eLib comparison at framework level.

Compiles the same smoke train step on an 8-chip submesh under both
substrates and reports collective op counts/bytes from the HLO — the
system-level analogue of the paper's Fig. 3 eLib speedup panel.  Runs in
a subprocess pinned to the CPU, so the main process keeps one device and
the child never contends for a chip the parent holds.

  PYTHONPATH=src python -m benchmarks.bench_substrate
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.launch import build
    from repro.launch.mesh import make_mesh
    from repro.launch.hlo import collective_bytes

    out = {}
    for comm in ("shmem", "xla"):
        cfg = smoke_config("qwen2-0.5b")
        mesh = make_mesh(4, 2)
        with jax.set_mesh(mesh):
            batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                     "targets": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
            wrap, (ps, psp), (os_, osp), _ = build.make_train_step(
                cfg, mesh, comm)
            compiled = jax.jit(wrap(batch), donate_argnums=(0, 1)).lower(
                build.global_shape(ps, psp, mesh),
                build.global_shape(os_, osp, mesh), batch).compile()
        coll = collective_bytes(compiled.as_text())
        cost = compiled.cost_analysis()
        out[comm] = {"counts": coll["counts"], "bytes": coll["bytes"],
                     "flops": cost.get("flops", 0.0)}
    print("SUBSTRATE_JSON:" + json.dumps(out))
""")


def run() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    for line in r.stdout.splitlines():
        if line.startswith("SUBSTRATE_JSON:"):
            return json.loads(line[len("SUBSTRATE_JSON:"):])
    raise RuntimeError(r.stdout[-2000:] + r.stderr[-2000:])


def main():
    out = run()
    print("substrate,op,count,bytes")
    for comm, d in out.items():
        for k in d["counts"]:
            if d["counts"][k]:
                print(f"{comm},{k},{d['counts'][k]},{d['bytes'][k]}")
    s, x = out["shmem"], out["xla"]
    tot_s = sum(s["bytes"].values())
    tot_x = sum(x["bytes"].values())
    print(f"# shmem moves {tot_s/1e6:.1f} MB in "
          f"{sum(s['counts'].values())} ops (ppermute stages); "
          f"xla moves {tot_x/1e6:.1f} MB in "
          f"{sum(x['counts'].values())} fused collectives — the paper's "
          f"explicit-algorithm vs vendor-primitive trade at pod scale")


if __name__ == "__main__":
    main()
