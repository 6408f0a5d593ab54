"""Witness for the tensor-parallel gradient fault (PERF.md, Open
questions): the program's step-1 gradient of every leaf, read from the
first AdamW moment, on (data, model) meshes of 4 CPU devices, as a ratio
to the plain reference's gradient norm on the same weights and batch.
A sound step reads 1.000 on every leaf.

  JAX_PLATFORMS=cpu python3 chipbench/tools/tp_grad_witness.py --seeds 1,2,3
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness  # noqa: E402

# danube3's head layout (GQA group 4) at widths a CPU holds
SIZE = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
            num_attention_heads=8, num_key_value_heads=2, vocab_size=1024,
            eos_token_id=2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--meshes", default="1x1,4x1,2x2,1x2")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.launch import build
    from repro.launch.mesh import make_mesh
    from repro.train import optimizer as opt

    from chipbench import sut, weights
    from chipbench.reference import decoder

    conf = harness.load_json(harness.BENCH_DIR / "configs" /
                             "qwen2-0.5b.json")
    conf.update(SIZE, qkv_bias=False, tie_word_embeddings=False)
    for seed in [int(s) for s in args.seeds.split(",")]:
        rows = np.random.default_rng(seed).integers(
            0, SIZE["vocab_size"], (8, 33), dtype=np.int32)
        batch = {"tokens": rows[:, :-1], "targets": rows[:, 1:]}
        with jax.default_matmul_precision("highest"):
            w = weights.make(conf, weights.seed_key(seed), norm_std=0.0,
                             bias_std=0.0)
            g = jax.grad(decoder.loss, argnums=1)(
                conf, w, batch["tokens"], batch["targets"])
        ref = {k: float(jnp.linalg.norm(v)) for k, v in g.items()}
        for spec in args.meshes.split(","):
            dp, tp = (int(x) for x in spec.split("x"))
            cfg = sut.model_config(conf, remat="none")
            cfg = dataclasses.replace(cfg, dtype=jnp.float32)
            mesh = make_mesh(dp, tp)
            with jax.set_mesh(mesh), jax.default_matmul_precision("highest"):
                wrap, _, (_, ospecs), ocfg = build.make_train_step(
                    cfg, mesh, "shmem")
                params, pspecs = sut.make_params(conf, cfg, mesh, seed,
                                                 norm_std=0.0, bias_std=0.0)
                ost = jax.jit(build.shard_mapped(
                    lambda p: opt.init_state(p, ocfg), mesh, (pspecs,),
                    ospecs))(params)
                b = jax.tree.map(lambda x: jax.device_put(
                    x, NamedSharding(mesh, P("data", None))), batch)
                _, _, ost = jax.jit(wrap(b))(params, ost, b)
                m = jax.tree.map(lambda mv: mv["m"], ost["mv"],
                                 is_leaf=lambda x: isinstance(x, dict)
                                 and "m" in x)
            got = {k: float(jnp.linalg.norm(v)) / (1 - ocfg.b1)
                   for k, v in sut.reference_names(m).items()}
            print(json.dumps({"seed": seed, "mesh": spec, "ratio": {
                k: round(got[k] / ref[k], 4) for k in sorted(ref)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
