"""The system under test, as the drivers see it: the repository's
`ModelConfig` and its parameter tree and shardings, built from a
configuration file of `chipbench/configs/`.

This is the only benchmark module besides the drivers that imports the
program.  The reference imports neither.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from chipbench import weights

# the program's RMSNorm epsilon is a constant (models/layers.rms_norm)
PROGRAM_RMS_EPS = 1e-6

# program leaf path -> reference leaf name (chipbench.weights layout)
_NAMES = {
    "embed/table": "embed", "embed/head": "head", "final_norm": "final_norm",
    "layers/ln1": "layers/ln1", "layers/ln2": "layers/ln2",
    **{f"layers/attn/{k}": f"layers/{k}"
       for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
    **{f"layers/mlp/{k}": f"layers/{k}"
       for k in ("w_gate", "w_up", "w_down")},
}
# gains: the program stores g - 1 and normalises by (1 + stored)
_GAINS = ("final_norm", "layers/ln1", "layers/ln2")


def model_config(conf: dict, *, microbatches: int = 1,
                 remat: str = "full"):
    """The program's ModelConfig for a dense GQA configuration file."""
    from repro.models.config import ModelConfig

    if conf["rms_norm_eps"] != PROGRAM_RMS_EPS:
        raise ValueError(
            f"{conf['name']}: rms_norm_eps {conf['rms_norm_eps']} is not "
            f"the program's fixed {PROGRAM_RMS_EPS}")
    prec = conf["precision"]
    if (prec["params"], prec["compute"], prec["logits"]) != (
            "float32", "bfloat16", "float32"):
        raise ValueError(f"{conf['name']}: precision {prec} is not the "
                         "program's f32 params, bf16 compute, f32 logits")
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or d // h,
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        qkv_bias=bool(conf.get("qkv_bias")),
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        window=(conf.get("sliding_window")
                if conf.get("use_sliding_window") else None),
        dtype=jnp.bfloat16, param_dtype=jnp.float32,
        logit_dtype=jnp.float32, remat=remat, microbatches=microbatches)


def path_str(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in kp)


def to_program(flat: dict, pshapes) -> dict:
    """Benchmark weights (chipbench.weights layout) -> the program's
    parameter tree with the structure of `pshapes`."""
    def one(kp, _):
        name = path_str(kp)
        x = flat[_NAMES[name]]
        return x - 1.0 if name in _GAINS else x
    return jax.tree_util.tree_map_with_path(one, pshapes)


def reference_names(tree) -> dict:
    """{reference leaf name: leaf} of a program-shaped tree (gains are
    shifted by a constant, so differences and gradients map unchanged)."""
    return {_NAMES[path_str(kp)]: leaf
            for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def make_params(conf: dict, cfg, mesh, seed: int, *, norm_std: float,
                bias_std: float):
    """The program's parameters, made on the devices from the seed in one
    jitted call, in the program's shardings.  Returns (params, pspecs)."""
    from repro.launch import build

    dims_ = weights.dims(conf)
    shapes, pspecs = build.abstract_params(cfg, mesh)
    gshapes = build.global_shape(shapes, pspecs, mesh)
    for kp, s in jax.tree_util.tree_flatten_with_path(gshapes)[0]:
        want = weights.shapes(conf)[_NAMES[path_str(kp)]][0]
        if tuple(s.shape) != tuple(want):
            raise ValueError(f"{path_str(kp)}: program {s.shape} vs "
                             f"published layout {want} ({dims_})")
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), pspecs)
    make = jax.jit(lambda key: to_program(weights.make(
        conf, key, norm_std=norm_std, bias_std=bias_std), gshapes),
        out_shardings=shardings)
    return make(weights.seed_key(seed)), pspecs

