"""The system under test for a DeepSeek-V3 configuration file: the
repository's `ModelConfig` and its parameter tree, built from
`chipbench/configs/` and `weights_mla_moe`.

Besides the drivers, only this module and `sut.py` import the program.
The reference imports neither.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from chipbench import sut, weights_mla_moe as W

# program leaf (under dense_layers/ or layers/) -> published leaf name
_LAYER_NAMES = {
    **{f"attn/{k}": k for k in ("wq_a", "q_norm", "wq_b", "wkv_a",
                                "kv_norm", "wkv_b", "wo")},
    "ln1": "ln1", "ln2": "ln2",
    **{f"mlp/{k}": k for k in ("w_gate", "w_up", "w_down")},
    "moe/router": "router", "moe/router_bias": "router_bias",
    **{f"moe/{k}": f"experts/{k}" for k in ("w_gate", "w_up", "w_down")},
    **{f"moe/shared/{k}": f"shared/{k}" for k in ("w_gate", "w_up",
                                                 "w_down")},
}
_GLOBAL_NAMES = {"embed/table": "embed", "embed/head": "head",
                 "final_norm": "final_norm"}
# gains: the program stores g - 1 and normalises by (1 + stored)
_GAINS = ("ln1", "ln2", "q_norm", "kv_norm", "final_norm")


def model_config(conf: dict):
    """The program's ModelConfig for a DeepSeek-V3 configuration file."""
    from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                     YarnConfig)

    if conf["rms_norm_eps"] != sut.PROGRAM_RMS_EPS:
        raise ValueError(f"{conf['name']}: rms_norm_eps "
                         f"{conf['rms_norm_eps']} is not the program's "
                         f"fixed {sut.PROGRAM_RMS_EPS}")
    prec = conf["precision"]
    if (prec["params"], prec["compute"], prec["logits"]) != (
            "bfloat16", "bfloat16", "float32"):
        raise ValueError(f"{conf['name']}: precision {prec} is not bf16 "
                         "params and compute with f32 logits")
    if (conf["scoring_func"], conf["topk_method"], conf["norm_topk_prob"],
            conf["moe_layer_freq"]) != ("sigmoid", "noaux_tc", True, 1):
        raise ValueError(f"{conf['name']}: routing other than DeepSeek-V3's")
    rs = conf["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"{conf['name']}: rope scaling {rs['type']!r}")
    return ModelConfig(
        name=conf["name"], family="moe", attn="mla",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["v_head_dim"], d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"], tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        yarn=YarnConfig(factor=float(rs["factor"]),
                        beta_fast=float(rs["beta_fast"]),
                        beta_slow=float(rs["beta_slow"]),
                        original_max_pos=rs["original_max_position_embeddings"],
                        mscale=float(rs["mscale"]),
                        mscale_all_dim=float(rs["mscale_all_dim"])),
        mla=MLAConfig(q_lora_rank=conf["q_lora_rank"],
                      kv_lora_rank=conf["kv_lora_rank"],
                      qk_nope_dim=conf["qk_nope_head_dim"],
                      qk_rope_dim=conf["qk_rope_head_dim"],
                      v_dim=conf["v_head_dim"]),
        moe=MoEConfig(n_experts=conf["router_experts"],
                      top_k=conf["num_experts_per_tok"],
                      d_ff=conf["moe_intermediate_size"],
                      n_shared=conf["n_shared_experts"],
                      first_dense_layers=conf["first_k_dense_replace"],
                      score_func="sigmoid", correction_bias=True,
                      n_group=conf["n_group"], topk_group=conf["topk_group"],
                      routed_scale=float(conf["routed_scaling_factor"]),
                      experts_held=conf["n_routed_experts"],
                      experts_offset=conf["experts_offset"]),
        mtp=conf["num_nextn_predict_layers"] > 0, remat="none",
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        logit_dtype=jnp.float32)


def _layer_leaf(conf: dict, key, group: str, sub: str, shape):
    """A stacked program leaf of `group` (dense_layers or layers) from
    the published leaves of its layers."""
    name = _LAYER_NAMES[sub]
    nd = conf["first_k_dense_replace"]
    layers = (range(nd) if group == "dense_layers"
              else range(nd, conf["num_hidden_layers"]))
    out = []
    for i in layers:
        lshape, kind = W.layer_shapes(conf, i)[name]
        x = W.leaf(conf, key, i, name, lshape, kind)
        out.append(x - 1 if name in _GAINS else x)
    return jnp.stack(out).astype(shape.dtype).reshape(shape.shape)


def make_params(conf: dict, cfg, mesh, seed: int):
    """The program's parameters, made on the devices from the seed, one
    jitted call per leaf (so no more than one leaf's float32 draw is
    live), in the program's shardings.  Returns (params, pspecs)."""
    from repro.launch import build

    shapes, pspecs = build.abstract_params(cfg, mesh)
    gshapes = build.global_shape(shapes, pspecs, mesh)
    key = W.seed_key(seed)
    flat, tree = jax.tree_util.tree_flatten_with_path(gshapes)
    spec_leaves = jax.tree.leaves(pspecs)
    out = []
    for (kp, s), sp in zip(flat, spec_leaves):
        path = sut.path_str(kp)
        group, _, sub = path.partition("/")
        if path in _GLOBAL_NAMES:
            name = _GLOBAL_NAMES[path]
            gshape, kind = W.global_shapes(conf)[name]

            def fn(k, name=name, gshape=gshape, kind=kind, s=s):
                x = W.leaf(conf, k, None, name, gshape, kind)
                x = x - 1 if name in _GAINS else x
                return x.astype(s.dtype).reshape(s.shape)
        else:
            def fn(k, group=group, sub=sub, s=s):
                return _layer_leaf(conf, k, group, sub, s)
        out.append(jax.jit(fn, out_shardings=NamedSharding(mesh, sp))(key))
    return jax.tree_util.tree_unflatten(tree, out), pspecs
