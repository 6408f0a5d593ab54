"""Seeded random weights of a DeepSeek-V3 decoder (MLA, leading dense
layers, MoE layers with a shared expert and a held share of the routed
experts), in its published layout, one layer at a time.

One generator serves both sides of a comparison: the system under test
stacks these leaves into its own tree (`sut_mla_moe.make_params`), and
the plain reference makes each layer again from the same seed when it
runs it.  Every leaf is a function of (seed, layer, leaf name, expert
id) alone, so a layer, or one expert of it, is made without the others;
and every value is rounded to bfloat16, the configuration's storage, so
both sides hold the same numbers.

Layout for width d, H heads, q/kv ranks qr/r, head dims nope/rope/v,
dense width F, expert width Fe, E routed experts of which the held share
[offset, offset + held), vocabulary V:

  embed (V, d); head (d, V); final_norm (d,)
  layer i: ln1, ln2 (d,); wq_a (d, qr); q_norm (qr,);
    wq_b (qr, H*(nope+rope)); wkv_a (d, r+rope) ([c_kv | k_rope]);
    kv_norm (r,); wkv_b (r, H*(nope+v)); wo (H*v, d)
  dense layer: w_gate, w_up (d, F); w_down (F, d)
  MoE layer: router (d, E); router_bias (E,); experts/w_gate,
    experts/w_up (held, d, Fe); experts/w_down (held, Fe, d);
    shared/w_gate, shared/w_up (d, Fe); shared/w_down (Fe, d)

Norm gains are multiplicative (`x * gain`), as published.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORM_STD = 0.1           # gains 1 + N(0, 0.1^2)
BIAS_STD = 0.01          # correction bias N(0, 0.01^2)

# leaf name -> its number in the key derivation (never reorder)
_LEAF_IDS = {name: i for i, name in enumerate((
    "embed", "head", "final_norm", "ln1", "ln2", "wq_a", "q_norm", "wq_b",
    "wkv_a", "kv_norm", "wkv_b", "wo", "w_gate", "w_up", "w_down", "router",
    "router_bias", "experts/w_gate", "experts/w_up", "experts/w_down",
    "shared/w_gate", "shared/w_up", "shared/w_down"))}


def seed_key(seed: int):
    """A key of the `rbg` generator (XLA's RngBitGenerator, which a TPU
    runs at memory speed; threefry takes minutes for 4.3 G weights) from
    any non-negative seed, 64-bit ones included."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF, impl="rbg"),
                              seed >> 32)


def dims(conf: dict) -> dict:
    fe = conf["moe_intermediate_size"]
    return {"L": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "H": conf["num_attention_heads"], "qr": conf["q_lora_rank"],
            "r": conf["kv_lora_rank"], "nope": conf["qk_nope_head_dim"],
            "rope": conf["qk_rope_head_dim"], "v": conf["v_head_dim"],
            "F": conf["intermediate_size"], "Fe": fe,
            "Fs": fe * conf["n_shared_experts"],
            "E": conf["router_experts"], "held": conf["n_routed_experts"],
            "offset": conf["experts_offset"],
            "dense": conf["first_k_dense_replace"], "V": conf["vocab_size"]}


def is_moe(conf: dict, layer: int) -> bool:
    return layer >= conf["first_k_dense_replace"]


def layer_shapes(conf: dict, layer: int) -> dict:
    """Leaf name -> (shape, kind) of one layer; kind is the fan-in of a
    matrix, "gain" or "bias"."""
    s = dims(conf)
    d, H = s["d"], s["H"]
    out = {"ln1": ((d,), "gain"), "ln2": ((d,), "gain"),
           "wq_a": ((d, s["qr"]), d), "q_norm": ((s["qr"],), "gain"),
           "wq_b": ((s["qr"], H * (s["nope"] + s["rope"])), s["qr"]),
           "wkv_a": ((d, s["r"] + s["rope"]), d),
           "kv_norm": ((s["r"],), "gain"),
           "wkv_b": ((s["r"], H * (s["nope"] + s["v"])), s["r"]),
           "wo": ((H * s["v"], d), H * s["v"])}
    if not is_moe(conf, layer):
        out.update({"w_gate": ((d, s["F"]), d), "w_up": ((d, s["F"]), d),
                    "w_down": ((s["F"], d), s["F"])})
        return out
    out.update({"router": ((d, s["E"]), d), "router_bias": ((s["E"],), "bias"),
                "experts/w_gate": ((s["held"], d, s["Fe"]), d),
                "experts/w_up": ((s["held"], d, s["Fe"]), d),
                "experts/w_down": ((s["held"], s["Fe"], d), s["Fe"]),
                "shared/w_gate": ((d, s["Fs"]), d),
                "shared/w_up": ((d, s["Fs"]), d),
                "shared/w_down": ((s["Fs"], d), s["Fs"])})
    return out


def _draw(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "gain":
        x = 1.0 + NORM_STD * z
    elif kind == "bias":
        x = BIAS_STD * z
    else:
        x = z / math.sqrt(kind)
    return x.astype(jnp.bfloat16)


def leaf(conf: dict, key, layer: int | None, name: str, shape, kind):
    """One leaf in bfloat16.  An expert leaf stacks its held experts, each
    drawn from its own global expert id, so every share of the experts
    holds the same values for the same expert."""
    k = jax.random.fold_in(jax.random.fold_in(key, 0 if layer is None
                                              else layer + 1),
                           _LEAF_IDS[name])
    if not name.startswith("experts/"):
        return _draw(k, shape, kind)
    off = conf["experts_offset"]
    return jnp.stack([_draw(jax.random.fold_in(k, off + e), shape[1:], kind)
                      for e in range(shape[0])])


def make_layer(conf: dict, key, layer: int) -> dict:
    """{leaf name: bfloat16 array} of one layer.  Traceable."""
    return {name: leaf(conf, key, layer, name, shape, kind)
            for name, (shape, kind) in layer_shapes(conf, layer).items()}


def global_shapes(conf: dict) -> dict:
    s = dims(conf)
    return {"embed": ((s["V"], s["d"]), s["d"]),
            "head": ((s["d"], s["V"]), s["d"]),
            "final_norm": ((s["d"],), "gain")}


def make_global(conf: dict, key) -> dict:
    """The embedding, head and final gain, in bfloat16.  Traceable."""
    return {name: leaf(conf, key, None, name, shape, kind)
            for name, (shape, kind) in global_shapes(conf).items()}
