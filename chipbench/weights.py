"""Seeded random weights of a dense GQA decoder, in its published layout.

One generator serves both sides of a comparison: the system under test
is given these weights (`sut.program_params` maps them into its own
tree), and the plain reference makes them again from the same seed.
Layers are stacked on a leading axis.  Norm gains are multiplicative
(`x * gain`), as published.

Layout for L layers, width d, H query and K key/value heads of size hd,
MLP width F and vocabulary V:

  embed (V, d); head (d, V) when the head is untied; final_norm (d,)
  layers/ln1, ln2 (L, d); wq (L, d, H*hd); wk, wv (L, d, K*hd);
  wo (L, H*hd, d); bq (L, H*hd), bk, bv (L, K*hd) with QKV bias;
  w_gate, w_up (L, d, F); w_down (L, F, d)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {"L": conf["num_hidden_layers"], "d": d, "H": h,
            "K": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or d // h,
            "F": conf["intermediate_size"], "V": conf["vocab_size"]}


def shapes(conf: dict) -> dict:
    """Leaf name -> (shape, fan_in); fan_in None marks a norm gain and
    0 a bias."""
    s = dims(conf)
    L, d, H, K, hd, F, V = (s[k] for k in ("L", "d", "H", "K", "hd", "F",
                                           "V"))
    out = {"embed": ((V, d), d), "final_norm": ((d,), None),
           "layers/ln1": ((L, d), None), "layers/ln2": ((L, d), None),
           "layers/wq": ((L, d, H * hd), d),
           "layers/wk": ((L, d, K * hd), d),
           "layers/wv": ((L, d, K * hd), d),
           "layers/wo": ((L, H * hd, d), H * hd),
           "layers/w_gate": ((L, d, F), d), "layers/w_up": ((L, d, F), d),
           "layers/w_down": ((L, F, d), F)}
    if not conf["tie_word_embeddings"]:
        out["head"] = ((d, V), d)
    if conf.get("qkv_bias"):
        out.update({"layers/bq": ((L, H * hd), 0),
                    "layers/bk": ((L, K * hd), 0),
                    "layers/bv": ((L, K * hd), 0)})
    return out


def make(conf: dict, key, *, norm_std: float, bias_std: float) -> dict:
    """Flat {leaf name: f32 array}.  Matrices are N(0, 1/fan_in) (the
    embedding N(0, 1/d), so logits start near unit scale); gains are
    1 + N(0, norm_std^2) and biases N(0, bias_std^2).  Traceable: call it
    inside `jax.jit` to make every leaf on the device in one program."""
    out = {}
    for i, (name, (shape, fan_in)) in enumerate(sorted(shapes(conf).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if fan_in is None:
            out[name] = 1.0 + norm_std * z
        elif fan_in == 0:
            out[name] = bias_std * z
        else:
            out[name] = z / math.sqrt(fan_in)
    return out


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
