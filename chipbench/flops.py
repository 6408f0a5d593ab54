"""Model FLOPs and bytes of a dense GQA decoder, from its configuration's
shapes.  These are the work the model requires, not what a program
happens to compute: causal attention counts only the keys at or before
each query, recomputation under remat is not counted, and padding is not
counted.

A multiply-add is 2 FLOPs.  Per token and layer the projections and the
MLP take 2 * (d*(H + 2K)*hd + H*hd*d + 3*d*F); the head 2*d*V; attention
over c keys 4*H*hd*c (scores and the weighted sum).  Training is 3x the
forward pass (the backward pass takes two matrix products per forward
one).
"""
from __future__ import annotations

from chipbench.weights import dims


def matmul_params(conf: dict) -> int:
    """Weights that every token multiplies: layers and the head."""
    s = dims(conf)
    d, H, K, hd, F, V = (s[k] for k in ("d", "H", "K", "hd", "F", "V"))
    per_layer = d * (H + 2 * K) * hd + H * hd * d + 3 * d * F
    return s["L"] * per_layer + d * V


def decode_param_bytes(conf: dict, bytes_per_param: int) -> int:
    """Parameter bytes one decode step must read: every matmul weight
    (the tied embedding read as the head), norm gains and biases; the
    embedding rows of an untied model's inputs are negligible."""
    s = dims(conf)
    n = matmul_params(conf) + s["L"] * 2 * s["d"] + s["d"]
    if conf.get("qkv_bias"):
        n += s["L"] * (s["H"] + 2 * s["K"]) * s["hd"]
    return n * bytes_per_param


def kv_bytes_per_token(conf: dict, bytes_per_value: int) -> int:
    s = dims(conf)
    return s["L"] * 2 * s["K"] * s["hd"] * bytes_per_value


def attention_flops(conf: dict, queries_keys: int) -> int:
    """FLOPs of attention summed over (query, key) pairs."""
    s = dims(conf)
    return 4 * s["L"] * s["H"] * s["hd"] * queries_keys


def causal_pairs(n: int) -> int:
    """(query, key) pairs of causal attention over n positions."""
    return n * (n + 1) // 2


def prefill_flops(conf: dict, prompt_len: int) -> int:
    return (2 * matmul_params(conf) * prompt_len
            + attention_flops(conf, causal_pairs(prompt_len)))


def decode_flops(conf: dict, context_sum: int, n_tokens: int) -> int:
    """n_tokens decoded tokens whose attention spans context_sum keys in
    all (each token's position + 1)."""
    return (2 * matmul_params(conf) * n_tokens
            + attention_flops(conf, context_sum))


def train_flops_per_sequence(conf: dict, seq_len: int) -> int:
    return 3 * prefill_flops(conf, seq_len)
