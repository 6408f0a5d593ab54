"""Model FLOPs and bytes of DeepSeek-V3 serving at one expert-parallel
chip's share, from its configuration's shapes.  The work the model
requires, not what the program happens to compute: causal attention
counts only the keys at or before each query, padding is not counted,
and a held expert counts only the token rows routed to it.

A multiply-add is 2 FLOPs.  Per token and layer: the MLA projections
(q down and up, the joint kv down, the output) and, where the layer has
them, the dense SwiGLU (3*d*F) or the shared expert (3*d*Fs) plus the
router (d*E); each routed row of a held expert 3*d*Fe; the head 2*d*V.
Attention differs between the two forms the program runs:

- prefill, non-absorbed: the kv up-projection r*H*(nope+v) per token,
  and per (query, key) pair 2*H*(nope+rope) for the score and 2*H*v for
  the weighted sum;
- decode, absorbed: the query absorption H*nope*r and the value lift
  H*r*v per token, and per cached key 2*H*(r+rope) for the score and
  2*H*r for the weighted sum of the latent rows.
"""
from __future__ import annotations

from chipbench.flops import causal_pairs
from chipbench.weights_mla_moe import dims, is_moe

BF16 = 2


def _proj(s: dict) -> int:
    """MLA projection weights every token multiplies in both forms."""
    H = s["H"]
    return (s["d"] * s["qr"] + s["qr"] * H * (s["nope"] + s["rope"])
            + s["d"] * (s["r"] + s["rope"]) + H * s["v"] * s["d"])


def _mlp(conf: dict, s: dict, layer: int) -> int:
    """Weights of a layer's dense MLP, or of its shared expert and router."""
    if not is_moe(conf, layer):
        return 3 * s["d"] * s["F"]
    return 3 * s["d"] * s["Fs"] + s["d"] * s["E"]


def expert_row_flops(conf: dict) -> int:
    s = dims(conf)
    return 2 * 3 * s["d"] * s["Fe"]


def _per_token(conf: dict, absorbed: bool) -> int:
    s = dims(conf)
    H = s["H"]
    lift = (H * s["nope"] * s["r"] + H * s["r"] * s["v"] if absorbed
            else s["r"] * H * (s["nope"] + s["v"]))
    per = sum(_proj(s) + lift + _mlp(conf, s, i) for i in range(s["L"]))
    return 2 * (per + s["d"] * s["V"])


def attention_pair_flops(conf: dict, absorbed: bool) -> int:
    """FLOPs of one (query, key) pair over all layers."""
    s = dims(conf)
    per = (2 * s["H"] * (2 * s["r"] + s["rope"]) if absorbed
           else 2 * s["H"] * (s["nope"] + s["rope"] + s["v"]))
    return s["L"] * per


def prefill_flops(conf: dict, prompt_len: int, expert_rows: float) -> int:
    """A prompt's prefill; `expert_rows`: token rows its held experts
    computed, over every MoE layer."""
    return (_per_token(conf, False) * prompt_len
            + attention_pair_flops(conf, False) * causal_pairs(prompt_len)
            + int(expert_rows * expert_row_flops(conf)))


def decode_flops(conf: dict, context_sum: int, n_tokens: int,
                 expert_rows: int) -> int:
    """n_tokens decoded tokens whose attention spans context_sum cached
    keys in all, and whose held experts computed expert_rows token rows
    over every MoE layer."""
    return (_per_token(conf, True) * n_tokens
            + attention_pair_flops(conf, True) * context_sum
            + expert_rows * expert_row_flops(conf))


def latent_bytes_per_key(conf: dict) -> int:
    """Latent-page bytes one cached position costs a decode, all layers."""
    s = dims(conf)
    return s["L"] * (s["r"] + s["rope"]) * BF16


def decode_weight_bytes(conf: dict) -> int:
    """Weight bytes one decode step reads: every layer's MLA, dense MLP,
    shared expert and router, the held experts, and the head."""
    s = dims(conf)
    H = s["H"]
    lift = s["r"] * H * (s["nope"] + s["v"])
    n = sum(_proj(s) + lift + _mlp(conf, s, i)
            + (s["held"] * 3 * s["d"] * s["Fe"] if is_moe(conf, i) else 0)
            for i in range(s["L"]))
    return (n + s["d"] * s["V"]) * BF16
