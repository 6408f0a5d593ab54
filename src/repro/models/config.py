"""ModelConfig — one dataclass that spans all 10 assigned architectures.

Families: dense GQA decoders, MoE (top-k + shared experts, MLA), hybrid
(Mamba2 + shared attention), pure SSM, encoder-only audio, VLM (backbone +
stub frontend).  `input_specs()` produces the ShapeDtypeStruct stand-ins
for each assigned input shape (train / prefill / decode / long-decode).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                  # per-expert hidden
    n_shared: int = 0          # shared (always-on) experts
    first_dense_layers: int = 0
    router_noise: float = 0.0
    capacity_factor: float = 1.25
    ep_over_data: bool = False   # EP group = (data x model) instead of model
    # routing (layers.route): scores softmax | sigmoid; selection over
    # score + correction bias (a router leaf when `correction_bias`),
    # restricted to the `topk_group` best of `n_group` expert groups (a
    # group scores the sum of its top 2); gates are the selected scores
    # normalised to sum 1, times `routed_scale`
    score_func: str = "softmax"
    correction_bias: bool = False
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    # the expert share a serving chip holds (layers.moe_held): experts
    # [experts_offset, experts_offset + experts_held) of n_experts; None
    # holds them all
    experts_held: int | None = None
    experts_offset: int = 0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (arXiv:2309.00071): frequencies ramped from
    original to 1/factor between the `beta_fast` and `beta_slow`
    rotation counts of `original_max_pos`, and an attention-scale factor
    0.1 * mscale * ln(factor) + 1."""
    factor: float
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    original_max_pos: int = 4096
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state: int = 128           # N
    head_dim: int = 64         # P
    n_groups: int = 1          # G (B/C groups)
    chunk: int = 128
    conv_width: int = 4
    expand: int = 2


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    # attention flavor
    attn: str = "gqa"           # gqa | mla | none
    qkv_bias: bool = False
    causal: bool = True
    window: int | None = None            # sliding window (all layers)
    local_global_period: int | None = None  # gemma2: odd layers local SWA
    local_window: int | None = None
    softcap: float | None = None          # attention logit softcap
    final_softcap: float | None = None    # lm-head logit softcap
    rope_theta: float = 10000.0
    yarn: YarnConfig | None = None       # rope scaling of the MLA rope lanes
    mla: MLAConfig | None = None
    # MoE / SSM / hybrid
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    hybrid_attn_period: int | None = None   # zamba2: shared attn every k
    # heads
    tie_embeddings: bool = False
    mtp: bool = False            # deepseek multi-token prediction head
    # frontend stub
    frontend: str | None = None  # vision | audio
    n_frontend_tokens: int = 0
    # execution
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.float32     # stored weights (bf16 for the
                                       # largest archs; optimizer math
                                       # always runs f32)
    use_pallas: bool = False
    attention: str = "mono"      # mono | ring: "ring" runs sequence-sharded
                                 # attention as the fused comm-compute ring
                                 # (core/fusion.ring_attention) when inputs
                                 # are sequence-sharded over `data` on the
                                 # shmem backend (DESIGN.md §14)
    remat: str = "full"          # none | full
    logit_dtype: Any = jnp.float32
    fsdp: bool = False           # ZeRO-3: 2D block weights sharded over data
    probe_unroll: bool = False   # roofline probes: unroll every scan so
                                 # cost_analysis counts all iterations
    microbatches: int = 1        # grad-accumulation steps per train_step
    moment_dtype: str = "f32"    # f32 | bf16 | int8 (optimizer moments)
    shard_strategy: str = "tp"   # tp | dp_only (replicate params, shard the
                                 # batch over data x model — right for small
                                 # models where TP width starves the MXU)

    @property
    def hd(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def is_encoder(self) -> bool:
        return not self.causal

    # -- parameter counting (for MODEL_FLOPS = 6*N*D) -----------------------
    def param_count(self, active_only: bool = False) -> int:
        d, ff, v = self.d_model, self.d_ff, self.vocab
        hd = self.hd if self.attn != "none" else 0
        n_q, n_kv = self.n_heads, self.n_kv_heads
        per_layer = 0
        if self.attn == "gqa":
            per_layer += d * hd * (n_q + 2 * n_kv) + n_q * hd * d
            if self.qkv_bias:
                per_layer += hd * (n_q + 2 * n_kv)
        elif self.attn == "mla":
            m = self.mla
            per_layer += d * m.q_lora_rank
            per_layer += m.q_lora_rank * n_q * (m.qk_nope_dim + m.qk_rope_dim)
            per_layer += d * (m.kv_lora_rank + m.qk_rope_dim)
            per_layer += m.kv_lora_rank * n_q * (m.qk_nope_dim + m.v_dim)
            per_layer += n_q * m.v_dim * d
        if self.ssm is not None:
            s = self.ssm
            d_in = s.expand * d
            nheads = d_in // s.head_dim
            per_layer_ssm = d * (2 * d_in + 2 * s.n_groups * s.state + nheads)
            per_layer_ssm += d_in * d + nheads  # out proj + A
            per_layer_ssm += s.conv_width * (d_in + 2 * s.n_groups * s.state)
        # mlp
        if self.moe is not None:
            mo = self.moe
            dense_ff = 3 * d * ff
            routed = 3 * d * mo.d_ff
            active_mlp = (mo.top_k + mo.n_shared) * routed + d * mo.n_experts
            total_mlp = (mo.n_experts + mo.n_shared) * routed + d * mo.n_experts
            mlp = active_mlp if active_only else total_mlp
        else:
            mlp = 3 * d * ff
            dense_ff = mlp

        total = 0
        for i in range(self.n_layers):
            is_ssm_layer = (self.family in ("ssm", "hybrid"))
            if is_ssm_layer:
                total += per_layer_ssm + 2 * d
                continue
            total += per_layer + 2 * d
            if self.moe is not None and i < self.moe.first_dense_layers:
                total += dense_ff
            elif self.d_ff > 0:
                total += mlp
        if self.hybrid_attn_period:
            # one shared attention block (+ mlp) reused
            total += per_layer + 3 * d * self.d_ff + 2 * d
        total += v * d * (1 if self.tie_embeddings else 2)
        return int(total)


# ---------------------------------------------------------------------------
# input shapes (assigned): each cell is (name, seq_len, global_batch, kind)
# ---------------------------------------------------------------------------

SHAPES = {
    "train_4k": dict(seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": dict(seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": dict(seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": dict(seq_len=524288, global_batch=1, kind="decode"),
}

# long_500k eligibility: sub-quadratic state only (DESIGN.md §5)
LONG_OK_FAMILIES = ("ssm", "hybrid")


def shape_applicable(cfg: ModelConfig, shape: str) -> tuple[bool, str]:
    s = SHAPES[shape]
    if s["kind"] == "decode" and cfg.is_encoder:
        return False, "encoder-only arch has no decode step"
    if shape == "long_500k":
        if cfg.family in LONG_OK_FAMILIES:
            return True, ""
        if cfg.window is not None or cfg.local_global_period is not None:
            return True, ""  # SWA-bounded KV
        return False, "pure full-attention arch skipped for 500k decode"
    return True, ""


def input_specs(cfg: ModelConfig, shape: str, *, batch_override=None):
    """ShapeDtypeStruct stand-ins for every model input of a given shape
    cell (no allocation; shardable)."""
    s = SHAPES[shape]
    B = batch_override or s["global_batch"]
    L = s["seq_len"]
    i32 = jnp.int32
    if s["kind"] == "train":
        if cfg.frontend == "audio":
            # encoder masked-prediction: stub frontend provides frame embeds
            return dict(
                frames=jax.ShapeDtypeStruct((B, L, cfg.d_model), cfg.dtype),
                targets=jax.ShapeDtypeStruct((B, L), i32),
            )
        specs = dict(
            tokens=jax.ShapeDtypeStruct((B, L), i32),
            targets=jax.ShapeDtypeStruct((B, L), i32),
        )
        if cfg.frontend == "vision":
            specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype)
        return specs
    if s["kind"] == "prefill":
        if cfg.frontend == "audio":
            return dict(frames=jax.ShapeDtypeStruct((B, L, cfg.d_model),
                                                    cfg.dtype))
        specs = dict(tokens=jax.ShapeDtypeStruct((B, L), i32))
        if cfg.frontend == "vision":
            specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype)
        return specs
    # decode: one new token against a cache of length L
    return dict(
        tokens=jax.ShapeDtypeStruct((B, 1), i32),
        positions=jax.ShapeDtypeStruct((B,), i32),
    )
