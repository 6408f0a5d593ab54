"""deepseek-v3-671b [moe]: 61L d7168 128H MLA, 1 shared + 256 routed
top-8 experts (ff 2048), first 3 layers dense (ff 18432), MTP head,
v129280.  Sigmoid routing with a correction bias, limited to the best 4
of 8 expert groups, gates normalised and scaled by 2.5 (`noaux_tc`);
YaRN rope (factor 40 over 4096 original positions).  EP over the full
(data x model) mesh, ZeRO-3 fsdp for the dense trunk, int8 optimizer
moments. [arXiv:2412.19437; hf config.json]"""
import jax.numpy as jnp

from ..models.config import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b", family="moe", n_layers=61, d_model=7168,
    n_heads=128, n_kv_heads=128, head_dim=128, d_ff=18432, vocab=129280,
    attn="mla",
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128,
                  qk_rope_dim=64, v_dim=128),
    moe=MoEConfig(n_experts=256, top_k=8, d_ff=2048, n_shared=1,
                  first_dense_layers=3, ep_over_data=True,
                  score_func="sigmoid", correction_bias=True, n_group=8,
                  topk_group=4, routed_scale=2.5),
    rope_theta=10000.0,
    yarn=YarnConfig(factor=40.0, beta_fast=32.0, beta_slow=1.0,
                    original_max_pos=4096, mscale=1.0, mscale_all_dim=1.0),
    mtp=True, fsdp=True, moment_dtype="int8", microbatches=16,
    param_dtype=jnp.bfloat16,   # 1.3 TB of experts: bf16 storage, f32
                                # optimizer math (deepseek itself used fp8)
)


def smoke():
    return ModelConfig(
        name="deepseek-smoke", family="moe", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=160, vocab=128,
        attn="mla",
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
                      qk_rope_dim=8, v_dim=16),
        moe=MoEConfig(n_experts=8, top_k=2, d_ff=32, n_shared=1,
                      first_dense_layers=1, score_func="sigmoid",
                      correction_bias=True, n_group=4, topk_group=2,
                      routed_scale=2.5),
        yarn=YarnConfig(factor=40.0, original_max_pos=64),
        mtp=True, remat="none", microbatches=1)
