"""The latent paged-decode kernel's share of its roofline: for the traced
window's decodes, max(latent-page bytes / peak HBM bandwidth, latent
attention FLOPs / bf16 peak) over the device time of the decode
program's `attend` scope (the kernel and its operand preparation).
Bytes: every cached position a decode attends, all layers, 576 bf16
lanes each; FLOPs: 2*H*(2r + rope) per cached position and layer
(chipbench/flops_mla_moe.py)."""
from chipbench import flops_mla_moe as F
from chipbench import scopes_mla_moe


def read(r):
    by = scopes_mla_moe.program_scope_seconds(r, "decode_fn")
    if not by or by.get("attend", 0.0) <= 0:
        return None
    keys = r.counters["decode_context"]
    need_s = max(keys * F.latent_bytes_per_key(r.config)
                 / r.peak["hbm_bytes_per_s"],
                 keys * F.attention_pair_flops(r.config, True)
                 / r.peak["bf16_flops_per_s"])
    return 100.0 * need_s / by["attend"]
