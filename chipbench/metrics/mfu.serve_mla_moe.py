"""Model FLOP utilization of DeepSeek-V3 serving at one chip's share:
the useful FLOPs of the traced window's steps (prompt tokens without
bucket padding; decoded tokens with their latent attention over live
positions; held experts by the token rows routed to them; the shared
expert, the dense layers and the head slice; chipbench/flops_mla_moe.py)
over the device time of the prefill and decode programs, over the bf16
peak.  A prefill's held-expert rows are taken at the decodes' measured
rows per token (the prefill does not report its own)."""
from chipbench import flops_mla_moe as F

PROGRAMS = ("prefill_fn", "decode_fn")


def read(r):
    c = r.counters
    busy = sum(s for name, s in r.reduction.module_s.items()
               if any(p in name for p in PROGRAMS))
    if busy <= 0 or "expert_rows" not in c:
        return None
    per_token = c["expert_rows"] / max(c["decode_rows"], 1)
    work = sum(F.prefill_flops(r.config, n, n * per_token)
               for n in c["prefill_prompt_lens"])
    work += F.decode_flops(r.config, c["decode_context"], c["decode_rows"],
                           c["expert_rows"])
    return 100.0 * work / busy / r.peak["bf16_flops_per_s"]
