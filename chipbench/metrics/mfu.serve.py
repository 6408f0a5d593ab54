"""Model FLOP utilization of the serving programs: the FLOPs of the
useful tokens of the traced window's steps (prompt tokens without bucket
padding, decoded tokens with their causal context; chipbench/flops.py)
over the device time of the prefill and decode programs, over the bf16
peak."""
from chipbench import flops

PROGRAMS = ("prefill_fn", "decode_fn")


def read(r):
    c = r.counters
    busy = sum(s for name, s in r.reduction.module_s.items()
               if any(p in name for p in PROGRAMS))
    if busy <= 0:
        return None
    work = sum(flops.prefill_flops(r.config, n)
               for n in c["prefill_prompt_lens"])
    work += flops.decode_flops(r.config, c["decode_context"],
                               c["decode_rows"])
    return 100.0 * work / busy / r.peak["bf16_flops_per_s"]
