"""Bytes the decode calls of the traced window had to read (the float32
parameters once per call, plus the bfloat16 keys and values of every live
token; chipbench/flops.py) over the decode program's device time, over
the peak HBM bandwidth."""
from chipbench import flops

PARAM_BYTES, KV_BYTES = 4, 2          # float32 parameters, bfloat16 cache


def read(r):
    c = r.counters
    busy = sum(s for name, s in r.reduction.module_s.items()
               if "decode_fn" in name)
    if busy <= 0 or not c["decode_calls"]:
        return None
    need = (c["decode_calls"] * flops.decode_param_bytes(r.config, PARAM_BYTES)
            + c["decode_context"] * flops.kv_bytes_per_token(r.config,
                                                             KV_BYTES))
    return 100.0 * need / busy / r.peak["hbm_bytes_per_s"]
