"""Share, in %, of the decode program's device time in the MoE layers'
scopes: `moe_route`, `moe_experts` and `moe_shared` (each operation's
scope read from the compiled text, chipbench/scopes_mla_moe.py)."""
from chipbench import scopes_mla_moe


def read(r):
    by = scopes_mla_moe.program_scope_seconds(r, "decode_fn")
    if not by:
        return None
    busy = sum(by.values())
    return 100.0 * sum(by.get(s, 0.0) for s in scopes_mla_moe.MOE) / busy
