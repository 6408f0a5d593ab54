"""Instruction -> named scope maps of the MoE+MLA serving programs, and
the device seconds of a program by scope.

`hlo_scopes.instruction_scopes` with the scopes the MoE+MLA model adds:
`mla_absorb` (the query absorption and the W_vb lift), `moe_route`,
`moe_experts` and `moe_shared`; `attend` holds the paged-decode kernel
on the decode path.  An instruction maps to the innermost of these on
its op name's path, `other` without any, `unscoped` without an op name.
"""
from __future__ import annotations

from chipbench import hlo_scopes

SCOPES = hlo_scopes.SCOPES + ("mla_absorb", "moe_route", "moe_experts",
                              "moe_shared")
MOE = ("moe_route", "moe_experts", "moe_shared")


def scope_of(op_name: str | None) -> str:
    if not op_name:
        return hlo_scopes.UNSCOPED
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return hlo_scopes.OTHER


def instruction_scopes(hlo_text: str) -> dict:
    out = {}
    for line in hlo_text.splitlines():
        m = hlo_scopes._INSTR.match(line)
        if m:
            op = hlo_scopes._OP_NAME.search(line)
            out[m.group(1)] = scope_of(op.group(1) if op else None)
    return out


def program_scope_seconds(reading, program: str) -> dict | None:
    """{scope: device seconds} of the runs of `program` in the traced
    window (ops its compiled text lacks count as `unmatched`), or None
    when the trace or the maps are missing."""
    module_op = getattr(reading.reduction, "module_op_s", None)
    scopes = (reading.counters.get("op_scopes") or {}).get(program)
    if not module_op or not scopes:
        return None
    out = {}
    for (prog, op), sec in module_op.items():
        if program in prog:
            s = scopes.get(op, "unmatched")
            out[s] = out.get(s, 0.0) + sec
    return out or None
