"""Map the instructions of a compiled program to the model's named scopes.

The program wraps its blocks in `jax.named_scope` (`models/layers.py`,
`serve/step.py`), which reaches the compiled HLO text as
`metadata={op_name="jit(decode_fn)/while/body/closed_call/kv_gather/..."}`
on the instructions that the device trace names.  The trace itself
carries no metadata, so its operations ("fusion.187") are matched to
scopes through the text of the program they ran in.

An instruction maps to the innermost scope of `SCOPES` on its op name's
path, to `other` when its op name holds none of them, and to `unscoped`
when it has no op name (instructions the compiler inserted, such as a
copy that changes a buffer's layout).
"""
from __future__ import annotations

import re

SCOPES = ("kv_update", "kv_gather", "attend", "attn_proj", "mlp", "lm_head",
          "sample")
OTHER, UNSCOPED = "other", "unscoped"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z_][\w.\-]*) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def scope_of(op_name: str | None) -> str:
    """The innermost of `SCOPES` on an op name's path."""
    if not op_name:
        return UNSCOPED
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return OTHER


def instruction_scopes(hlo_text: str) -> dict:
    """Instruction name -> scope, for every instruction of the text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(op.group(1) if op else None)
    return out
