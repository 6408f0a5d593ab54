"""Collective traffic counted from compiled HLO text.  Importing this
module sets no flags and touches no device."""
from __future__ import annotations

import re


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of collective ops in the (scheduled) HLO."""
    dtypes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
              "u8": 1, "f64": 8, "s64": 8, "pred": 1, "s16": 2, "u16": 2}
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    out = {k: 0 for k in kinds}
    counts = {k: 0 for k in kinds}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w\.\-]+ = (.*?)\s*(all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)", ls)
        if not m:
            continue
        kind = m.group(2)
        # async starts are counted; done ops carry no new bytes
        if re.search(rf"{kind}-done", ls):
            continue
        shapes = shape_re.findall(m.group(1))
        nbytes = 0
        for dt, dims in shapes:
            if dt not in dtypes:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * dtypes[dt]
        out[kind] += nbytes
        counts[kind] += 1
    return {"bytes": out, "counts": counts}
