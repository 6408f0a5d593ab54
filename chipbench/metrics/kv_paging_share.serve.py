"""Share, in %, of the decode program's device time spent in operations
under the model's `kv_update` and `kv_gather` scopes: `module_op_s` of
`phase_reduce.Reduction` for the programs named `decode_fn`, each
operation's scope read from the program's compiled text (counter
`op_scopes`, from `hlo_scopes.instruction_scopes`).  Operations the
compiler inserted without metadata (`unscoped`) are not counted as
paging, whatever they move."""

PROGRAM = "decode_fn"
PAGING = ("kv_update", "kv_gather")


def read(r):
    module_op = getattr(r.reduction, "module_op_s", None)
    scopes = (r.counters.get("op_scopes") or {}).get(PROGRAM)
    if not module_op or not scopes:
        return None
    busy = paging = 0.0
    for (prog, op), sec in module_op.items():
        if PROGRAM in prog:
            busy += sec
            if scopes.get(op) in PAGING:
                paging += sec
    if busy <= 0:
        return None
    return 100.0 * paging / busy
