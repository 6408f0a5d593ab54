"""Mean token rows a held expert computed per decode step of the window,
over every MoE layer: the engine's per-step expert-row counts (returned
with each step's tokens) summed, over decode steps x MoE layers x held
experts."""


def read(r):
    c = r.counters
    if not c.get("expert_row_steps") or not c.get("expert_rows_shape"):
        return None
    layers, held = c["expert_rows_shape"]
    return c["expert_rows"] / (c["expert_row_steps"] * layers * held)
