"""Mean live slots of the window's decode steps (the engine's batch), read
from the serving driver's counters.  Above the knee the queue keeps the
slots full, and completed tokens per second follow the batch the engine
holds and the time of its step."""


def read(r):
    c = r.counters
    if not c.get("decode_calls"):
        return None
    return c["decode_rows"] / c["decode_calls"]
