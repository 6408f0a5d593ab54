"""Mean wall time of the ServeEngine.step() calls that began in the
traced window (host clock around the program's call; each step ends in a
host sync, so its device time is inside)."""


def read(r):
    c = r.counters
    if not c["engine_steps"]:
        return None
    return 1e3 * c["engine_step_s"] / c["engine_steps"]
