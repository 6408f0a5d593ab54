"""Share of the traced serving window in which no operation ran on the
device (device trace)."""


def read(r):
    return 100.0 * (1.0 - r.reduction.busy_s / r.reduction.window_s)
