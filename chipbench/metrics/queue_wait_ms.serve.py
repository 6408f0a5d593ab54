"""Mean queue wait, in ms, of the requests admitted in the traced window:
admission minus submission, both stamped by the program's scheduler
(`Request.t_submit`, `SlotState.t_admit`; counter `queue_wait_s`).  A
mean, since an 8 s window admits about thirty requests, too few for a
tail."""


def read(r):
    waits = r.counters.get("queue_wait_s")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
