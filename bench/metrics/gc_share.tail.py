"""Share of the traced interval spent in the process's cyclic garbage
collections, on the harness's clock: every Python thread (admission, the
serve loop, retire, the traffic generator) stops while one runs."""


def read(m):
    t = m.traced
    if t is None or t.seconds <= 0:
        return None
    return 100.0 * sum(e[1] for e in t.gc_events) / t.seconds
