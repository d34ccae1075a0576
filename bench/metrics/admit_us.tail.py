"""Mean time inside one submit() call that admitted its queries in the
traced interval, entry to return (normalise, route, the backpressure
gate, enqueue), from the program's ``ServeStats.admit_seconds`` over
``submit_calls``."""

import serveloop


def read(m):
    return serveloop.per(m, "admit_seconds", "submit_calls", 1e6)
