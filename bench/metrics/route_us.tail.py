"""Mean routing time of one submit() call in the traced interval: the
engine pin and ``buckets_of``, from the program's
``ServeStats.route_seconds`` over ``submit_calls``."""

import serveloop


def read(m):
    return serveloop.per(m, "route_seconds", "submit_calls", 1e6)
