"""Mean host time of the serve loop per batch retired in the traced
interval, from the device join until every ticket is written and the stats
are closed (fetch, scatter, observe), from the program's
``ServeStats.retire_seconds`` over ``retired_batches``."""

import serveloop


def read(m):
    return serveloop.per(m, "retire_seconds", "retired_batches", 1e6)
