"""Mean launch lag per query answered in the traced interval: from when
its group could first ship (full, deadline or flush) or its own later
arrival, to the group's launch.  The part of ``queue_wait`` that the serve
loop's backlog adds to the coalescing deadline, from the program's
``ServeStats.launch_lag_seconds`` over ``queries``."""

import serveloop


def read(m):
    return serveloop.per(m, "launch_lag_seconds", "queries", 1e3)
