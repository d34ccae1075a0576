"""Share of the traced interval in which the device was idle while the
serve loop slept in ``serve.wait`` (no group could ship yet: the
coalescing deadline holds the chip), by exact overlap of the device's idle
intervals with the loop's spans on the profiler timeline."""

import serveloop


def read(m):
    return serveloop.idle_share(m, "wait")
