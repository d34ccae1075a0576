"""Share of the traced interval in which the device was idle while the
serve loop did host work (``serve.stage``, ``serve.dispatch``,
``serve.fetch``, ``serve.scatter``, ``serve.observe``: host work holds the
chip), by exact overlap of the device's idle intervals with the loop's
spans on the profiler timeline."""

import serveloop


def read(m):
    return serveloop.idle_share(m, "host")
