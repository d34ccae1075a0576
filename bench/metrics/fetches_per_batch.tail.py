"""Mean number of device arrays the serve loop copied to the host per
batch retired in the traced interval: 1 where a batch's answers cross in
one copy, 5 where an argmin batch's five outputs cross one by one.  From
the program's ``ServeStats.fetched_arrays`` over ``retired_batches``; a
program without that counter reads None."""

import serveloop


def read(m):
    return serveloop.per(m, "fetched_arrays", "retired_batches", 1.0)
