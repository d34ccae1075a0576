"""Queries per dispatched batch over the batch size, from the program's
ServeStats counters across the traced interval."""


def read(m):
    t = m.traced
    if t is None or t.counters["batches"] <= 0:
        return None
    return 100.0 * t.counters["queries"] / (t.counters["batches"]
                                            * t.batch_size)
