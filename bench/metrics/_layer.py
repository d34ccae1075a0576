"""Shared arithmetic of the per-layer readers (not a metric itself)."""

import work


def submit_us(m):
    t = m.traced
    if t is None or not len(t.submit_s):
        return None
    return 1e6 * float(t.submit_s.mean())


def idle_share(m):
    t = m.traced
    if t is None or t.red["window_s"] <= 0 or not t.red["devices"]:
        return None
    return 100.0 * (1.0 - t.red["busy_s"] / t.red["window_s"])


def entry_us(m, entry):
    t = m.traced
    if t is None or not t.answered or entry not in t.red["entry_s"]:
        return None
    return 1e6 * t.red["entry_s"][entry] / t.answered


def work_of(m, kind):
    """(operations, bytes) the traced interval's batches had to do."""
    t = m.traced
    ops = nbytes = 0.0
    for width, n in t.counters["batches_by_width"].items():
        if kind == "fold":
            o, b = work.fold(t.batch_size, width, t.edges)
            o, b = 2 * o, 2 * b             # one fold per endpoint side
        else:
            o, b = work.join(t.batch_size, width, t.edges)
        ops += n * o
        nbytes += n * b
    return ops, nbytes


def roofline(m, kind, entry):
    t = m.traced
    if t is None or entry not in t.red["entry_s"]:
        return None
    ops, nbytes = work_of(m, kind)
    return work.roofline_share(ops, nbytes, t.red["entry_s"][entry], t.peaks)
