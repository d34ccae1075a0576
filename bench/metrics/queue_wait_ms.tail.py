"""Mean ``queue_wait`` stage (admitted to its group's launch) of the
program's request spans begun in the traced interval, every request
sampled."""


def read(m):
    t = m.traced
    waits = [s.stages["queue_wait"] for s in (t.spans if t else ())
             if "queue_wait" in s.stages]
    return 1e3 * sum(waits) / len(waits) if waits else None
