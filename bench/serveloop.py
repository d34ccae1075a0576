"""The serve loop's own records of a traced interval: its counters and its
``serve.*`` spans on the profiler's timeline.

The program's batcher counts, in ``ServeStats``, each query's launch lag
(from when its group could first ship to its launch), the loop's host time
per retired batch, and the time and routing part of each ``submit()``
call.  While ``telemetry.timeline`` is on, it also writes each stage of the
loop as a host event on the JAX profiler's clock (DESIGN.md §12).

* ``measure.snapshot`` / ``measure.delta`` carry those counters, with
  every other one ``ServeStats`` declares; a program without one reads
  None.
* ``split_idle(pd, window_ns)``: the device's idle time inside
  ``[0, window_ns]``, split by the ``serve.*`` span that the loop's thread
  was in, by exact interval overlap; None when no host line holds the
  loop's spans.  The loop's line is the one with the most of them: a loop
  that always has a group to ship never opens ``serve.wait``.
* ``per`` and ``idle_share``: the arithmetic of the metric readers
  ``launch_lag_ms.tail``, ``retire_us.tail``, ``admit_us.tail``,
  ``route_us.tail``, ``idle_wait_share.tail`` and
  ``idle_host_share.tail``.  They read ``Traced.counters`` and
  ``Traced.red["serve_idle"]``; where the run did not record them, they
  read None.
"""

from __future__ import annotations

import tracefile

WAIT = ("serve.wait",)
HOST = ("serve.stage", "serve.dispatch", "serve.fetch", "serve.scatter",
        "serve.observe")
JOIN = ("serve.join",)
PARTS = (("wait", WAIT), ("host", HOST), ("join", JOIN))


def _length(ivs) -> float:
    return sum(e - s for s, e in ivs)


def _intersect(a, b) -> list:
    """Overlap of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a, b) -> list:
    """The parts of merged, sorted interval list ``a`` outside merged,
    sorted list ``b`` (one sweep over both)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if e > s:
            out.append((s, e))
    return out


def _loop_line(host_planes):
    """The host line with the most serve-loop spans, or None."""
    names = {name for _, part in PARTS for name in part}
    best, most = None, 0
    for plane in host_planes:
        for ln in plane.lines:
            k = sum(1 for ev in ln.events if ev.name in names)
            if k > most:
                best, most = ln, k
    return best


def split_idle(pd, window_ns: float):
    """Device idle seconds (averaged over the chips) by the loop's span:
    ``wait``, ``host`` (stage, dispatch, fetch, scatter, observe) and
    ``join``, each overlap counted once in that order, and ``idle`` in
    all.  Idle time with the loop in no span is ``idle`` less the rest."""
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    line = _loop_line(host)
    if line is None:
        return None
    part_of = {name: part for part, names in PARTS for name in names}
    ivs = {part: [] for part, _ in PARTS}
    for ev in line.events:
        part = part_of.get(ev.name)
        if part is not None:
            s, e = tracefile._clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                   window_ns)
            if e > s:
                ivs[part].append((s, e))
    spans = {part: tracefile._union(v) for part, v in ivs.items()}
    out = {"idle": 0.0, "wait": 0.0, "host": 0.0, "join": 0.0}
    devices = [p for p in pd.planes
               if p.name.startswith(tracefile.DEVICE_PREFIX)]
    n = 0
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get(tracefile.OP_LINE) or lines.get(tracefile.MODULE_LINE)
        if ops is None:
            continue
        n += 1
        busy = []
        for ev in ops.events:
            s, e = tracefile._clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                   window_ns)
            if e > s:
                busy.append((s, e))
        idle = _subtract([(0.0, float(window_ns))], tracefile._union(busy))
        out["idle"] += _length(idle)
        for part, _ in PARTS:
            hit = _intersect(idle, spans[part])
            out[part] += _length(hit)
            idle = _subtract(idle, hit)
    if not n:
        return None
    return {k: v / n / 1e9 for k, v in out.items()}


def per(m, num: str, den: str, scale: float):
    """``scale`` x the change of counter ``num`` over that of ``den`` in
    the traced interval; None where either was not counted or ``den`` did
    not move."""
    t = m.traced
    c = t.counters if t is not None else {}
    a, b = c.get(num), c.get(den)
    if a is None or not b:
        return None
    return scale * a / b


def idle_share(m, part: str):
    """Share (%) of the traced interval in which the device was idle and
    the loop was in ``part``'s spans."""
    t = m.traced
    split = t.red.get("serve_idle") if t is not None else None
    if split is None or t.red["window_s"] <= 0:
        return None
    return 100.0 * split[part] / t.red["window_s"]
