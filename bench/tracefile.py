"""Reduce a profiler trace (``.xplane.pb``) to device time and idle share.

The JAX profiler writes one XSpace per traced window.  Each chip is a plane
named ``/device:TPU:<n>``; on it the line ``XLA Modules`` holds one event
per program execution (named after the jitted function, e.g.
``jit__fold_endpoint(17)``) and ``XLA Ops`` one event per operation.  Host
threads are lines of the plane ``/host:CPU``.

``reduce(path, window_ns, entries)`` gives, averaged over the chips seen:

* ``busy_s``: the union of the intervals in which an operation ran;
* ``entry_s[name]``: device time of the programs of each jit entry (a
  module whose name contains ``_<name>`` or ``<name>``);
* ``ops``: device time per operation (the heaviest ones), named
  ``<module>/<HLO instruction>``;
* ``gaps``: idle time by what the host was doing (the innermost host event
  at the middle of each idle gap).

Times outside ``[0, window_ns]`` of the trace's own clock are cut off.
"""

from __future__ import annotations

import bisect
import collections
import gzip
import heapq

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, w):
    return max(0.0, s), min(float(w), e)


def load(path: str):
    """A trace file, raw or gzip-compressed (``.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce(pd, window_ns: float, entries=()) -> dict:
    """Device busy/idle, per-entry and per-op device time from a trace."""
    devices = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    busy_ns = 0.0
    entry_ns = collections.Counter()
    op_ns = collections.Counter()
    gaps_all = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get(OP_LINE) or lines.get(MODULE_LINE)
        if ops is None:
            continue
        mods = lines.get(MODULE_LINE)
        mod_evs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                          ev.name) for ev in (mods.events if mods else ()))
        mod_starts = [m[0] for m in mod_evs]
        ivs = []
        for ev in ops.events:
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, window_ns)
            if e > s:
                ivs.append((s, e))
                op_ns[_op_name(ev, mod_evs, mod_starts)] += e - s
        merged = _union(ivs)
        busy_ns += sum(e - s for s, e in merged)
        prev = 0.0
        for s, e in merged + [[float(window_ns), float(window_ns)]]:
            if s > prev:
                gaps_all.append((prev, s))
            prev = max(prev, e)
        for ev in (mods.events if mods is not None else ()):
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, window_ns)
            if e <= s:
                continue
            for name in entries:
                if name in ev.name:
                    entry_ns[name] += e - s
                    break
    n = max(1, len(devices))
    gaps = _label_gaps(gaps_all, host)
    return {
        "devices": len(devices),
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "entry_s": {k: v / n / 1e9 for k, v in entry_ns.items()},
        "ops": [(k, v / n / 1e9) for k, v in op_ns.most_common(10)],
        "gaps": [(k, v / n / 1e9) for k, v in gaps.most_common(10)],
    }


def _op_name(ev, mod_evs, mod_starts) -> str:
    """``<module>/<instruction>``: the HLO instruction (the event's text
    before `` = ``) inside the program execution that holds it."""
    op = ev.name.split(" = ")[0]
    k = bisect.bisect_right(mod_starts, ev.start_ns) - 1
    if k >= 0 and mod_evs[k][1] >= ev.start_ns:
        return f"{mod_evs[k][2]}/{op}"
    return op


def _label_gaps(gaps, host_planes) -> collections.Counter:
    """Idle nanoseconds by the innermost host event at each gap's middle
    (a sweep over gap middles in time order, shortest open event on top)."""
    events = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for plane in host_planes for ln in plane.lines
                    for ev in ln.events if ev.duration_ns > 0)
    out = collections.Counter()
    heap, k = [], 0
    for g0, g1 in sorted(gaps):
        mid = 0.5 * (g0 + g1)
        while k < len(events) and events[k][0] <= mid:
            s, e, name = events[k]
            heapq.heappush(heap, (e - s, e, name))
            k += 1
        while heap and heap[0][1] < mid:
            heapq.heappop(heap)
        out[heap[0][2] if heap else "no host event"] += g1 - g0
    return out
