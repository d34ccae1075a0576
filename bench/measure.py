"""One run's measurements, as the metric readers (``metrics/*.py``) see them.

Host-clock numbers come from ``openloop.Run``; counters are read from the
program's ``ServeStats`` at the window's marks; device numbers come from
the profiler trace of a ``--trace 1`` run (``tracefile.reduce``).
"""

from __future__ import annotations

import gc
import time

import numpy as np


def snapshot(server) -> dict:
    """The program's serving counters at one instant."""
    from repro.core.packed import TRACES

    st = server.stats
    per_width: dict = {}
    for b in list(st.per_bucket.values()):
        per_width[b.width] = per_width.get(b.width, 0) + b.batches
    return {"queries": st.queries, "batches": st.batches,
            "jit_traces": TRACES.count, "batches_by_width": per_width}


def delta(a: dict, b: dict) -> dict:
    widths = set(a["batches_by_width"]) | set(b["batches_by_width"])
    return {"queries": b["queries"] - a["queries"],
            "batches": b["batches"] - a["batches"],
            "jit_traces": b["jit_traces"] - a["jit_traces"],
            "batches_by_width": {
                w: b["batches_by_width"].get(w, 0)
                - a["batches_by_width"].get(w, 0) for w in widths}}


class GcWatch:
    """The process's cyclic garbage collections on the harness's clock,
    as (start, seconds, generation).  Every Python thread stops while one
    runs: the server's loop, its admission and the traffic generator."""

    def __init__(self):
        self.events: list = []
        self._start = None

    def __enter__(self):
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._note)

    def _note(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif self._start is not None:
            self.events.append((self._start, now - self._start,
                                info["generation"]))
            self._start = None

    def between(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] < t1]


class Traced:
    """The traced interval of a ``--trace 1`` run."""

    def __init__(self, t0, t1, counters, submit_s, answered, spans,
                 reduction, batch_size, edges, peaks, gc_events):
        self.seconds = t1 - t0
        self.counters = counters          # delta() over the interval
        self.submit_s = submit_s          # submit() durations in it
        self.answered = answered          # queries seen done in it
        self.spans = spans                # program spans begun in it
        self.red = reduction              # tracefile.reduce()
        self.batch_size = batch_size
        self.edges = edges                # padded edge count
        self.peaks = peaks                # work.peaks(device kind)
        self.gc_events = gc_events        # GcWatch events in it


class Measurement:
    def __init__(self, seconds, setup_s, window_queries, lat_ms,
                 traced=None):
        self.seconds = seconds            # the measured window
        self.setup_s = setup_s
        self.window_queries = window_queries  # answered inside the window
        self.lat_ms = np.asarray(lat_ms, float)   # requests due in it
        self.traced = traced
