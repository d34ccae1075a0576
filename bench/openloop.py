"""Offer a plan's requests to ``PathServer.submit()`` on schedule (open loop).

Every request is due at a fixed time; it is submitted when due, or at once
when the generator runs late, and timed on this harness's clock from when
it was due to when a waiter thread sees its own ticket done.  The waiter
sleeps on the oldest pending ticket for at most ``POLL_S`` and then looks
at every pending ticket, so a ticket that finishes before an older one is
stamped within ``POLL_S`` of finishing.  Nothing here reads a timestamp
the program writes.  The waiter copies each answer out and lets the
ticket go, so the harness keeps no more of the program's objects alive
than are in flight (held tickets would grow the collector's work over the
window).
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

GRACE_S = 60.0       # how long past the window an answer may still come
POLL_S = 0.0005      # longest a finished ticket waits to be seen


class Run:
    """What one pass of ``offer`` measured, on the harness's clock."""

    def __init__(self, n: int, per_request: int):
        self.submitted = np.full(n, np.nan)   # submit() entered
        self.submit_s = np.full(n, np.nan)    # submit() call duration
        self.done = np.full(n, np.nan)        # ticket seen done
        self.answers = np.full((n, per_request), np.nan, np.float32)
        self.errors: list = []
        self.n_offered = 0


def offer(server, plan, t0: float, stop_at: float, marks=(),
          annotate=None) -> Run:
    """Submit ``plan`` requests due before ``stop_at`` (perf_counter
    seconds), due times counted from ``t0``; the generator stops at
    ``stop_at`` even where backpressure has held it behind its schedule.
    ``marks``: (time, callback) pairs that a timer thread runs once that
    time has passed (counter snapshots, profiler start and stop).
    ``annotate``: a context-manager factory wrapped round each
    ``submit()`` (a profiler annotation in traced runs).  Returns when
    every submitted ticket is done or ``GRACE_S`` past ``stop_at``."""
    n = len(plan.due)
    run = Run(n, plan.per_request)
    q: queue.Queue = queue.Queue()

    def waiter():
        deadline = stop_at + GRACE_S
        pending: dict = {}               # request index -> ticket, oldest first
        closed = False
        while True:
            while not closed:            # take what the generator submitted
                try:
                    item = q.get(block=not pending)
                except queue.Empty:
                    break
                if item is None:
                    closed = True
                else:
                    pending[item[0]] = item[1]
            if not pending:
                return
            if time.perf_counter() > deadline:
                return
            next(iter(pending.values())).wait(POLL_S)
            now = time.perf_counter()
            for i in [i for i, tk in pending.items() if tk.done]:
                run.done[i] = now
                run.answers[i] = pending.pop(i).result(0)

    def timer():
        for when, fn in sorted(marks, key=lambda m: m[0]):
            _sleep_until(when)
            fn()

    threads = [threading.Thread(target=waiter, name="bench-waiter",
                                daemon=True),
               threading.Thread(target=timer, name="bench-marks",
                                daemon=True)]
    for th in threads:
        th.start()
    due_abs = t0 + plan.due
    try:
        for i in range(n):
            due = due_abs[i]
            if due >= stop_at or time.perf_counter() >= stop_at:
                break
            _sleep_until(due)
            s, t = plan.request(i)
            a = time.perf_counter()
            try:
                if annotate is None:
                    tk = server.submit(s, t)
                else:
                    with annotate():
                        tk = server.submit(s, t)
            except Exception as e:          # refused or failed: counted
                run.errors.append((i, repr(e)))
                continue
            finally:
                run.submitted[i] = a
                run.submit_s[i] = time.perf_counter() - a
                run.n_offered = i + 1
            q.put((i, tk))
            del tk
    finally:
        q.put(None)
        for th in threads:
            th.join()
    return run


def _sleep_until(when: float) -> None:
    left = when - time.perf_counter()
    if left > 0:
        time.sleep(left)
