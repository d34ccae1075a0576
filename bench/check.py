"""Decide ``correct``: the timed path's answers against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the queries answered for requests offered in the window (``SAMPLE`` drawn
from the seed, plus the ``LONGEST`` with the largest answers) is answered
again by ``reference.Reference`` in float64, on the same float32 points
the program was given.  Two numbers are compared, each with its limit:

* ``max_rel_err``: the widest gap |program - reference| / max(reference,
  1) over the sample (infinite where reachability differs); its limit is
  the cell's (``cells/<cell>.json``);
* ``unanswered``: requests offered in the window whose answer never came,
  or whose ``submit()`` failed; limit 0.
"""

from __future__ import annotations

import numpy as np

SAMPLE = 4096
LONGEST = 64


def sample(plan, run, in_window, seed: int):
    """(s, t, answers) of the sampled queries of answered requests."""
    idx = np.nonzero(in_window & ~np.isnan(run.done))[0]
    if not len(idx):
        return np.zeros((0, 2)), np.zeros((0, 2)), np.zeros(0)
    rows = np.concatenate([np.arange(plan.rows(int(i)).start,
                                     plan.rows(int(i)).stop) for i in idx])
    answers = run.answers[idx].astype(np.float64).ravel()
    rng = np.random.default_rng([seed, 0x5A3])
    pick = rng.choice(len(rows), size=min(SAMPLE, len(rows)), replace=False)
    fin = np.where(np.isfinite(answers), answers, -1.0)
    pick = np.unique(np.concatenate([pick, np.argsort(-fin)[:LONGEST]]))
    r = rows[pick]
    return plan.s[r], plan.t[r], answers[pick]


def max_rel_err(got: np.ndarray, truth: np.ndarray) -> float:
    if not len(got):
        return float("inf")
    fin = np.isfinite(truth)
    if not np.array_equal(fin, np.isfinite(got)):
        return float("inf")
    err = np.abs(got[fin] - truth[fin]) / np.maximum(truth[fin], 1.0)
    return float(err.max(initial=0.0))


def compare(ref, s, t, answers, unanswered: int, limit: float) -> dict:
    truth = ref.distances(s.astype(np.float64), t.astype(np.float64))
    return {"max_rel_err": {"value": max_rel_err(answers, truth),
                            "limit": limit},
            "unanswered": {"value": int(unanswered), "limit": 0}}


def passed(compared: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in compared.values())
