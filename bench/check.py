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

Where the mix asks for routes, the sampled queries of path requests are
held to two more numbers:

* ``path_rel_err``: the widest |float64 length of the polyline -
  reference| / max(reference, 1) (infinite where reachability differs:
  an empty polyline against a finite reference, or the reverse); its
  limit is the cell's ``max_rel_err``;
* ``bad_paths``: polylines that do not start at the exact float32 ``s``
  or end at the exact ``t`` given, or that have a leg
  ``reference.blocked`` says enters an obstacle; limit 0.
"""

from __future__ import annotations

import numpy as np

import reference

SAMPLE = 4096
LONGEST = 64


def sample(plan, run, in_window, seed: int):
    """(s, t, answers, routes) of the sampled queries of answered
    requests; ``routes`` holds each sampled query's polyline, None for a
    query of a distance request."""
    idx = np.nonzero(in_window & ~np.isnan(run.done))[0]
    if not len(idx):
        return np.zeros((0, 2)), np.zeros((0, 2)), np.zeros(0), []
    rows = np.concatenate([np.arange(plan.rows(int(i)).start,
                                     plan.rows(int(i)).stop) for i in idx])
    answers = run.answers[idx].astype(np.float64).ravel()
    rng = np.random.default_rng([seed, 0x5A3])
    pick = rng.choice(len(rows), size=min(SAMPLE, len(rows)), replace=False)
    fin = np.where(np.isfinite(answers), answers, -1.0)
    pick = np.unique(np.concatenate([pick, np.argsort(-fin)[:LONGEST]]))
    r = rows[pick]
    q = plan.per_request
    routes = [run.paths[int(idx[k // q])][k % q]
              if int(idx[k // q]) in run.paths else None for k in pick]
    return plan.s[r], plan.t[r], answers[pick], routes


def max_rel_err(got: np.ndarray, truth: np.ndarray) -> float:
    if not len(got):
        return float("inf")
    fin = np.isfinite(truth)
    if not np.array_equal(fin, np.isfinite(got)):
        return float("inf")
    err = np.abs(got[fin] - truth[fin]) / np.maximum(truth[fin], 1.0)
    return float(err.max(initial=0.0))


def path_rel_err(routes: list, truth: np.ndarray) -> float:
    """Widest gap of a polyline's float64 length from the reference."""
    if not len(routes):
        return float("inf")
    lengths = np.array([np.linalg.norm(np.diff(p, axis=0), axis=-1).sum()
                        if len(p) else np.inf for p in routes])
    return max_rel_err(lengths, truth)


def bad_paths(mp, routes: list, s: np.ndarray, t: np.ndarray) -> int:
    """Polylines off their given endpoints, or with a leg through an
    obstacle (``reference.blocked``); an empty polyline has no leg, and
    ``path_rel_err`` judges its reachability."""
    bad = np.zeros(len(routes), bool)
    legs_p, legs_q, owner = [], [], []
    for k, p in enumerate(routes):
        if not len(p):
            continue
        bad[k] = not (np.array_equal(p[0], s[k].astype(np.float64))
                      and np.array_equal(p[-1], t[k].astype(np.float64)))
        legs_p.append(p[:-1])
        legs_q.append(p[1:])
        owner.append(np.full(len(p) - 1, k))
    if owner:
        hit = reference.blocked(mp, np.concatenate(legs_p),
                                np.concatenate(legs_q), pairwise=False)
        bad[np.concatenate(owner)[hit]] = True
    return int(bad.sum())


def compare(ref, s, t, answers, unanswered: int, limit: float,
            routes=None) -> dict:
    """The compared numbers; with ``routes`` (``sample``'s, for a mix that
    asks for routes) ``path_rel_err`` and ``bad_paths`` too."""
    truth = ref.distances(s.astype(np.float64), t.astype(np.float64))
    out = {"max_rel_err": {"value": max_rel_err(answers, truth),
                           "limit": limit},
           "unanswered": {"value": int(unanswered), "limit": 0}}
    if routes is not None:
        k = np.array([r is not None for r in routes], bool)
        got = [r for r in routes if r is not None]
        out["path_rel_err"] = {"value": path_rel_err(got, truth[k]),
                               "limit": limit}
        out["bad_paths"] = {"value": bad_paths(ref.mp, got, s[k], t[k]),
                            "limit": 0}
    return out


def passed(compared: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in compared.values())
