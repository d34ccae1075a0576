"""The one traffic generator: a mix's parameters + a seed -> a request plan.

A traffic mix is a data file under ``traffic/`` (see ``load_mix`` in
``spec.py``); this module turns it, the configuration's map and the run's
``--seed`` into every request of the run before the first is offered.

Mix keys:

``queries_per_request``  queries in one ``submit()`` call.
``arrivals``             ``fixed`` (evenly spaced) or ``poisson``.
``endpoints``            ``uniform`` (free space) or ``cluster`` (Cluster-k:
                         each endpoint inside one of the config's k cluster
                         rectangles, the two chosen independently).
``clusters``             k, for ``cluster`` endpoints; the config's
                         ``clusters`` entry must place k rectangles.
``ramp_s``               seconds of load offered before the window opens.

The request rate is the cell's (``cells/<cell>.json``).  Copies of the
program's Unknown and Cluster-k workloads (``repro.core.workload``),
vectorised: rejection sampling in bulk instead of one point at a time.
"""

from __future__ import annotations

import dataclasses

import numpy as np


POOL = 1 << 17        # distinct queries drawn per run; requests cycle them


@dataclasses.dataclass
class Plan:
    due: np.ndarray          # [R] seconds after the ramp starts
    s: np.ndarray            # [P, 2] float32 query sources (the pool)
    t: np.ndarray            # [P, 2] float32 query targets
    per_request: int         # q

    def rows(self, i: int) -> slice:
        """Pool rows of request ``i`` (requests cycle through the pool)."""
        lo = (i * self.per_request) % len(self.s)
        return slice(lo, lo + self.per_request)

    def request(self, i: int):
        r = self.rows(i)
        return self.s[r], self.t[r]


def free_points(mp, n: int, rng, rect=None) -> np.ndarray:
    """[n, 2] points drawn uniformly from free space (inside ``rect``),
    each exactly representable in float32."""
    x0, y0, x1, y1 = rect if rect is not None else (0, 0, mp.width,
                                                      mp.height)
    out = np.zeros((0, 2))
    for _ in range(200):
        if len(out) >= n:
            break
        cand = rng.uniform([x0, y0], [x1, y1],
                           size=(max(64, 2 * (n - len(out))), 2))
        # test the float32 point the program will be given
        cand = cand.astype(np.float32).astype(np.float64)
        out = np.concatenate([out, cand[~mp.strictly_inside(cand)]])
    if len(out) < n:
        raise RuntimeError(f"rect {rect} holds too little free space")
    return out[:n]


def cluster_rects(mp, k: int, seed: int, side_frac: float = 0.10) -> list:
    """k cluster rectangles (side ``side_frac`` of the map) centred on free
    points, each holding free space: the placement of Cluster-k."""
    rng = np.random.default_rng(seed)
    sw, sh = side_frac * mp.width, side_frac * mp.height
    rects = []
    while len(rects) < k:
        c = free_points(mp, 1, rng)[0]
        x0 = min(max(c[0] - sw / 2, 0.0), mp.width - sw)
        y0 = min(max(c[1] - sh / 2, 0.0), mp.height - sh)
        rect = (x0, y0, x0 + sw, y0 + sh)
        probe = rng.uniform(rect[:2], rect[2:], size=(64, 2))
        if (~mp.strictly_inside(probe)).sum() >= 4:
            rects.append(rect)
    return rects


def cluster_points(mp, rects: list, n: int, rng) -> tuple:
    """n Cluster-k (s, t) pairs: each endpoint in a rectangle drawn
    uniformly from ``rects``, independently for s and t."""
    pick = rng.integers(0, len(rects), size=(n, 2))
    s = np.zeros((n, 2))
    t = np.zeros((n, 2))
    for r, rect in enumerate(rects):
        for col, out in ((0, s), (1, t)):
            sel = np.nonzero(pick[:, col] == r)[0]
            out[sel] = free_points(mp, len(sel), rng, rect)
    return s, t


def endpoints(mp, mix: dict, cfg: dict, n: int, rng) -> tuple:
    kind = mix["endpoints"]
    if kind == "uniform":
        p = free_points(mp, 2 * n, rng)
        return p[:n], p[n:]
    if kind == "cluster":
        cl = cfg.get("clusters") or {}
        if cl.get("k") != mix["clusters"]:
            raise ValueError(f"mix wants Cluster-{mix['clusters']} but the "
                             f"config places {cl.get('k')} clusters")
        rects = cluster_rects(mp, cl["k"], cl["seed"], cl["side_frac"])
        return cluster_points(mp, rects, n, rng)
    raise ValueError(f"unknown endpoints {kind!r}")


def make_plan(mp, mix: dict, cfg: dict, rate: float, seconds: float,
              seed: int) -> Plan:
    """Every request offered in a run: ramp plus window at ``rate``
    requests per second.  Points are rounded to float32, the precision the
    program is given; the reference answers the same rounded points."""
    rng = np.random.default_rng(seed)
    span = float(mix["ramp_s"]) + float(seconds)
    n_req = int(np.ceil(rate * span)) + 1
    if mix["arrivals"] == "fixed":
        due = np.arange(n_req) / rate
    elif mix["arrivals"] == "poisson":
        due = np.cumsum(rng.exponential(1.0 / rate, n_req))
        while due[-1] < span:       # top up a short draw
            due = np.concatenate([due, due[-1] + np.cumsum(
                rng.exponential(1.0 / rate, n_req // 8 + 1))])
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    due = due[due < span]
    q = int(mix["queries_per_request"])
    n = min(len(due), max(1, POOL // q)) * q
    s, t = endpoints(mp, mix, cfg, n, rng)
    return Plan(due=due, s=s.astype(np.float32), t=t.astype(np.float32),
                per_request=q)
