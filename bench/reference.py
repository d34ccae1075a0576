"""The plain reference: exact Euclidean shortest paths among obstacles.

Independent of the program: it reads only the map's convex pieces
(``scene.Map.pieces``, whose union is the obstacle set; pieces may
overlap).  A shortest path among polygonal obstacles bends only at convex
obstacle corners, each of which is a corner of some piece, so a query's
distance is the direct segment when it is free, else the best of
``|s-u| + D[u, v] + |v-t|`` over piece corners ``u`` seen from ``s`` and
``v`` seen from ``t``, where ``D`` is the all-pairs shortest-path matrix of
the corners' visibility graph (Floyd-Warshall).

A segment is blocked when it passes through a piece's open interior: it
may slide along an edge or graze a corner (touching is not blocked).  Each
segment is clipped against every piece's half-planes (Cyrus-Beck); the
interior is shrunk by ``INSIDE`` map units, so an exact graze stays a graze
under float64 rounding and a sliver shorter than that moves a distance by
less than the comparison can see.

``Reference(mp)`` computes every distance in float64.  The control of
``correct`` (``control.py``) is ``Reference(mp, labels="bfloat16")``: what
a bfloat16 label slab would answer, the corner-to-corner distances ``D``
stored in bfloat16 and every query-time distance and sum taken in float32,
as the program's device path takes them.  Visibility is decided in float64
in both.
"""

from __future__ import annotations

import numpy as np

INSIDE = 1e-9          # map units of interior shrink (graze tolerance)
CHUNK = 256            # queries per block (bounds the [Q, V, V] temporary)


def _bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def _f32(x):
    return np.asarray(x, np.float32).astype(np.float64)


def _lines(mp):
    """Unit-normal form of every obstacle edge, edge-major [K, P]: g(x) - c
    is the signed distance of x from the line, positive inside."""
    a, b = mp.edge_a, mp.edge_b                       # [P, K, 2]
    e = b - a
    n = np.linalg.norm(e, axis=-1)
    n = np.where(n > 0, n, 1.0)
    ex, ey = (e[..., 0] / n).T, (e[..., 1] / n).T     # [K, P]
    return ex, ey, ex * a[..., 1].T - ey * a[..., 0].T


def _g(lines, x):
    """[K, N, P] g of points x [N, 2] against every edge line."""
    ex, ey, _ = lines
    return ex[:, None] * x[None, :, 1, None] - ey[:, None] * x[None, :, 0,
                                                               None]


def _enters(f0, f1):
    """Does f0[k] + t * f1[k] > 0 hold for every edge k of some obstacle,
    for t in a sub-interval of (0, 1) of positive length?  The leading
    axis is the edge of a polygon, the trailing one the polygon."""
    lo_t = hi_t = never = None
    for a, b in zip(f0, f1):
        with np.errstate(divide="ignore", invalid="ignore"):
            r = -a / b
        lo = np.where(b > 0, r, 0.0)
        hi = np.where(b < 0, r, 1.0)
        nv = (b == 0) & (a <= 0)
        if lo_t is None:
            lo_t, hi_t, never = lo, hi, nv
        else:
            lo_t = np.maximum(lo_t, lo)
            hi_t = np.minimum(hi_t, hi)
            never = never | nv
    return ((hi_t > lo_t) & ~never).any(-1)


def blocked(mp, p: np.ndarray, q: np.ndarray, pairwise: bool = True
            ) -> np.ndarray:
    """Segment p -> q enters an obstacle's interior (shrunk by INSIDE).

    ``pairwise``: p [N, 2], q [M, 2] -> [N, M] over all pairs; else p and
    q are [N, 2] and the result [N] is for p[i] -> q[i]."""
    lines = _lines(mp)
    gp, gq = _g(lines, p), _g(lines, q)               # [K, N|M, P]
    f0 = gp - lines[2][:, None] - INSIDE
    if not pairwise:
        return _enters(f0, gq - gp)
    out = np.zeros((len(p), len(q)), bool)
    for lo in range(0, len(p), 16):
        sl = slice(lo, lo + 16)
        out[sl] = _enters(f0[:, sl, None], gq[:, None] - gp[:, sl, None])
    return out


class Reference:
    def __init__(self, mp, labels: str = "float64"):
        if labels not in ("float64", "bfloat16"):
            raise ValueError(labels)
        self.mp = mp
        # query-time rounding: none, or float32 beside bfloat16 labels
        self.r = _f32 if labels == "bfloat16" else (lambda x: x)
        v = mp.vertices
        self.v = v
        vis = ~blocked(mp, v, v)
        D = np.where(vis, np.linalg.norm(v[:, None] - v[None], axis=-1),
                     np.inf)
        np.fill_diagonal(D, 0.0)
        for k in range(len(v)):
            D = np.minimum(D, D[:, k:k + 1] + D[k:k + 1, :])
        self.D = _bf16(D) if labels == "bfloat16" else D

    def distances(self, s: np.ndarray, t: np.ndarray) -> np.ndarray:
        """[N] shortest distances for query pairs (inf: unreachable)."""
        s = np.asarray(s, np.float64)
        t = np.asarray(t, np.float64)
        r, v = self.r, self.v
        out = np.empty(len(s))
        for lo in range(0, len(s), CHUNK):
            sl = slice(lo, lo + CHUNK)
            sc, tc = s[sl], t[sl]
            direct = ~blocked(self.mp, sc, tc, pairwise=False)
            ds = np.where(~blocked(self.mp, sc, v),
                          r(np.linalg.norm(sc[:, None] - v[None], axis=-1)),
                          np.inf)                                # [n, V]
            dt = np.where(~blocked(self.mp, tc, v),
                          r(np.linalg.norm(tc[:, None] - v[None], axis=-1)),
                          np.inf)
            via = r(ds[:, :, None] + self.D[None]).min(1)       # [n, V]
            best = r(via + dt).min(1)
            dd = r(np.linalg.norm(sc - tc, axis=-1))
            out[sl] = np.where(direct, dd, best)
        return out
