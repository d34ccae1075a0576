"""Maps as the benchmark makes them: obstacle polygons from a config's seed.

A configuration names a map generator (``map``) and its parameters (top
level keys of the configuration); the obstacles are drawn here, from the
configuration's own seed.  Each map comes in two descriptions of the same
obstacle set:

* ``polygons``: simple polygons (a wall junction is one non-convex polygon),
  handed to the program as its input (``repro.core.geometry.Scene.build``);
* ``pieces``: convex polygons whose union is the same set (a junction is
  two overlapping bars), read by the plain reference (``reference.py``) and
  the traffic generator, so neither reads geometry back from the program.

``room_lattice`` follows the MovingAI room maps (Sturtevant, "Benchmarks
for Grid-Based Pathfinding", IEEE TCIAIG 2012; movingai.com/benchmarks):
square rooms of ``room_cells`` grid cells in a ``rooms_per_side`` square
lattice, separated by walls ``wall_cells`` thick, one door of
``door_cells`` in every wall between two neighbouring rooms.  The map's
outer edge is the scene box.  A wall that meets the edge runs on past it
for a whole map side (solid ground), so no path slips round its end along
the edge, and the program's polygon carries a vertex where it crosses the
edge.
"""

from __future__ import annotations

import numpy as np

MAP_KEYS = ("map", "map_seed", "room_cells", "rooms_per_side", "wall_cells",
            "door_cells")


def _rect(x0, y0, x1, y1):
    return np.array([[x0, y0], [x1, y0], [x1, y1], [x0, y1]], np.float64)


def _plus(hx0, hx1, vy0, vy1, a, b, c, d):
    """Outline (CCW) of a horizontal bar [hx0, hx1] x [c, d] crossed by a
    vertical bar [a, b] x [vy0, vy1], every arm of positive length."""
    return np.array([[a, vy0], [b, vy0], [b, c], [hx1, c], [hx1, d], [b, d],
                     [b, vy1], [a, vy1], [a, d], [hx0, d], [hx0, c], [a, c]],
                    np.float64)


def _cut(poly, side):
    """``poly`` (a rectangle crossing the box edge) with a vertex added
    where each of its edges crosses a box side (x or y = 0 or ``side``)."""
    out = []
    for i, a in enumerate(poly):
        b = poly[(i + 1) % len(poly)]
        out.append(a)
        for ax in (0, 1):
            for lim in (0.0, side):
                if (a[ax] - lim) * (b[ax] - lim) < 0:
                    q = a + (lim - a[ax]) / (b[ax] - a[ax]) * (b - a)
                    q[ax] = lim
                    out.append(q)
    return np.array(out, np.float64)


def room_lattice(cfg: dict):
    """(polygons, pieces, side) of a room-lattice map."""
    n, R = int(cfg["rooms_per_side"]), float(cfg["room_cells"])
    w, D = float(cfg["wall_cells"]), float(cfg["door_cells"])
    side = n * R
    ext = side                       # how far an edge wall runs past the box
    rng = np.random.default_rng(cfg["map_seed"])
    lo = [None] + [k * R - w / 2 for k in range(1, n)]    # wall line k spans
    hi = [None] + [k * R + w / 2 for k in range(1, n)]    # [lo[k], hi[k]]

    def doors():
        """[line k - 1, segment j] door start along each wall line: the
        door keeps at least a wall's thickness of wall on either side."""
        out = np.zeros((n - 1, n))
        for k in range(n - 1):
            for j in range(n):
                a = (hi[j] if j else 0.0) + w
                b = (lo[j + 1] if j < n - 1 else side) - w - D
                out[k, j] = rng.uniform(a, b)
        return out

    dv, dh = doors(), doors()        # vertical lines, then horizontal
    polygons, pieces = [], []
    # junction (k, l): vertical line k crosses horizontal line l
    for k in range(1, n):
        for l in range(1, n):
            hx0, hx1 = dh[l - 1, k - 1] + D, dh[l - 1, k]
            vy0, vy1 = dv[k - 1, l - 1] + D, dv[k - 1, l]
            polygons.append(_plus(hx0, hx1, vy0, vy1, lo[k], hi[k], lo[l],
                                  hi[l]))
            pieces += [_rect(hx0, lo[l], hx1, hi[l]),
                       _rect(lo[k], vy0, hi[k], vy1)]
    # walls that meet the edge: from past the edge to the door
    for k in range(1, n):
        for rect in (_rect(lo[k], -ext, hi[k], dv[k - 1, 0]),
                     _rect(lo[k], dv[k - 1, n - 1] + D, hi[k], side + ext),
                     _rect(-ext, lo[k], dh[k - 1, 0], hi[k]),
                     _rect(dh[k - 1, n - 1] + D, lo[k], side + ext, hi[k])):
            polygons.append(_cut(rect, side))
            pieces.append(rect)
    return polygons, pieces, side


GENERATORS = {"room_lattice": room_lattice}


class Map:
    """Obstacles inside a ``width`` x ``height`` box: ``polygons`` (the
    program's input) and ``pieces`` (convex CCW, the same union)."""

    def __init__(self, polygons, pieces, width: float, height: float):
        self.polygons = [np.asarray(p, np.float64) for p in polygons]
        self.pieces = [np.asarray(p, np.float64) for p in pieces]
        self.width = float(width)
        self.height = float(height)
        self.n_edges = sum(len(p) for p in self.polygons)
        k = max(len(p) for p in self.pieces)
        # [P, K, 2] edge starts/ends of the pieces, short pieces padded with
        # a repeated last edge (a repeated half-plane changes nothing)
        a = np.stack([np.concatenate([p, np.repeat(p[-1:], k - len(p), 0)])
                      for p in self.pieces])
        b = np.stack([np.concatenate([np.roll(p, -1, 0),
                                      np.repeat(p[:1], k - len(p), 0)])
                      for p in self.pieces])
        self.edge_a, self.edge_b = a, b
        self.vertices = np.concatenate(self.pieces)

    def strictly_inside(self, pts: np.ndarray) -> np.ndarray:
        """[N] bool: the point lies in the open interior of an obstacle."""
        pts = np.asarray(pts, np.float64)
        e = self.edge_b - self.edge_a                      # [P, K, 2]
        ex, ey = e[..., 0].T, e[..., 1].T                  # [K, P]
        ax, ay = self.edge_a[..., 0].T, self.edge_a[..., 1].T
        c = ex * ay - ey * ax
        out = np.zeros(len(pts), bool)
        for lo in range(0, len(pts), 8192):
            x = pts[lo:lo + 8192, 0, None]
            y = pts[lo:lo + 8192, 1, None]
            inside = None
            for k in range(len(ex)):       # edge k of every piece
                f = ex[k] * y - ey[k] * x > c[k]
                inside = f if inside is None else inside & f
            out[lo:lo + 8192] = inside.any(-1)
        return out


def map_spec(cfg: dict) -> dict:
    """The keys of a configuration that define its map."""
    return {k: cfg[k] for k in MAP_KEYS}


def make_map(cfg: dict) -> Map:
    """The map a configuration describes."""
    polygons, pieces, side = GENERATORS[cfg["map"]](cfg)
    return Map(polygons, pieces, side, side)
