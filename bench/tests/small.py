"""A cell small enough for the CPU: the harness's own code at a test size.

``SmallCell(mix)`` stands in for ``spec.Cell``: a room lattice of 3 x 3
rooms 16 cells on a side, a 0.3 budget, float32 slabs, the named traffic
mix at a low rate (with ``path_share`` set, where given).
The tests run ``run.run_cell`` on it with the chip check skipped.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL_MAP = {"map": "room_lattice", "map_seed": 0, "room_cells": 16,
             "rooms_per_side": 3, "wall_cells": 1, "door_cells": 2}


class SmallCell:
    def __init__(self, mix: str, scores: str = "uniform", rate=None,
                 limit: float = 5e-5, path_share=None):
        with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
            self.mix = json.load(f)
        self.mix["ramp_s"] = 0.5
        if path_share is not None:
            self.mix["path_share"] = path_share
        self.name = f"small.{mix}"
        self.chips = 1
        self.config = {
            "name": f"small-{scores}", **SMALL_MAP, "cell_size": 2.0,
            "budget_fraction": 0.3, "scores": scores,
            "slab_dtype": "float32",
            "clusters": {"k": 4, "seed": 7, "side_frac": 0.1},
            "history": {"queries": 200, "seed": 8, "alpha": 0.2}}
        self.rate = rate if rate is not None else (
            20.0 if self.mix["queries_per_request"] > 1 else 400.0)
        self.limit = limit
        self.end_to_end = [{"name": n, "unit": u} for n, u in (
            ("p50_ms", "ms"), ("setup_s", "s"))]
        self.per_layer = []
