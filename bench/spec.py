"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

Nothing about one configuration, mix, cell or metric is written in code:

* a configuration is the JSON file its ``configs`` entry names;
* a traffic mix is ``traffic/<traffic>.json``;
* a cell's offered rate (``rate``, requests/s) and the limit of its
  answers' widest relative gap (``max_rel_err``) are ``cells/<cell>.json``;
* a metric is ``metrics/<metric>.py``, whose ``read(m)`` returns the value
  from one run's measurements (``measure.Measurement``), or None when
  that run has nothing to read for it.

A cell reports the ``end_to_end`` metrics (``--trace 0``) or the
``per_layer`` metrics (``--trace 1``) that list it under ``workloads``,
or that have no ``workloads`` key.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    def __init__(self, workload: str, root: str = ROOT):
        bench = _json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: "
                             f"{', '.join(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = _json(os.path.join(root, conf["file"]))
        self.mix = _json(os.path.join(HERE, "traffic",
                                      self.entry["traffic"] + ".json"))
        own = _json(os.path.join(HERE, "cells", workload + ".json"))
        self.rate = float(own["rate"])
        self.limit = float(own["max_rel_err"])
        self.run_seconds = int(bench["run_seconds"])

        def mine(metrics):
            return [m for m in metrics
                    if workload in m.get("workloads", [workload])]
        self.end_to_end = mine(bench["end_to_end"])
        self.per_layer = mine(bench["per_layer"])


def reader(metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    sp = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read
