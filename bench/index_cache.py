"""Build a configuration's EHL* index, or load it from the checkout's cache.

The host build (visibility polygons of every vertex over every grid cell)
takes minutes, so the first run of a configuration in a checkout builds it
and later runs load it.  Two files under ``.cache/`` (ignored by git):

* ``full-<key>.npz``: the uncompressed index of a map and cell size,
  shared by every configuration over that map;
* ``<config>-<key>.npz``: the index after the configuration's budget.

A key hashes the configuration's inputs and the program's ``core``
sources, so a change to either builds afresh.  The format is this
harness's own: one member per array, each read once on load (the
program's ``checkpoint.store.load_ehl_index`` re-reads a member for every
region, which takes most of a minute at this size).
"""

from __future__ import annotations

import glob
import hashlib
import json
import os

import numpy as np

import scene as maps
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE = os.path.join(HERE, ".cache")


def _key(*parts, src: str) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(json.dumps(p, sort_keys=True).encode())
    for path in sorted(glob.glob(os.path.join(src, "repro", "core", "*.py"))):
        with open(path, "rb") as f:
            h.update(os.path.basename(path).encode() + f.read())
    return h.hexdigest()[:16]


def save(path: str, index) -> None:
    live = sorted(index.regions)
    regs = [index.regions[r] for r in live]

    def flat(get):
        parts = [np.asarray(get(r), np.int64) for r in regs]
        off = np.cumsum([0] + [len(p) for p in parts])
        return np.concatenate(parts), off

    cells, cells_off = flat(lambda r: r.cells)
    keys, keys_off = flat(lambda r: r.keys)
    hubs, hubs_off = flat(lambda r: r.hubs)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, cell_size=np.float64(index.cell_size),
                 nx=np.int64(index.nx), ny=np.int64(index.ny),
                 mapper=np.asarray(index.mapper, np.int64),
                 rids=np.asarray(live, np.int64),
                 scores=np.asarray([r.score for r in regs], np.float64),
                 cells=cells, cells_off=cells_off, keys=keys,
                 keys_off=keys_off, hubs=hubs, hubs_off=hubs_off)
    os.replace(tmp, path)


def load(path: str, scene, graph, hl):
    from repro.core import EHLIndex, Region

    with np.load(path) as z:
        a = {k: z[k] for k in z.files}          # each member read once
    co, ko, ho = a["cells_off"], a["keys_off"], a["hubs_off"]
    cells, keys, hubs = a["cells"], a["keys"], a["hubs"]
    regions = {}
    for i, rid in enumerate(a["rids"].tolist()):
        regions[rid] = Region(rid=rid, cells=cells[co[i]:co[i + 1]].tolist(),
                              keys=keys[ko[i]:ko[i + 1]],
                              hubs=hubs[ho[i]:ho[i + 1]],
                              score=float(a["scores"][i]))
    return EHLIndex(scene=scene, graph=graph, hl=hl,
                    cell_size=float(a["cell_size"]), nx=int(a["nx"]),
                    ny=int(a["ny"]), mapper=a["mapper"].copy(),
                    regions=regions)


def history_scores(index, mp, cfg: dict):
    """Workload-aware cell scores s(c) = 1 + w_c from the configuration's
    Cluster-k history (EHL* §Compression)."""
    from repro.core import QuerySet, workload_scores

    cl, hist = cfg["clusters"], cfg["history"]
    rects = traffic.cluster_rects(mp, cl["k"], cl["seed"], cl["side_frac"])
    s, t = traffic.cluster_points(mp, rects, hist["queries"],
                                  np.random.default_rng(hist["seed"]))
    return workload_scores(index, QuerySet("history", s, t))


def build_or_load(cfg: dict, mp, src: str, log=print, cache: str = CACHE):
    """The configuration's host ``EHLIndex`` and how it was obtained."""
    from repro.core import (Scene, build_ehl, build_hub_labels,
                            build_visgraph, compress)

    scene = Scene.build(mp.polygons, mp.width, mp.height)
    graph = build_visgraph(scene)
    hl = build_hub_labels(graph)
    full_key = _key(maps.map_spec(cfg), cfg["cell_size"], src=src)
    cfg_key = _key(cfg, full_key, src=src)
    path = os.path.join(cache, f"{cfg['name']}-{cfg_key}.npz")
    if os.path.exists(path):
        return load(path, scene, graph, hl), "loaded"
    full_path = os.path.join(cache, f"full-{full_key}.npz")
    if os.path.exists(full_path):
        index, how = load(full_path, scene, graph, hl), "compressed"
    else:
        index = build_ehl(scene, cell_size=cfg["cell_size"], graph=graph,
                          hl=hl)
        save(full_path, index)
        how = "built"
    log(f"index: {len(index.regions)} regions, "
        f"{index.label_memory() / 1e6:.1f} MB of labels before the budget")
    budget = int(index.label_memory() * cfg["budget_fraction"])
    if cfg["scores"] == "uniform":
        compress(index, budget)
    elif cfg["scores"] == "history":
        compress(index, budget, cell_scores=history_scores(index, mp, cfg),
                 alpha=cfg["history"]["alpha"])
    else:
        raise ValueError(f"unknown scores {cfg['scores']!r}")
    save(path, index)
    return index, how
