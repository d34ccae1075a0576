#!/usr/bin/env python3
"""Chip smoke: drive the EHL* serving path once on a TPU and check answers.

    python3 chip_smoke.py              # one chip: jnp + pallas engines
    python3 chip_smoke.py --chips 4    # four chips: the region-sharded engine

Both modes build the ``rooms-L`` map (seed 0) from scratch through the
library's normal entry points — ``build_visgraph`` -> ``build_ehl`` ->
``compress_to_fraction`` -> ``pack_bucketed`` — then answer a set of
uniform queries through ``PathServer`` and hold every answer to the
float64 host oracle (``repro.core.query``).

One chip: the jnp engine (synchronous ``query()``, then ``submit()`` on
the continuous-batching loop, then ``query_paths``), and the pallas engine
with its kernels compiled by Mosaic at every bucket width of the index.
Four chips: the index planned into 4 region shards on a 4-device serving
mesh, one shard per chip, against the single-chip jnp engine and the
oracle in the same process.

The run fails (non-zero exit, no result line) when JAX finds no TPU, when
there are fewer TPU devices than ``--chips``, when any phase raises, or
when any check fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Every timing
printed here is set-up bookkeeping, not a performance result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

MAP = "rooms-L"        # the largest map suite the repo builds
SEED = 0
BUDGET = 0.3           # one of the budget fractions the paper sweeps
CELL = 2.0
N_QUERIES = 512
QUERY_SEED = 33
BATCH = 256
N_PATHS = 32
SHARDS = 4

# Answers vs the float64 oracle, relative and absolute (the conformance
# suite's HOST_TOL): the device evaluates Eq. 1-3 in f32 on f32-rounded
# query points.  Rounding a point on a 180-unit map moves it by <= 1.1e-5,
# and the f32 sum of the four path terms drifts by <= ~8 eps * d (~1e-6
# relative); 1e-4 leaves an order of magnitude of headroom over both.
ORACLE_TOL = 1e-4


def check_devices(devices, chips: int):
    """The devices to run on, or SystemExit when the runtime cannot hold
    the run: the platform must be ``tpu`` and there must be ``chips`` of
    them.  Never falls back to the CPU or stacks work on fewer chips."""
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform is "
                         f"{platform!r}); refusing to run off the chip")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, the runtime has {len(devices)}")
    return list(devices[:chips])


def within_oracle(d, truth) -> tuple[bool, bool, float]:
    """(same reachability, all within ORACLE_TOL, max relative error)."""
    fin = np.isfinite(truth)
    same_reach = bool(np.array_equal(fin, np.isfinite(d)))
    err = np.abs(d[fin].astype(np.float64) - truth[fin])
    ok = bool(np.all(err <= ORACLE_TOL * (1.0 + np.abs(truth[fin]))))
    rel = float((err / np.maximum(np.abs(truth[fin]), 1.0)).max(initial=0))
    return same_reach, ok, rel


def bitwise_diff(a, b) -> tuple[int, float]:
    """(answers not bit-identical, max abs difference among finite)."""
    n = int(np.sum(a.view(np.uint32) != b.view(np.uint32)))
    fin = np.isfinite(a) & np.isfinite(b)
    return n, float(np.abs(a[fin] - b[fin]).max(initial=0.0))


class Smoke:
    """Phase runner: records which phases ran and every failed check."""

    def __init__(self):
        self.phases: list[str] = []
        self.failures: list[str] = []

    def phase(self, name: str) -> None:
        self.phases.append(name)
        print(f"== phase {name}", flush=True)

    def check(self, ok: bool, what: str) -> None:
        print(f"   check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failures.append(what)

    def oracle(self, label: str, d, truth) -> None:
        same, ok, rel = within_oracle(d, truth)
        self.check(same, f"{label}: reachability identical to the oracle")
        self.check(ok, f"{label}: distances within {ORACLE_TOL:g} of the "
                       f"oracle (max rel err {rel:.3e})")


def build(smoke: Smoke):
    """rooms-L from the seed through the library's build entry points."""
    from repro.core import (build_ehl, build_visgraph, compress_to_fraction,
                            make_map, pack_bucketed, uniform_queries)
    from repro.core.query import query as host_query

    smoke.phase("build")
    t0 = time.perf_counter()
    scene = make_map(MAP, seed=SEED)
    graph = build_visgraph(scene)
    index = build_ehl(scene, cell_size=CELL, graph=graph)
    t1 = time.perf_counter()
    full_regions = len(index.regions)
    stats = compress_to_fraction(index, BUDGET)
    t2 = time.perf_counter()
    bx = pack_bucketed(index)
    t3 = time.perf_counter()
    print(f"   {MAP} seed={SEED}: host build {t1 - t0:.1f}s "
          f"({full_regions} regions), compress to {BUDGET:g} "
          f"{t2 - t1:.1f}s ({len(index.regions)} regions, "
          f"{stats.final_bytes / 1e6:.1f} MB labels), pack_bucketed "
          f"{t3 - t2:.1f}s")
    print(f"   bucketed artifact: {bx.device_bytes() / 1e6:.1f} MB on "
          f"device, widths {tuple(bx.widths)}")
    for k, w in enumerate(bx.widths):
        nbytes = sum(group[k].nbytes for group in
                     (bx.hub_ids, bx.via_xy, bx.via_d, bx.via_ids,
                      bx.hub_base, bx.vid_base) if len(group) > k)
        print(f"     bucket {k}: width {w:5d} rows {bx.hub_ids[k].shape[0]:5d}"
              f" {nbytes / 1e6:8.2f} MB")

    smoke.phase("oracle")
    qs = uniform_queries(scene, graph, N_QUERIES, seed=QUERY_SEED,
                         require_path=False)
    s = qs.s.astype(np.float32)
    t = qs.t.astype(np.float32)
    # the oracle answers the same f32-rounded points the device sees
    truth = np.array([host_query(index, si, ti, want_path=False)[0]
                      for si, ti in zip(s.astype(np.float64),
                                        t.astype(np.float64))])
    print(f"   {N_QUERIES} uniform queries, {int(np.isfinite(truth).sum())}"
          f" reachable by the float64 oracle")
    return index, bx, s, t, truth


def warm(server, label: str, paths: bool) -> None:
    """Compile every jit entry the server can hit; print the seconds the
    entries spent tracing and compiling (or loading from the cache)."""
    from repro import obs

    with obs.profiled(costs=False) as cap:
        server.warmup(paths=paths)
    summary = cap.summary()
    n = sum(v["compiles"] for v in summary.values())
    secs = sum(v["compile_s"] for v in summary.values())
    print(f"   {label}: {n} jit entries compiled in {secs:.2f}s")


def assert_compiled(smoke: Smoke, bx, batch: int) -> None:
    """Every bucket's served fold and join entries lower to Mosaic
    ``tpu_custom_call``s — the kernels are compiled, not interpreted."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.core.packed import _fold_endpoint, _join_endpoints

    pts = jnp.zeros((2, batch, 2), jnp.float32)     # both endpoint sides
    for k, w in enumerate(bx.widths):
        fold = _fold_endpoint.jit.lower(bx, pts, bucket=k, use_kernels=True)
        ms, mt = jax.eval_shape(functools.partial(
            _fold_endpoint.jit, bucket=k, use_kernels=True), bx, pts)
        join = _join_endpoints.jit.lower(bx, ms, mt, pts, use_kernels=True)
        ok = all("tpu_custom_call" in low.as_text() for low in (fold, join))
        smoke.check(ok, f"pallas bucket {k} (width {w}): fold and join "
                        "lower to tpu_custom_call")


def kernels_vs_refs(smoke: Smoke, widths) -> None:
    """The compiled kernels against their jnp references, bit for bit, at
    the serving batch and every bucket width, on seeded random inputs."""
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = np.random.default_rng(SEED)
    for w in widths:
        hub = [jnp.asarray(np.sort(rng.integers(0, 4 * w, (BATCH, w)), 1),
                           jnp.int32) for _ in range(2)]
        vd = [rng.uniform(0, 300, (BATCH, w)).astype(np.float32)
              for _ in range(2)]
        for v in vd:
            v[rng.random(v.shape) < 0.2] = np.inf
        args = (hub[0], jnp.asarray(vd[0]), hub[1], jnp.asarray(vd[1]))
        n, _ = bitwise_diff(np.asarray(ops.label_join_rowmin_ref(*args)),
                            np.asarray(ops.label_join_rowmin_kernel(*args)))
        smoke.check(n == 0, f"label_join_rowmin {BATCH}x{w}: bit-identical "
                            f"to the jnp reference ({n} differ)")
        p, q = (jnp.asarray(rng.uniform(0, 180, (BATCH * w // 8, 2)),
                            jnp.float32) for _ in range(2))
        e = [jnp.asarray(rng.uniform(0, 180, (256, 2)), jnp.float32)
             for _ in range(3)]
        n = int(np.sum(np.asarray(ops.segvis_ref(p, q, *e))
                       != np.asarray(ops.segvis_kernel(p, q, *e))))
        smoke.check(n == 0, f"segvis {BATCH * w // 8}x256: verdicts "
                            f"identical to the jnp reference ({n} differ)")


def single_chip(smoke: Smoke, index, bx, s, t, truth) -> None:
    from repro.core import path_length
    from repro.serving import PathServer, make_engine

    smoke.phase("jnp-sync")
    srv = PathServer(make_engine(bx, backend="jnp"), batch_size=BATCH)
    warm(srv, "jnp", paths=True)
    d_jnp = srv.query(s, t)
    smoke.oracle("jnp query()", d_jnp, truth)

    smoke.phase("jnp-submit")
    srv.start_async()
    try:
        d_async = srv.submit(s, t).result(timeout=600)
    finally:
        srv.stop_async()
    n, _ = bitwise_diff(d_jnp, np.asarray(d_async, np.float32))
    smoke.check(n == 0, f"jnp submit(): bit-identical to query() "
                        f"({n} differ)")

    smoke.phase("jnp-paths")
    dp, paths = srv.query_paths(s[:N_PATHS], t[:N_PATHS], host_index=index)
    fin = np.isfinite(dp)
    gap = max((abs(path_length(p) - float(d))
               for d, p, f in zip(dp, paths, fin) if f), default=0.0)
    bound = ORACLE_TOL * (1.0 + float(np.abs(dp[fin]).max(initial=0)))
    smoke.check(gap <= bound, f"{N_PATHS} paths: max |len(path) - d| = "
                              f"{gap:.3e} <= {bound:.3e}")
    smoke.oracle("jnp query_paths()", dp, truth[:N_PATHS])

    smoke.phase("kernels")
    kernels_vs_refs(smoke, bx.widths)

    smoke.phase("pallas")
    srv_p = PathServer(make_engine(bx, backend="pallas"), batch_size=BATCH)
    warm(srv_p, "pallas", paths=False)
    assert_compiled(smoke, bx, BATCH)
    d_pal = srv_p.query(s, t)
    smoke.oracle("pallas query()", d_pal, truth)
    n, gap = bitwise_diff(d_jnp, d_pal)
    print(f"   jnp vs pallas: {n} of {len(s)} answers differ in bits, "
          f"max abs diff {gap:.3e}")


def sharded(smoke: Smoke, devices, index, bx, s, t, truth) -> None:
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import PathServer, make_engine
    from repro.sharding import ShardPlanner, ShardedQueryEngine

    smoke.phase("sharded")
    mesh = make_serving_mesh(SHARDS)
    planner = ShardPlanner(SHARDS)
    art = planner.build(index, planner.plan(index))
    eng = ShardedQueryEngine(art, mesh=mesh)
    placed = [{d.id for a in (sh.hub_ids + sh.via_d) for d in a.devices()}
              for sh in eng.router.shards]
    print(f"   shard devices: {[sorted(p) for p in placed]}")
    smoke.check(all(len(p) == 1 for p in placed)
                and len(set.union(*placed)) == SHARDS,
                f"each of {SHARDS} shards' slabs on its own device")
    smoke.check({d.id for d in devices} == set.union(*placed),
                "the shards cover every chip")

    single = PathServer(make_engine(bx, backend="jnp"), batch_size=BATCH)
    warm(single, "single-chip jnp", paths=False)
    ref = single.query(s, t)
    srv = PathServer(eng, batch_size=BATCH)
    warm(srv, "sharded jnp", paths=False)
    out = srv.query(s, t)
    for st in srv.stats.per_shard:
        print(f"   shard {st.shard} [{st.device}]: regions={st.regions} "
              f"bytes={st.device_bytes / 1e6:.2f} MB batches={st.batches} "
              f"gathers_out={st.gathers_out}")
    smoke.oracle("single-chip jnp", ref, truth)
    smoke.oracle("sharded jnp", out, truth)
    n, gap = bitwise_diff(ref, out)
    print(f"   sharded vs single-chip: {n} of {len(s)} answers differ in "
          f"bits, max abs diff {gap:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDS), default=1,
                    help=f"1: jnp + pallas engines on one chip; {SHARDS}: "
                         "only the region-sharded engine over four chips")
    args = ap.parse_args(argv)

    import jax
    devices = check_devices(jax.devices(), args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache

    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(jax.devices())} using={len(devices)}")
    cache = enable_compile_cache(ROOT)
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache: {cache} ({entries} entries at start)")

    smoke = Smoke()
    index, bx, s, t, truth = build(smoke)
    if args.chips == 1:
        single_chip(smoke, index, bx, s, t, truth)
    else:
        sharded(smoke, devices, index, bx, s, t, truth)
    print(f"phases run: {', '.join(smoke.phases)}")
    if smoke.failures:
        print("CHIP SMOKE FAILED:\n  " + "\n  ".join(smoke.failures))
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
