"""End-to-end serving driver: EHL* index answering batched ESPP queries.

Builds the index under a memory budget (workload-aware if --clusters > 0),
freezes it into a device layout (width-bucketed by default — DESIGN.md §4),
then serves a stream of query batches through a pluggable query engine and
reports throughput plus per-bucket routing stats — the paper's online phase
as a service.

    PYTHONPATH=src python examples/pathfind_serve.py --budget 0.2 --clusters 2

``--adaptive`` instead runs the closed-loop demo (DESIGN.md §8): serve a
clustered workload, shift it mid-run, and watch the index manager capture
the live distribution, recompress under the device-byte budget, and
hot-swap the artifact with zero downtime:

    PYTHONPATH=src python examples/pathfind_serve.py --adaptive \
        --map rooms-S --queries 250 --budget 0.4 --rounds 6

``--shards N`` serves through the region-sharded engine (DESIGN.md §9):
the bucketed slabs are placed one shard per device over N devices, and the
run fails when the runtime has fewer (forced host devices work:
``XLA_FLAGS=--xla_force_host_platform_device_count=N``), batches route by
(shard, bucket), and the answers are checked bitwise against the
single-device engine — the CI sharded smoke gate:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
        python examples/pathfind_serve.py --shards 4 --queries 1000

``--shards`` combines with ``--adaptive``: hot-swaps then republish every
shard atomically under one generation.
"""

import argparse
import os
import sys

import numpy as np

from repro import obs
from repro.core import (build_ehl, build_visgraph, bucketed_device_bytes,
                        cluster_queries, compress_to_fraction, make_map,
                        pack_bucketed, pack_index, path_length, plan_buckets,
                        slab_device_bytes, slab_layout, uniform_queries,
                        workload_scores)
from repro.indexing import IndexManager
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.serving import PathServer, expected_join_cost, make_engine

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--map", default="rooms-M")
    ap.add_argument("--budget", type=float, default=0.2)
    ap.add_argument("--clusters", type=int, default=0)
    ap.add_argument("--queries", type=int, default=2000)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--layout", choices=("bucketed", "slab"),
                    default="bucketed",
                    help="device layout: width-bucketed slabs or the single "
                         "global-Lmax slab")
    ap.add_argument("--backend", choices=("jnp", "pallas", "host"),
                    default="jnp", help="query engine backend")
    ap.add_argument("--kernels", action="store_true",
                    help="alias for --backend pallas (kernels compile on a "
                         "TPU and run in the Pallas interpreter on the CPU; "
                         "other backends are refused)")
    ap.add_argument("--quantize", choices=("off", "bf16", "f16"),
                    default="off",
                    help="serve quantized label slabs (DESIGN.md §11): "
                         "narrow distances + delta-encoded u16 via ids with "
                         "exact-argmin residual rescue; checks argmin/path "
                         "answers bitwise against the f32 engine and the "
                         "byte drop against --quantize-min-drop (CI gate)")
    ap.add_argument("--quantize-min-drop", type=float, default=1.8,
                    help="[quantize] required f32/quantized device-byte "
                         "ratio")
    ap.add_argument("--paths", type=int, default=0,
                    help="also extract N full paths via the batched argmin "
                         "engine and verify their lengths")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve through the region-sharded engine over N "
                         "devices; checks answers bitwise against the "
                         "single-device engine and per-shard bytes against "
                         "the per-device cap (CI smoke gate)")
    ap.add_argument("--shard-tol", type=float, default=1.15,
                    help="[shards] per-device byte cap as a multiple of "
                         "total/num_shards")
    ap.add_argument("--serve-async", action="store_true",
                    help="also serve through the continuous-batching loop "
                         "(coalescing queue + double-buffered dispatch) and "
                         "check the answers bitwise against the synchronous "
                         "path, requiring >= 1 full-batch flush and >= 1 "
                         "deadline flush (CI smoke gate; exits nonzero)")
    ap.add_argument("--adaptive", action="store_true",
                    help="adaptive serving demo: live workload capture -> "
                         "budgeted recompression -> zero-downtime hot-swap "
                         "(repro.indexing); shifts the workload mid-run")
    ap.add_argument("--rounds", type=int, default=8,
                    help="[adaptive] serving rounds (workload shifts at "
                         "the midpoint)")
    ap.add_argument("--min-swaps", type=int, default=1,
                    help="[adaptive] exit nonzero unless at least this many "
                         "hot-swaps were published (CI smoke gate)")
    ap.add_argument("--async-swap", action="store_true",
                    help="[adaptive] build/validate/swap on a background "
                         "thread instead of between rounds")
    ap.add_argument("--metrics", action="store_true",
                    help="export telemetry (DESIGN.md §12) on exit: "
                         "telemetry.prom + telemetry.json + events.jsonl "
                         "under --metrics-dir; self-checks that the "
                         "Prometheus text parses and the expected series/"
                         "events are present (CI smoke gate)")
    ap.add_argument("--metrics-dir",
                    default=os.path.join(ROOT, "benchmarks", "artifacts",
                                         "telemetry"),
                    help="[metrics] output directory")
    args = ap.parse_args()
    backend = "pallas" if args.kernels else args.backend
    print(f"compile cache: {enable_compile_cache(ROOT)}")
    if args.metrics:
        # compile/cost attribution (DESIGN.md §13) rides along with the
        # telemetry export; it must be enabled before the FIRST warmup —
        # the pjit cache is process-wide, so every cold compile happens
        # exactly once, and a capture installed later sees none of them
        obs.enable_profile()
    if args.adaptive:
        return run_adaptive(args, backend)
    if args.shards > 1:
        return run_sharded(args, backend)

    scene = make_map(args.map, seed=0)
    graph = build_visgraph(scene)
    index = build_ehl(scene, cell_size=2.0, graph=graph)
    full_mb = index.label_memory() / 1e6

    scores, alpha = None, 0.0
    if args.clusters > 0:
        hist = cluster_queries(scene, graph, args.clusters, 2000, seed=9,
                               require_path=False)
        scores, alpha = workload_scores(index, hist), 0.2
    stats = compress_to_fraction(index, args.budget, cell_scores=scores,
                                 alpha=alpha)
    print(f"index: {full_mb:.1f} MB -> {stats.final_bytes / 1e6:.1f} MB "
          f"({args.budget:.0%} budget, workload-aware={args.clusters > 0})")

    # only the layout that actually serves is materialized on device; the
    # other side of the comparison print is computed analytically from the
    # grid's pack metadata
    serve_bucketed = args.layout == "bucketed" and backend != "host"
    serve_slab = args.layout == "slab" and backend != "host"
    pk = pack_index(index) if serve_slab else None
    bx = pack_bucketed(index) if serve_bucketed else None
    slab_bytes = pk.device_bytes() if pk is not None \
        else slab_device_bytes(index)
    bucket_bytes = bx.device_bytes() if bx is not None \
        else bucketed_device_bytes(index)
    counts, widths, region_bucket = plan_buckets(index)
    print(f"slab layout:     {len(index.regions)} regions, "
          f"{slab_bytes / 1e6:.1f} MB on device")
    print(f"bucketed layout: widths={widths}, "
          f"{bucket_bytes / 1e6:.1f} MB on device "
          f"({slab_bytes / max(1, bucket_bytes):.1f}x smaller)")
    counts = np.asarray(counts)
    for k, w in enumerate(widths):
        m = region_bucket == k
        used, total = counts[m].sum(), max(1, m.sum()) * w
        print(f"  bucket {k}: width={w:5d} regions={int(m.sum()):5d} "
              f"waste={1 - used / total:.1%}")

    if backend == "host":
        if args.quantize != "off":
            print("--quantize needs a device backend (jnp|pallas)")
            sys.exit(2)
        engine = make_engine(index, backend="host")
    else:
        engine = make_engine(bx if serve_bucketed else pk, backend=backend)

    eng32, qerr = None, 0.0
    if args.quantize != "off" and backend != "host":
        lay = slab_layout(args.quantize)
        artq = (pack_bucketed(index, layout=lay) if serve_bucketed
                else pack_index(index, layout=lay))
        art32 = bx if serve_bucketed else pk
        drop = art32.device_bytes() / artq.device_bytes()
        qerr = float(np.asarray(artq.qerr))
        qs_ = artq.quant_stats()
        print(f"quantized[{args.quantize}]: "
              f"{artq.device_bytes() / 1e6:.2f} MB on device "
              f"({drop:.2f}x smaller), qerr={qerr:.2e}, "
              f"id_fallback={qs_['id_fallback']} "
              f"vid_fallback={qs_['vid_fallback']} "
              f"dist_fallback={qs_['dist_fallback']}")
        eng32 = engine                  # f32 reference for the bitwise gate
        engine = make_engine(artq, backend=backend)
        if drop < args.quantize_min_drop:
            print(f"QUANTIZED SMOKE FAILED:\n  byte drop {drop:.2f}x < "
                  f"required {args.quantize_min_drop:.2f}x")
            sys.exit(1)

    if args.clusters > 0:
        qs = cluster_queries(scene, graph, args.clusters, args.queries,
                             seed=33, require_path=False)
    else:
        qs = uniform_queries(scene, graph, args.queries, seed=33,
                             require_path=False)
    srv = PathServer(engine, batch_size=args.batch)
    srv.warmup(paths=args.paths > 0)
    d = srv.query(qs.s.astype(np.float32), qs.t.astype(np.float32))
    print(f"served {srv.stats.queries} queries in {srv.stats.seconds:.3f}s "
          f"-> {srv.stats.us_per_query:.1f} us/query "
          f"({srv.stats.qps:,.0f} qps); {np.isfinite(d).sum()} reachable "
          f"[layout={args.layout}, backend={backend}]")
    for k, b in sorted(srv.stats.per_bucket.items()):
        print(f"  bucket {k}: width={b.width:5d} queries={b.queries:5d} "
              f"batches={b.batches:3d} occupancy={b.occupancy:.1%} "
              f"{b.us_per_query:.1f} us/query")

    if eng32 is not None:
        failures = check_quantized(engine, eng32, qs.s.astype(np.float32),
                                   qs.t.astype(np.float32), qerr)
        if failures:
            print("QUANTIZED SMOKE FAILED:\n  " + "\n  ".join(failures))
            sys.exit(1)
        print("quantized smoke OK: argmin/covis bitwise vs f32, "
              "distances within the 2*qerr bound")

    if args.serve_async:
        failures = check_async(srv, qs.s.astype(np.float32),
                               qs.t.astype(np.float32), backend)
        if failures:
            print("ASYNC SMOKE FAILED:\n  " + "\n  ".join(failures))
            sys.exit(1)

    if args.paths > 0:
        n = min(args.paths, len(qs.s))
        dp, paths = srv.query_paths(qs.s[:n].astype(np.float32),
                                    qs.t[:n].astype(np.float32),
                                    host_index=index)
        err = max((abs(path_length(p) - float(di))
                   for di, p in zip(dp, paths) if np.isfinite(di)),
                  default=0.0)
        print(f"extracted {n} paths via batched argmin ({backend}); "
              f"max |len(path) - d| = {err:.2e}")

    if args.metrics:
        failures = dump_metrics(args, srv.telemetry)
        if failures:
            print("METRICS SMOKE FAILED:\n  " + "\n  ".join(failures))
            sys.exit(1)


def engine_argmin(engine, s, t) -> list:
    """Full-batch argmin through any bucket-routed engine (exact shapes)."""
    from repro.core.packed import empty_results

    keys = engine.buckets_of(s, t)
    outs = empty_results(len(s), True)
    for k in np.unique(keys):
        m = keys == k
        res = engine.batch_argmin(s[m], t[m], bucket=int(k))
        for o, r in zip(outs, res):
            o[m] = np.asarray(r)[:int(m.sum())]
    return outs


def check_quantized(eng_q, eng_32, s, t, qerr: float) -> list:
    """The quantized serving gate: distances within the documented bound,
    argmin winners (covis verdicts + via/hub ids — i.e. the extracted
    paths) bitwise-identical to the f32 engine.  Returns failure strings.
    """
    d32, cv32, vs32, hb32, vt32 = engine_argmin(eng_32, s, t)
    dq, cvq, vsq, hbq, vtq = engine_argmin(eng_q, s, t)
    failures = []
    fin = np.isfinite(d32)
    if not np.array_equal(fin, np.isfinite(dq)):
        failures.append("reachability differs from the f32 engine")
    bound = 2.0 * qerr + 1e-4 * np.abs(np.where(fin, d32, 0.0))
    err = np.abs(np.where(fin, dq - d32, 0.0))
    if not np.all(err <= bound + 1e-6):
        failures.append(f"distance error {err.max():.3e} over the "
                        f"2*qerr bound {2 * qerr:.3e}")
    if not np.array_equal(cv32, cvq):
        failures.append("covis verdicts differ from the f32 engine")
    m = ~cv32 & fin                     # rows whose path runs via hubs
    for name, a, b in (("via_s", vs32, vsq), ("hub", hb32, hbq),
                       ("via_t", vt32, vtq)):
        if not np.array_equal(a[m], b[m]):
            failures.append(f"argmin {name} ids differ from the f32 "
                            "engine (paths not bitwise)")
    return failures


def check_async(srv, s, t, label: str) -> list:
    """Continuous-batching smoke: serve through the coalescing loop and
    compare bitwise against the synchronous path.

    Two traffic shapes force both flush reasons deterministically:

    * *burst* — one ``submit()`` of > batch_size queries that all share the
      hottest dispatch key, so a full group exists the moment the serve
      loop looks (>= 1 full flush guaranteed);
    * *trickle* — a sub-batch-size submit with no ``flush()``, so only the
      ``max_wait_ms`` deadline can ship it (>= 1 deadline flush).

    Returns a list of failure strings (empty = pass).
    """
    bs = srv.batch_size
    with srv.engine.pin() as eng:
        keys = eng.buckets_of(s, t)
    vals, counts = np.unique(keys, return_counts=True)
    hot = np.nonzero(keys == int(vals[np.argmax(counts)]))[0]
    reps = -(-(bs + 1) // len(hot))     # ceil: tile past one full batch
    sb = np.tile(s[hot], (reps, 1))[:bs + len(hot)]
    tb = np.tile(t[hot], (reps, 1))[:bs + len(hot)]
    ref_burst = srv.query(sb, tb)
    ref_trickle = srv.query(s[:8], t[:8])

    srv.start_async(max_wait_ms=2.0)
    got_burst = srv.submit(sb, tb).result(timeout=120)
    got_trickle = srv.submit(s[:8], t[:8]).result(timeout=120)
    srv.stop_async()

    st = srv.stats
    failures = []
    if not np.array_equal(ref_burst, got_burst):
        failures.append(f"{label}: burst answers differ from sync path")
    if not np.array_equal(ref_trickle, got_trickle):
        failures.append(f"{label}: trickle answers differ from sync path")
    if st.full_flushes < 1:
        failures.append(f"{label}: no full-batch flush observed "
                        f"({st.full_flushes})")
    if st.deadline_flushes < 1:
        failures.append(f"{label}: no deadline flush observed "
                        f"({st.deadline_flushes})")
    bad_occ = {k: b.occupancy for k, b in st.per_bucket.items()
               if b.occupancy > 1.0}
    if bad_occ:
        failures.append(f"{label}: per-bucket occupancy above 1.0: "
                        f"{bad_occ}")
    print(f"async serve [{label}]: submitted={st.submitted} "
          f"flushes full={st.full_flushes} deadline={st.deadline_flushes} "
          f"forced={st.forced_flushes} pipeline_peak={st.pipeline_peak} "
          f"queue_peak={st.queue_depth_peak} "
          f"identical={'yes' if not failures else 'NO'}")
    return failures


def dump_metrics(args, telemetry, *, expect_shards: int = 0,
                 expect_swaps: int = 0) -> list:
    """Export telemetry.prom / telemetry.json / events.jsonl and self-check
    the export (DESIGN.md §12).  Returns failure strings (empty = pass):

    * the Prometheus text must round-trip through ``parse_prometheus``;
    * ``serve_queries_total`` must be present with a nonzero sum;
    * compile/cost attribution series (``jit_compiles_total`` +
      ``jit_cost_flops_total``, DESIGN.md §13) must be present — the
      capture is enabled with ``--metrics`` before the first warmup;
    * sharded runs must export per-shard series for every shard id;
    * adaptive runs must have logged >= ``expect_swaps`` swap events and
      exported build-pipeline stage spans (``build_stage_ms``).
    """
    out = os.path.abspath(args.metrics_dir)
    os.makedirs(out, exist_ok=True)
    text = obs.prometheus_text(telemetry.registry)
    with open(os.path.join(out, "telemetry.prom"), "w") as f:
        f.write(text)
    with open(os.path.join(out, "telemetry.json"), "w") as f:
        f.write(obs.json_snapshot(telemetry.registry))
    n_events = telemetry.events.dump_jsonl(
        os.path.join(out, "events.jsonl"))

    failures = []
    try:
        parsed = obs.parse_prometheus(text)
    except ValueError as e:
        return [f"metrics: exported Prometheus text does not parse: {e}"]
    served = sum(parsed.get("serve_queries_total", {}).values())
    if served <= 0:
        failures.append("metrics: no serve_queries_total series exported")
    compiles = sum(parsed.get("jit_compiles_total", {}).values())
    if compiles <= 0:
        failures.append("metrics: no jit_compiles_total series exported "
                        "(profile capture not live before first warmup?)")
    if sum(parsed.get("jit_cost_flops_total", {}).values()) <= 0:
        failures.append("metrics: no jit_cost_flops_total series "
                        "(cost_analysis capture produced nothing)")
    if expect_shards > 0:
        shards = {dict(k).get("shard")
                  for k in parsed.get("shard_slots_total", {})}
        missing = {str(i) for i in range(expect_shards)} - shards
        if missing:
            failures.append("metrics: per-shard series missing for "
                            f"shard(s) {sorted(missing)}")
    if expect_swaps > 0:
        swaps = telemetry.events.counts().get("swap", 0)
        if swaps < expect_swaps:
            failures.append(f"metrics: {swaps} swap events in the log, "
                            f"expected >= {expect_swaps}")
        builds = sum(parsed.get("builds_total", {}).values())
        if builds < expect_swaps:
            failures.append(f"metrics: {builds:.0f} builds_total, "
                            f"expected >= {expect_swaps}")
        if sum(parsed.get("build_stage_ms_count", {}).values()) <= 0:
            failures.append("metrics: no build_stage_ms stage spans "
                            "exported for the adaptive build pipeline")
        if telemetry.events.counts().get("plan_execute", 0) < 1:
            failures.append("metrics: no plan_execute planner decision "
                            "records in the event log")
    print(f"metrics: exported {len(parsed)} series "
          f"({served:.0f} queries served, {compiles:.0f} jit compiles), "
          f"{n_events} events -> {out}")
    return failures


def run_sharded(args, backend: str) -> None:
    """Sharded serving smoke: answers must match the single-device engine
    bitwise and every shard must respect the per-device byte cap.  Exits
    nonzero on any violation (the CI gate)."""
    import jax
    from repro.sharding import ShardPlanner, ShardedQueryEngine

    if backend == "host":
        print("--shards needs a device backend (jnp|pallas)")
        sys.exit(2)
    mesh = make_serving_mesh(args.shards)   # raises on too few devices
    scene = make_map(args.map, seed=0)
    graph = build_visgraph(scene)
    index = build_ehl(scene, cell_size=2.0, graph=graph)
    compress_to_fraction(index, args.budget)

    lay = None if args.quantize == "off" else slab_layout(args.quantize)
    planner = ShardPlanner(args.shards, tol=args.shard_tol)
    plan = planner.plan(index)
    sharded = planner.build(index, plan)
    eng = ShardedQueryEngine(sharded, mesh=mesh,
                             use_kernels=backend == "pallas")
    bx = pack_bucketed(index)
    single = make_engine(bx, backend=backend)
    eng_q, sharded_q, qerr = None, None, 0.0
    if lay is not None:
        sharded_q = ShardPlanner(args.shards, tol=args.shard_tol,
                                 layout=lay).build(index, plan)
        eng_q = ShardedQueryEngine(sharded_q, mesh=mesh,
                                   use_kernels=backend == "pallas")
        qerr = max(float(np.asarray(b.qerr)) for b in sharded_q.shards)

    per = sharded.per_shard_bytes()
    print(f"sharded: {args.shards} shards over "
          f"{len({str(d) for d in eng.router.devices})} device(s) "
          f"(runtime has {len(jax.devices())}), "
          f"plan moves={plan.moves}")
    print(f"  bytes: total={sharded.device_bytes() / 1e6:.2f} MB "
          f"(single-device {bx.device_bytes() / 1e6:.2f} MB), "
          f"imbalance={sharded.imbalance():.3f}")

    qs = uniform_queries(scene, graph, args.queries, seed=33,
                         require_path=False)
    s = qs.s.astype(np.float32)
    t = qs.t.astype(np.float32)

    srv1 = PathServer(single, batch_size=args.batch)
    srv1.warmup()
    ref = srv1.query(s, t)
    srv2 = PathServer(eng, batch_size=args.batch)
    srv2.warmup()
    out = srv2.query(s, t)
    print(f"  single-device: {srv1.stats.us_per_query:.1f} us/query; "
          f"sharded: {srv2.stats.us_per_query:.1f} us/query "
          f"({srv2.stats.qps:,.0f} qps)")
    for st in srv2.stats.per_shard:
        print(f"  shard {st.shard} [{st.device}]: regions={st.regions} "
              f"bytes={st.device_bytes / 1e6:.2f}MB occ={st.occupancy:.0%} "
              f"batches={st.batches} slots={st.slots} "
              f"gathers_out={st.gathers_out} "
              f"{st.us_per_slot:.1f} us/slot")

    failures = []
    fin = np.isfinite(ref)
    if not (np.array_equal(fin, np.isfinite(out))
            and np.array_equal(np.where(fin, ref, 0),
                               np.where(fin, out, 0))):
        bad = int((np.where(fin, ref, 0) != np.where(fin, out, 0)).sum())
        failures.append(f"{bad} answers differ from single-device engine")
    cap = args.shard_tol * sharded.device_bytes() / args.shards
    if max(per) > cap:
        failures.append(f"max shard {max(per)}B over per-device cap "
                        f"{cap:.0f}B")
    if eng_q is not None:
        drop = sharded.device_bytes() / sharded_q.device_bytes()
        print(f"  quantized[{args.quantize}]: "
              f"{sharded_q.device_bytes() / 1e6:.2f} MB total "
              f"({drop:.2f}x smaller), qerr={qerr:.2e}")
        if drop < args.quantize_min_drop:
            failures.append(f"quantized byte drop {drop:.2f}x < required "
                            f"{args.quantize_min_drop:.2f}x")
        failures += check_quantized(eng_q, eng, s, t, qerr)
    if args.serve_async:
        failures += check_async(srv2, s, t, "sharded")
    if args.metrics:
        failures += dump_metrics(args, srv2.telemetry,
                                 expect_shards=args.shards)
    if failures:
        print("SHARDED SMOKE FAILED:\n  " + "\n  ".join(failures))
        sys.exit(1)
    print(f"sharded smoke OK: {len(s)} answers bitwise-identical, "
          f"per-shard bytes within {args.shard_tol:.2f}x of fair share")


def run_adaptive(args, backend: str) -> None:
    """Closed-loop demo: the served workload shifts mid-run and the index
    manager recompresses + hot-swaps to follow it, holding the device-byte
    budget throughout.  Exits nonzero unless >= --min-swaps swaps happened
    with answers stable across every swap boundary (the CI smoke gate)."""
    scene = make_map(args.map, seed=0)
    graph = build_visgraph(scene)
    index = build_ehl(scene, cell_size=2.0, graph=graph)
    lay = None if args.quantize == "off" else slab_layout(args.quantize)
    budget = int(bucketed_device_bytes(index) * args.budget)
    shard_kw = {}
    if args.shards > 1:
        from repro.sharding import sharded_overhead_bytes
        over_kw = dict(layout=lay) if lay is not None else {}
        budget += sharded_overhead_bytes(index, args.shards, **over_kw)
        shard_kw = dict(num_shards=args.shards,
                        mesh=make_serving_mesh(args.shards),
                        shard_tol=args.shard_tol)

    # validate_tol=0: a candidate only goes live if the probe answers are
    # *bitwise* identical, so the smoke gate below (np.array_equal across
    # every swap boundary) is checking the same criterion the manager
    # enforces — merging/splitting preserves each winning label's exact
    # float arithmetic, so zero tolerance is attainable, and any candidate
    # that misses it is aborted rather than published
    # (quantized layouts widen the manager's effective probe tolerance by
    # the generations' quantization-error bounds — the *argmin* stays exact
    # via the residual rescue, but reported distances carry the bound)
    # one Telemetry bundle across the manager and the server, so swap /
    # drift events and serve-side series land in the same export
    tel = obs.Telemetry()
    mgr = IndexManager(index, budget, backend=backend,
                       batch_size=args.batch,
                       min_queries=max(64, args.queries // 4),
                       replan_threshold=0.10, min_dwell=1, probe_n=64,
                       seed=17, validate_tol=0.0, layout=lay,
                       telemetry=tel, **shard_kw)
    uniform_engine = mgr.engine.current    # generation-0 uniform-score ref
    srv = PathServer(mgr.engine, batch_size=args.batch,
                     recorder=mgr.recorder, telemetry=tel)
    srv.warmup()
    print(f"adaptive: budget={budget / 1e6:.2f} MB "
          f"(x{args.budget:.2f} of uncompressed artifact), "
          f"initial device={mgr.device_bytes() / 1e6:.2f} MB, "
          f"backend={backend}")

    k = max(2, args.clusters)
    half = max(1, args.rounds // 2)
    phases = [cluster_queries(scene, graph, k, args.queries, seed=101,
                              require_path=False),
              cluster_queries(scene, graph, k, args.queries, seed=202,
                              require_path=False)]
    failures = []
    lat = {0: [], 1: []}
    for rnd in range(args.rounds):
        phase = 0 if rnd < half else 1
        qs = phases[phase]
        srv.stats.seconds = 0.0
        srv.stats.queries = 0
        srv.query(qs.s.astype(np.float32), qs.t.astype(np.float32))
        lat[phase].append(srv.stats.us_per_query)

        probe_pre = mgr.probe_answers()
        qe_pre = mgr._qerr_of(mgr.engine.artifact) if lay is not None else 0.0
        if args.async_swap:
            mgr.maybe_adapt(block=False)
            mgr.join()                      # bound the demo's swap count
            swapped = mgr.generation > srv.stats.generation
        else:
            swapped = mgr.maybe_adapt()
        if swapped:
            probe_post = mgr.probe_answers()
            both_inf = (~np.isfinite(probe_pre)) & (~np.isfinite(probe_post))
            diff = np.abs(np.where(both_inf, 0.0, probe_post - probe_pre))
            # quantized: two exact-equal generations may differ by the sum
            # of their 2*qerr distance bounds; f32 stays bitwise (tol 0)
            swap_tol = 0.0
            if lay is not None:
                swap_tol = 2.0 * (qe_pre
                                  + mgr._qerr_of(mgr.engine.artifact))
            stable = bool(np.all(diff <= swap_tol))
            if not stable:
                failures.append(f"round {rnd}: probe answers changed "
                                "across swap boundary")
            if mgr.device_bytes() > budget:
                failures.append(f"round {rnd}: swapped-in artifact "
                                f"{mgr.device_bytes()}B over budget")
        rec = mgr.history[-1] if swapped else None
        print(f"round {rnd} phase {phase}: "
              f"{srv.stats.us_per_query:7.1f} us/query  "
              f"device={mgr.device_bytes() / 1e6:5.2f} MB  "
              f"gen={mgr.generation}"
              + (f"  SWAP[{rec.kind}] drift={rec.drift:.2f} "
                 f"build={rec.build_s:.2f}s pack={rec.pack_s:.2f}s "
                 f"probe_err={rec.probe_max_err:.1e}" if swapped else ""))

    qs2 = phases[1]
    s2 = qs2.s.astype(np.float32)
    t2 = qs2.t.astype(np.float32)
    jc_adapt = expected_join_cost(mgr.engine.current, s2, t2)
    jc_uni = expected_join_cost(uniform_engine, s2, t2)
    p50 = {ph: float(np.median(v)) for ph, v in lat.items() if v}
    st = mgr.stats()
    print(f"phase p50 latency: {p50} us/query")
    print(f"post-swap join cost on shifted workload: adapted={jc_adapt:.0f} "
          f"vs uniform-score={jc_uni:.0f} (mean dispatch width^2; "
          f"{'better' if jc_adapt <= jc_uni else 'WORSE'})")
    print(f"lifecycle: {st}")
    print(f"serve stats: gen={srv.stats.generation} swaps={srv.stats.swaps} "
          f"stale_batches={srv.stats.stale_batches}")

    if mgr.swaps < args.min_swaps:
        failures.append(f"only {mgr.swaps} swaps, need >= {args.min_swaps}")
    if mgr.validation_failures:
        failures.append(f"{mgr.validation_failures} probe validations "
                        "failed (swap aborted)")
    if args.serve_async:
        failures += check_async(srv, s2, t2, "adaptive")
    if args.metrics:
        failures += dump_metrics(
            args, tel, expect_swaps=args.min_swaps,
            expect_shards=args.shards if args.shards > 1 else 0)
    if failures:
        print("ADAPTIVE SMOKE FAILED:\n  " + "\n  ".join(failures))
        sys.exit(1)
    print(f"adaptive smoke OK: {mgr.swaps} hot-swap(s), answers stable, "
          f"budget held")


if __name__ == "__main__":
    main()
