#!/usr/bin/env python3
"""Benchmark one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process: build or load the cell's EHL* index, serve it through
``PathServer.submit()`` with every serving choice left at the program's
defaults, offer the cell's traffic open loop at the cell's fixed rate (a
ramp, then the measured window), and check a sample of the answers against
the plain reference once the window has closed.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``compared`` (each number checked, beside its
limit).  The compared numbers are also the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import check  # noqa: E402
import index_cache  # noqa: E402
import measure  # noqa: E402
import openloop  # noqa: E402
import reference  # noqa: E402
import scene  # noqa: E402
import spec  # noqa: E402
import tracefile  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

TRACE_S = 10.0           # longest traced interval of a --trace 1 run
SPAN_CAPACITY = 1 << 19  # request spans kept in a traced run
ENTRIES = ("fold_endpoint", "join_endpoints")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_devices(chips: int):
    """The chips to run on, or SystemExit: the platform must be ``tpu``
    and there must be ``chips`` of them."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform {platform!r}); "
                         "refusing to run off the chip")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX has "
                         f"{len(devices)}")
    return devices


def setup(cell, seed: int, seconds: float, trace: bool, cache: str):
    """Index, server (warm, loop started) and traffic plan."""
    from repro import obs
    from repro.launch.cache import enable_compile_cache
    from repro.serving import PathServer

    enable_compile_cache(ROOT)
    cfg = cell.config
    t = time.perf_counter()
    mp = scene.make_map(cfg)
    index, how = index_cache.build_or_load(cfg, mp, SRC, log=log,
                                           cache=cache)
    t_index = time.perf_counter()
    # a traced run samples every request's spans; other runs keep the
    # program's default telemetry
    tel = obs.Telemetry(sample_rate=1.0, span_capacity=SPAN_CAPACITY) \
        if trace else None
    server = PathServer(index, telemetry=tel)
    t_pack = time.perf_counter()
    server.warmup()
    t_warm = time.perf_counter()
    plan = traffic.make_plan(mp, cell.mix, cfg, cell.rate, seconds, seed)
    server.start_async()
    t_plan = time.perf_counter()
    eng = server.engine
    widths = [eng.bucket_width(k) for k in range(eng.num_buckets)]
    log(f"setup: index {how} {t_index - t:.2f}s ({len(index.regions)} "
        f"regions, {index.label_memory() / 1e6:.1f} MB labels), pack "
        f"{t_pack - t_index:.2f}s ({eng.device_bytes() / 1e6:.1f} MB on "
        f"device, widths {widths}), warm {t_warm - t_pack:.2f}s, traffic "
        f"{t_plan - t_warm:.2f}s ({len(plan.due)} requests x "
        f"{plan.per_request}, pool {len(plan.s)} queries)")
    return mp, index, server, plan


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START, cache: str = index_cache.CACHE):
    """Measure one run; returns the result object (without ``compared``)
    and the compared numbers."""
    import jax

    mp, index, server, plan = setup(cell, seed, seconds, trace, cache)
    ramp = float(cell.mix["ramp_s"])
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    snaps: dict = {}

    def mark(name):
        def fn():
            snaps[name] = (time.perf_counter(), measure.snapshot(server))
        return fn

    marks = [(w0, mark("w0")), (w1, mark("w1"))]
    annotate = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        t_end = w0 + min(seconds, TRACE_S)

        def start():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            mark("i0")()

        def stop():
            mark("i1")()
            jax.profiler.stop_trace()

        marks = [(w0, start), (t_end, stop), (w1, mark("w1"))]
        annotate = functools.partial(jax.profiler.TraceAnnotation,
                                     "bench.submit")

    with measure.GcWatch() as gcw:
        run = openloop.offer(server, plan, t0, w1, marks=marks,
                             annotate=annotate)
    due = t0 + plan.due
    in_window = (due >= w0) & (due < w1)
    offered = in_window & ~np.isnan(run.submitted)
    unanswered = int((offered & np.isnan(run.done)).sum())
    late = run.submitted[offered] - due[offered]
    if late.size:
        log(f"generator lateness over {late.size} requests: p50 "
            f"{1e3 * np.percentile(late, 50):.3f} ms, p99 "
            f"{1e3 * np.percentile(late, 99):.3f} ms, max "
            f"{1e3 * late.max():.3f} ms")
    pauses = gcw.between(w0, w1)
    full = [e[1] for e in pauses if e[2] == 2]
    log(f"garbage collection in the window: {len(pauses)} collections, "
        f"{1e3 * sum(e[1] for e in pauses):.1f} ms in all; {len(full)} of "
        f"the oldest generation, longest "
        f"{1e3 * max(full, default=0.0):.1f} ms")
    w_a, w_b = snaps.get("w0", snaps.get("i0")), snaps["w1"]
    d = measure.delta(w_a[1], w_b[1])
    log(f"window: {seconds:g}s, {int(offered.sum())} requests offered, "
        f"{d['queries']} queries and {d['batches']} batches served, "
        f"{d['jit_traces']} compiles inside the window, "
        f"{len(run.errors)} submit errors")
    q = plan.per_request
    done_in = (run.done >= w0) & (run.done <= w1)
    lat_ms = np.where(np.isnan(run.done), np.inf, run.done - due)[
        in_window & ~np.isnan(run.submitted)] * 1e3
    if lat_ms.size:
        log(f"latency over {lat_ms.size} requests: p50 "
            f"{np.percentile(lat_ms, 50):.3f} ms, p99 "
            f"{np.percentile(lat_ms, 99):.3f} ms, max {lat_ms.max():.3f} ms")
    traced = None
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    bs = server.batch_size
    edges = work.padded_edges(mp.n_edges)
    if trace:
        (i0, c0), (i1, c1) = snaps["i0"], snaps["i1"]
        in_i = (run.submitted >= i0) & (run.submitted < i1)
        answered = int(((run.done >= i0) & (run.done <= i1)).sum()) * q
        spans = [s for s in server.telemetry.spans.traces("async")
                 if s.t_start is not None and i0 <= s.t_start < i1]
        t_red = time.perf_counter()
        path = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                      "*.xplane.pb"))
        red = tracefile.reduce(tracefile.load(path[0]), (i1 - i0) * 1e9,
                               ENTRIES)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: {red['devices']} devices, window {red['window_s']!r}s, "
            f"busy {red['busy_s']!r}s, entries {red['entry_s']}, reduced in "
            f"{time.perf_counter() - t_red:.2f}s")
        traced = measure.Traced(
            i0, i1, measure.delta(c0, c1), run.submit_s[in_i], answered,
            spans, red, bs, edges, work.peaks(devices[0].device_kind),
            gcw.between(i0, i1))
    m = measure.Measurement(seconds, setup_s, int(done_in.sum()) * q,
                            lat_ms, traced)
    metrics = {}
    for entry in (cell.per_layer if trace else cell.end_to_end):
        v = spec.reader(entry["name"])(m)
        if v is not None:
            metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
    s, t, answers = check.sample(plan, run, offered, seed)
    server.stop_async()
    del server, index, run
    gc.collect()
    t_ref = time.perf_counter()
    compared = check.compare(reference.Reference(mp), s, t, answers,
                             unanswered, cell.limit)
    log(f"reference: {len(s)} queries in "
        f"{time.perf_counter() - t_ref:.2f}s")
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": check.passed(compared),
           "attempted": int(offered.sum()), "failed": unanswered,
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": [list(o) for o in red["ops"]],
                            "idle_gaps": [list(g) for g in red["gaps"]]}
    return out, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    devices = check_devices(cell.chips)
    sys.path.insert(0, SRC)
    out, compared = run_cell(cell, args.seed, args.seconds,
                             bool(args.trace), devices)
    for name, v in compared.items():
        log(f"compared {name}: {v['value']!r} (limit {v['limit']!r})")
    out["compared"] = compared
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
