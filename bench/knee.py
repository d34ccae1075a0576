#!/usr/bin/env python3
"""Find a configuration's knee: sweep offered rates once, on the chip.

    python3 bench/knee.py --config room64-b20 --mix single-uniform \
        --rates 100 200 400 800 [--seconds 4] [--out knee.json]

For each mix, one server (the harness's own set-up) is offered each rate in
turn, open loop, for a ramp and a short window, and the achieved rate and
latencies are printed.  The knee is the highest rate whose achieved rate
keeps up with the offered one without a growing queue; a cell's rate
(``cells/<cell>.json``) is set from it once, by hand.  The benchmark's own
runs never search for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import openloop  # noqa: E402
import run as harness  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402


class _Cell:
    """A cell's pieces for a (configuration, mix) pair without a cell."""

    def __init__(self, config: str, mix: str, rate: float):
        bench = spec._json(os.path.join(spec.ROOT, "BENCHMARK.json"))
        conf = {c["name"]: c for c in bench["configs"]}[config]
        self.config = spec._json(os.path.join(spec.ROOT, conf["file"]))
        self.mix = spec._json(os.path.join(HERE, "traffic", mix + ".json"))
        self.rate = rate
        self.chips = 1


def sweep(server, mp, index, cell, rates, seconds, seed):
    rows = []
    ramp = float(cell.mix["ramp_s"])
    for rate in rates:
        plan = traffic.make_plan(mp, cell.mix, cell.config, rate, seconds,
                                 seed)
        t0 = time.perf_counter()
        w0, w1 = t0 + ramp, t0 + ramp + seconds
        run = openloop.offer(server, plan, t0, w1, index=index)
        due = t0 + plan.due
        win = (due >= w0) & (due < w1) & ~np.isnan(run.submitted)
        lat = (np.where(np.isnan(run.done), np.inf, run.done - due)[win]
               * 1e3)
        done_in = ((run.done >= w0) & (run.done <= w1)).sum()
        late = run.submitted[win] - due[win]
        q = plan.per_request
        row = {"rate": rate, "offered_qps": rate * q,
               "achieved_qps": float(done_in * q / seconds),
               "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
               "p99_ms": float(np.percentile(lat, 99)) if lat.size else None,
               "late_p99_ms": float(1e3 * np.percentile(late, 99))
               if late.size else None,
               "unanswered": int(np.isnan(run.done[win]).sum())}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", action="append", required=True)
    ap.add_argument("--rates", type=float, nargs="+", action="append",
                    required=True, help="one list per --mix")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=977)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    harness.check_devices(1)
    sys.path.insert(0, harness.SRC)
    out = {}
    first = _Cell(args.config, args.mix[0], args.rates[0][0])
    mp, index, server, _ = harness.setup(first, args.seed, 1.0, False,
                                         harness.index_cache.CACHE)
    cells = [_Cell(args.config, mix, rates[0]) for mix, rates in
             zip(args.mix, args.rates)]
    if any(traffic.path_share(c.mix) > 0 for c in cells):
        server.warmup(paths=True)       # a later mix may ask for routes
    for mix, rates, cell in zip(args.mix, args.rates, cells):
        print(f"== {args.config} x {mix}", flush=True)
        out[mix] = sweep(server, mp, index, cell, rates, args.seconds,
                         args.seed)
    server.stop_async()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"config": args.config, "sweeps": out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
