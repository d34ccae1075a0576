#!/usr/bin/env python3
"""The control of ``correct``: bfloat16 labels in the program's place must
come out not correct.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

The configuration states float32 label slabs; the nearest precision below
is bfloat16.  For each seed this draws the cell's traffic as a run does,
takes a sample of the size a run checks (``check.SAMPLE`` drawn from the
seed, plus the ``check.LONGEST`` longest by straight-line length), answers
it with ``reference.Reference(mp, labels="bfloat16")`` (label distances in
bfloat16, query-time sums in float32, the query points as given) and hands
those answers to ``check.compare`` and ``check.passed`` as a run hands the
program's.  It prints each seed's compared numbers and ``correct``, which
must be false; it needs no chip and runs no server.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import check  # noqa: E402
import reference  # noqa: E402
import scene  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402


def run(cell, seed: int, seconds: float, mp=None) -> dict:
    """The control's compared numbers on one seed's sample."""
    mp = mp if mp is not None else scene.make_map(cell.config)
    plan = traffic.make_plan(mp, cell.mix, cell.config, cell.rate, seconds,
                             seed)
    rng = np.random.default_rng([seed, 0x5A3])
    pool = len(plan.s)
    pick = rng.choice(pool, size=min(check.SAMPLE, pool), replace=False)
    # the longest by straight-line length (a run takes its longest answers)
    span = np.linalg.norm(plan.s - plan.t, axis=-1)
    pick = np.unique(np.concatenate([pick, np.argsort(-span)[:check.LONGEST]]))
    s, t = plan.s[pick].astype(np.float64), plan.t[pick].astype(np.float64)
    answers = reference.Reference(mp, labels="bfloat16").distances(s, t)
    return check.compare(reference.Reference(mp), s, t, answers, 0,
                         cell.limit)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    mp = scene.make_map(cell.config)
    for seed in args.seeds:
        compared = run(cell, seed, cell.run_seconds, mp)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": check.passed(compared),
                          "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
