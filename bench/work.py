"""The algorithm's work per served batch, and the chip's published peaks.

Operations and bytes are counted from shapes, for the work EHL* must do
whichever engine runs it (the jnp references XLA fuses, or the Pallas
kernels), so both engines are held to the same count:

* fold (``fold_endpoint``, once per endpoint side): every label slot of
  the side's region against every padded obstacle edge, 20 operations a
  segment test; it reads each gathered slot (hub id, via x/y, distance,
  via id: 20 bytes) and writes the masked triple (12 bytes).
* join (``join_endpoints``): W x W hub pairs per query at 4 operations,
  plus the direct segment's test against every edge; it reads both sides'
  hub ids and distances (8 bytes a slot) and writes one distance.

These are the terms of the program's roofline attribution bench
(``benchmarks/bench_attribution.py``: join B*L*L*4, visibility N*E*20,
gather B*W*20 bytes), counted here against the chip's published peaks.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SEG_OPS = 20         # operations per segment-edge test
PAIR_OPS = 4         # operations per hub pair of the join


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"add them to peaks.json with their source")
    return table[device_kind]


def padded_edges(num_edges: int, lane: int = 128) -> int:
    """Edges as the device holds them: lane-aligned, one spare slot."""
    return -(-(num_edges + 1) // lane) * lane


def fold(batch: int, width: int, edges: int) -> tuple[float, float]:
    """(operations, bytes) of one side's fold of one batch."""
    ops = float(batch) * width * edges * SEG_OPS
    nbytes = float(batch) * width * (20 + 12)
    return ops, nbytes


def join(batch: int, width: int, edges: int) -> tuple[float, float]:
    """(operations, bytes) of the join of one batch."""
    ops = float(batch) * width * width * PAIR_OPS \
        + float(batch) * edges * SEG_OPS
    nbytes = float(batch) * (2 * width * 8 + 2 * 8 + 4)
    return ops, nbytes


def roofline_share(ops: float, nbytes: float, seconds: float,
                   pk: dict) -> float | None:
    """Least time the chip could take over the time taken, in %."""
    if seconds <= 0 or ops <= 0:
        return None
    least = max(ops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds
