"""The ``fetches_per_batch.tail`` reader: device arrays copied to the host
per retired batch, from the counter deltas ``measure.delta`` carries.

Run by path: ``python -m pytest bench/tests``.
"""

from types import SimpleNamespace as NS

import numpy as np
import pytest

import small  # noqa: F401  (puts the harness on sys.path)
import measure
import spec

READ = spec.reader("fetches_per_batch.tail")


def _server(**counters):
    """A server whose ``ServeStats`` declares just ``counters``."""
    stats = type("Stats", (), {"_COUNTERS": dict.fromkeys(counters),
                               "per_bucket": {}, **counters})
    return NS(stats=stats())


def _read(a, b):
    d = measure.delta(measure.snapshot(a), measure.snapshot(b))
    traced = measure.Traced(0.0, 1.0, d, np.zeros(0), 0, [],
                            {"window_s": 1.0, "busy_s": 0.5, "devices": 1,
                             "entry_s": {}}, 256, 1000, {}, [])
    return READ(measure.Measurement(1.0, 1.0, 0, [1.0], traced))


@pytest.mark.parametrize("per_batch", [5, 1])
def test_reads_arrays_per_retired_batch(per_batch):
    a = _server(retired_batches=10, fetched_arrays=10 * per_batch)
    b = _server(retired_batches=130, fetched_arrays=130 * per_batch)
    assert _read(a, b) == pytest.approx(float(per_batch))


def test_a_program_without_the_counter_reads_none():
    a = _server(retired_batches=10)
    b = _server(retired_batches=130)
    assert _read(a, b) is None


def test_no_batch_retired_reads_none():
    a = _server(retired_batches=10, fetched_arrays=10)
    assert _read(a, a) is None


def test_the_program_declares_the_counter():
    from repro.serving.engine import ServeStats

    snap = measure.snapshot(NS(stats=ServeStats()))
    assert snap["fetched_arrays"] == 0
