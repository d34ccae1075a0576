"""The serve loop's records: device idle time split by the loop's
``serve.*`` spans, the counter snapshot (``measure.snapshot``), and the six
readers built on them.

Hand-built planes as in ``test_tracefile.py``; the recorded chip trace
(``data/chip_trace.xplane.pb.gz``) predates the spans.

Run by path: ``python -m pytest bench/tests``.
"""

import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

import small  # noqa: F401  (puts the harness on sys.path)
import measure
import serveloop
import spec
import tracefile

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "chip_trace.xplane.pb.gz")
READERS = ("launch_lag_ms.tail", "retire_us.tail", "admit_us.tail",
           "route_us.tail", "idle_wait_share.tail", "idle_host_share.tail")


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _space(device_ops, loop=(), caller=()):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name=tracefile.OP_LINE, events=[_ev(*e) for e in device_ops]),
        NS(name=tracefile.MODULE_LINE, events=[])])
    cpu = NS(name="/host:CPU", lines=[
        NS(name="python", events=[_ev(*e) for e in caller]),
        NS(name="python", events=[_ev(*e) for e in loop])])
    return NS(planes=[dev, cpu])


def test_idle_split_by_exact_overlap_at_gap_edges():
    # device busy 0-100 and 300-400 of a 1000 ns window: idle 100-300 and
    # 400-1000 (800 ns)
    pd = _space([("a", 0, 100), ("b", 300, 100)], loop=[
        ("serve.join", 50, 100),        # 50-150: idle only from 100
        ("serve.wait", 150, 100),       # 150-250: all idle
        ("serve.stage", 250, 100),      # 250-350: idle until 300
        ("serve.fetch", 500, 20),       # all idle
        ("serve.wait", 900, 200)])      # cut at the window's end
    split = serveloop.split_idle(pd, 1000)
    assert split["idle"] == pytest.approx(800e-9)
    assert split["wait"] == pytest.approx(200e-9)
    assert split["host"] == pytest.approx(70e-9)
    assert split["join"] == pytest.approx(50e-9)


def test_spans_on_another_thread_are_ignored():
    # the loop is the line with the most serve-loop spans; the caller's
    # spans, and a stray loop-named span on a third line, count for nothing
    pd = _space([("a", 0, 100)],
                loop=[("serve.wait", 100, 100), ("serve.join", 700, 10)],
                caller=[("serve.route", 200, 100),
                        ("serve.enqueue", 300, 200)])
    pd.planes[1].lines.append(NS(name="python", events=[
        _ev("serve.stage", 500, 100)]))
    split = serveloop.split_idle(pd, 1000)
    assert split["wait"] == pytest.approx(100e-9)
    assert split["join"] == pytest.approx(10e-9)
    assert split["host"] == 0.0


def test_a_loop_that_never_waits_is_found_by_its_other_spans():
    pd = _space([("a", 0, 100)], loop=[("serve.stage", 100, 50),
                                       ("serve.fetch", 200, 50)])
    split = serveloop.split_idle(pd, 1000)
    assert split["wait"] == 0.0
    assert split["host"] == pytest.approx(100e-9)


def test_shares_are_disjoint_and_within_the_idle_share():
    rng = np.random.default_rng(0)
    ops = [("op", s, d) for s, d in zip(np.sort(rng.uniform(0, 1e6, 200)),
                                         rng.uniform(10, 4000, 200))]
    names = serveloop.WAIT + serveloop.HOST + serveloop.JOIN
    loop = [(str(rng.choice(names)), s, d)
            for s, d in zip(np.sort(rng.uniform(0, 1e6, 300)),
                            rng.uniform(10, 6000, 300))]   # some overlap
    pd = _space(ops, loop=loop)
    split = serveloop.split_idle(pd, 1e6)
    red = tracefile.reduce(pd, 1e6)
    idle = red["window_s"] - red["busy_s"]
    assert split["idle"] == pytest.approx(idle, rel=1e-9)
    parts = split["wait"] + split["host"] + split["join"]
    assert parts <= split["idle"] * (1 + 1e-12)
    m = _measurement(red={**red, "serve_idle": split})
    w = spec.reader("idle_wait_share.tail")(m)
    h = spec.reader("idle_host_share.tail")(m)
    assert w > 0 and h > 0
    assert w + h <= spec.reader("idle_share.tail")(m) + 1e-9
    # no idle nanosecond is counted twice where spans overlap: the parts
    # add up to the idle time under the union of every span
    union = _space(ops, loop=[("serve.wait",) + e[1:] for e in loop])
    assert parts == pytest.approx(serveloop.split_idle(union, 1e6)["wait"],
                                  rel=1e-12)


def test_recorded_trace_has_no_loop_spans():
    pd = tracefile.load(RECORDED)
    assert serveloop.split_idle(pd, 0.20214630800001032e9) is None
    red = tracefile.reduce(pd, 0.20214630800001032e9,
                           ("fold_endpoint", "join_endpoints"))
    assert set(red) == {"devices", "window_s", "busy_s", "entry_s", "ops",
                        "gaps"}
    m = _measurement(red=red)
    for name in ("idle_wait_share.tail", "idle_host_share.tail"):
        assert spec.reader(name)(m) is None


def _measurement(counters=None, red=None):
    red = red if red is not None else {"window_s": 1.0, "busy_s": 0.5,
                                       "devices": 1, "entry_s": {}}
    traced = measure.Traced(0.0, 1.0, counters or {}, np.zeros(0), 0, [],
                            red, 256, 1000, {}, [])
    return measure.Measurement(1.0, 1.0, 0, [1.0], traced)


COUNTERS = ("launch_lag_seconds", "retire_seconds", "retired_batches",
            "submit_calls", "admit_seconds", "route_seconds")


def _server(**counters):
    """A server whose ``ServeStats`` declares just ``counters``."""
    stats = type("Stats", (), {"_COUNTERS": dict.fromkeys(counters),
                               "per_bucket": {}, **counters})
    return NS(stats=stats())


def test_snapshot_of_a_program_without_the_counters_reads_none():
    old = _server(queries=5, batches=1)
    snap = measure.snapshot(old)
    assert {k: snap.get(k) for k in COUNTERS} == {k: None for k in COUNTERS}
    d = measure.delta(snap, snap)
    assert (d["queries"], d["batches"], d["jit_traces"]) == (0, 0, 0)
    for name in READERS:
        assert spec.reader(name)(_measurement(d)) is None


def test_snapshot_carries_every_counter_the_program_declares():
    from repro.serving.engine import ServeStats

    snap = measure.snapshot(NS(stats=ServeStats()))
    assert set(ServeStats._COUNTERS) <= set(snap)
    assert set(COUNTERS) <= set(snap)
    # a counter added to the program later is carried by name, no edit
    d = measure.delta(measure.snapshot(_server(queries=1, frobs=2)),
                      measure.snapshot(_server(queries=4, frobs=7)))
    assert (d["queries"], d["frobs"]) == (3, 5)
    # one the other side lacks reads None
    d = measure.delta(measure.snapshot(_server(queries=1)),
                      measure.snapshot(_server(queries=4, frobs=7)))
    assert d["frobs"] is None


def test_readers_read_none_when_their_counters_did_not_move():
    zero = {k: 0 for k in COUNTERS}
    m = _measurement({"queries": 0, "batches": 0, **zero})
    for name in READERS:
        assert spec.reader(name)(m) is None


def test_readers_divide_the_interval_deltas():
    st0 = _server(queries=0, batches=0, launch_lag_seconds=1.0,
                  retire_seconds=2.0, retired_batches=10, submit_calls=100,
                  admit_seconds=0.5, route_seconds=0.1)
    st1 = _server(queries=400, batches=100, launch_lag_seconds=1.8,
                  retire_seconds=2.05, retired_batches=110,
                  submit_calls=2100, admit_seconds=0.9, route_seconds=0.3)
    d = measure.delta(measure.snapshot(st0), measure.snapshot(st1))
    m = _measurement(d)
    got = {n: spec.reader(n)(m) for n in READERS[:4]}
    assert got == {
        "launch_lag_ms.tail": pytest.approx(1e3 * 0.8 / 400),
        "retire_us.tail": pytest.approx(1e6 * 0.05 / 100),
        "admit_us.tail": pytest.approx(1e6 * 0.4 / 2000),
        "route_us.tail": pytest.approx(1e6 * 0.2 / 2000)}
