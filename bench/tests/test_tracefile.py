"""The trace reduction: device time per jit entry and idle share.

``data/chip_trace.xplane.pb.gz`` is a 0.2 s trace recorded on one TPU v5e
by a ``bench/run.py --trace 1`` run (rooms-L at EHL*-0.3 under ticks of
256 uniform queries, its profiler directory kept by hand, gzip-compressed);
the numbers it must reduce to are those that run printed;
``test_recorded_trace`` pins what ``tracefile.reduce`` reads from it.  The
other tests build small planes by hand.

Run by path: ``python -m pytest bench/tests``.
"""

import os
from types import SimpleNamespace as NS

import pytest

import small  # noqa: F401  (puts the harness on sys.path)
import tracefile

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "chip_trace.xplane.pb.gz")


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _space(device_ops, modules, host=()):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name=tracefile.OP_LINE, events=[_ev(*e) for e in device_ops]),
        NS(name=tracefile.MODULE_LINE, events=[_ev(*e) for e in modules])])
    cpu = NS(name="/host:CPU", lines=[
        NS(name="thread", events=[_ev(*e) for e in host])])
    return NS(planes=[dev, cpu])


def test_busy_is_the_union_of_overlapping_ops():
    pd = _space([("a", 0, 100), ("b", 50, 100), ("c", 400, 100)],
                [("jit__fold_endpoint(3)", 0, 150),
                 ("jit__join_endpoints(4)", 400, 100)])
    red = tracefile.reduce(pd, 1000, ("fold_endpoint", "join_endpoints"))
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(250e-9)
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["entry_s"] == {"fold_endpoint": pytest.approx(150e-9),
                              "join_endpoints": pytest.approx(100e-9)}
    ops = dict(red["ops"])
    assert ops["jit__fold_endpoint(3)/a"] == pytest.approx(100e-9)
    assert ops["jit__join_endpoints(4)/c"] == pytest.approx(100e-9)


def test_events_are_cut_to_the_window():
    pd = _space([("a", -50, 100), ("b", 950, 100)],
                [("jit__fold_endpoint(3)", -50, 1100)])
    red = tracefile.reduce(pd, 1000, ("fold_endpoint",))
    assert red["busy_s"] == pytest.approx(100e-9)
    assert red["entry_s"]["fold_endpoint"] == pytest.approx(1000e-9)


def test_idle_gaps_are_named_by_the_innermost_host_event():
    pd = _space([("a", 0, 100), ("b", 300, 100)], [],
                host=[("serve", 0, 1000), ("bench.submit", 120, 50),
                      ("PjitFunction(_fold_endpoint)", 380, 200)])
    red = tracefile.reduce(pd, 1000, ())
    gaps = dict(red["gaps"])
    # 100-300: middle 200 lies only in "serve"; 400-1000: middle 700 too
    assert gaps == {"serve": pytest.approx(800e-9)}
    pd = _space([("a", 0, 100), ("b", 300, 100)], [],
                host=[("serve", 0, 1000), ("bench.submit", 150, 100)])
    assert dict(tracefile.reduce(pd, 1000, ())["gaps"]) == {
        "bench.submit": pytest.approx(200e-9), "serve": pytest.approx(600e-9)}


def test_no_device_plane_reads_nothing():
    pd = NS(planes=[NS(name="/host:CPU", lines=[])])
    red = tracefile.reduce(pd, 1000, ("fold_endpoint",))
    assert red["devices"] == 0 and red["busy_s"] == 0.0
    assert red["entry_s"] == {}


def test_recorded_trace():
    red = tracefile.reduce(tracefile.load(RECORDED),
                           0.20214630800001032e9,
                           ("fold_endpoint", "join_endpoints"))
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(0.1503834480000103, rel=1e-12)
    assert red["entry_s"] == {
        "fold_endpoint": pytest.approx(0.1433402480000103, rel=1e-12),
        "join_endpoints": pytest.approx(0.007064544, rel=1e-12)}
    # the heaviest operation is a fold fusion, named with its program
    assert red["ops"][0][0].startswith("jit__fold_endpoint(")
