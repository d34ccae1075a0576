"""Telemetry subsystem (DESIGN.md §12): histogram quantile correctness,
request-span completeness through the serving stack, generation-tagged
series reset across hot-swaps, export fidelity, the serve loop's
``serve.*`` spans on the profiler timeline and the batcher's launch-lag,
retire and admission counters.

The serving-path tests drive a private ``MetricsRegistry`` per server (the
views accept one), so nothing here depends on — or pollutes — the
process-wide ``obs.REGISTRY`` other tests record into.
"""

import glob
import json
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.indexing import SwappableEngine
from repro.serving.batcher import CoalescingBatcher, QueueFull
from repro.serving.engine import PathServer
from repro.serving.query_engine import QueryEngine


class _KeyedEngine(QueryEngine):
    """Deterministic 4-key engine (see tests/test_batcher.py)."""

    name = "keyed"
    static_shapes = True
    num_buckets = 4

    def __init__(self, val: float = 0.0):
        self.val = val

    def buckets_of(self, s, t):
        return (np.asarray(s)[:, 0].astype(np.int64) % 4).astype(np.int32)

    def bucket_width(self, bucket: int) -> int:
        return 128

    def batch(self, s, t, bucket: int = 0):
        return (np.asarray(s)[:, 0] + 1000.0 * self.val).astype(np.float32)

    def batch_argmin(self, s, t, bucket: int = 0):
        d = self.batch(s, t, bucket)
        z = np.zeros(len(d), np.int32)
        return d, z, z, z, z


def _pts(xs):
    xs = np.asarray(xs, np.float32)
    return np.stack([xs, np.zeros_like(xs)], axis=1)


def _traced_server(engine, **kw):
    """Server over a private registry with every request head-sampled."""
    tel = obs.Telemetry(registry=obs.MetricsRegistry(), sample_rate=1.0)
    return PathServer(engine, telemetry=tel, **kw), tel


# --------------------------------------------------------------- histograms

def test_histogram_quantiles_exact_on_bucket_bounds():
    """When every sample sits on a bucket bound, rank-based readback must
    agree exactly with numpy's inverted-CDF quantile."""
    bounds = np.array([1.0, 2.0, 4.0, 8.0, 16.0, 32.0])
    rng = np.random.default_rng(5)
    data = rng.choice(bounds, size=257)
    h = obs.Histogram("t_ms", (), bounds=bounds)
    h.record_many(data)
    for q in (0.01, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        want = float(np.quantile(data, q, method="inverted_cdf"))
        assert h.quantile(q) == want, q
    assert h.count == len(data)
    assert h.sum == pytest.approx(float(data.sum()))


def test_histogram_quantile_bounded_by_bucket_resolution():
    """Off-bound samples: the readback overshoots by at most one bucket
    ratio and never leaves the observed [min, max] range."""
    bounds = obs.log_bounds(1e-3, 1e3, per_decade=8)
    ratio = 10.0 ** (1.0 / 8.0)
    rng = np.random.default_rng(11)
    data = rng.lognormal(mean=1.0, sigma=1.2, size=4096)
    h = obs.Histogram("t_ms", (), bounds=bounds)
    h.record_many(data)
    for q in (0.5, 0.9, 0.95, 0.99):
        exact = float(np.quantile(data, q, method="inverted_cdf"))
        got = h.quantile(q)
        assert exact <= got * (1.0 + 1e-9) <= exact * ratio * (1.0 + 1e-9)
        assert data.min() <= got <= data.max()


def test_histogram_merge_matches_combined_recording():
    bounds = np.array([1.0, 2.0, 4.0, 8.0])
    a = obs.Histogram("x", (), bounds=bounds)
    b = obs.Histogram("x", (), bounds=bounds)
    a.record_many([0.5, 1.0, 3.0])
    b.record_many([2.0, 9.0, 100.0])            # overflow bucket included
    both = obs.Histogram("x", (), bounds=bounds)
    both.record_many([0.5, 1.0, 3.0, 2.0, 9.0, 100.0])
    a.merge(b)
    assert a.count == both.count and a.sum == pytest.approx(both.sum)
    assert np.array_equal(a.counts, both.counts)
    assert a.min == both.min and a.max == both.max
    for q in (0.5, 0.95):
        assert a.quantile(q) == both.quantile(q)


def test_head_sampler_is_deterministic():
    s = obs.HeadSampler(rate=0.25, slow_ms=0.0)
    picks = [s.sample() for _ in range(100)]
    assert sum(picks) == 25
    assert picks == [i % 4 == 3 for i in range(100)]   # leaky bucket, no RNG
    assert not any(obs.HeadSampler(rate=0.0).sample() for _ in range(10))
    assert all(obs.HeadSampler(rate=1.0).sample() for _ in range(10))
    assert obs.HeadSampler(rate=0.0, slow_ms=10.0).slow(0.02)
    assert not obs.HeadSampler(rate=0.0, slow_ms=10.0).slow(0.005)


# --------------------------------------------------- span completeness (async)

def test_async_spans_complete_and_telescope():
    """Every request head-sampled: each trace is a closed span tree with
    the full async taxonomy and stage attribution summing to e2e."""
    srv, tel = _traced_server(_KeyedEngine(), batch_size=8)
    b = CoalescingBatcher(srv, autostart=False)
    rng = np.random.default_rng(3)
    xs = rng.integers(0, 64, size=48).astype(np.float32)
    tickets = [b.submit(_pts(xs[i:i + 3]), _pts(xs[i:i + 3]))
               for i in range(0, 48, 3)]
    b.start()
    b.flush()
    assert b.drain(timeout=10)
    b.close()
    for tk in tickets:
        tk.result(timeout=1)
    traces = tel.spans.traces("async")
    # one trace per (dispatched group, ticket) pair — a submit whose keys
    # split across groups is traced once per group it rode in
    assert len(traces) >= len(tickets)
    for tr in traces:
        assert tr.closed and tr.complete(obs.ASYNC_STAGES)
        assert tr.e2e_seconds > 0
        assert abs(tr.stage_sum - tr.e2e_seconds) <= 0.05 * tr.e2e_seconds
        tree = tr.tree()
        # the async path has no rescue or unwind stage (sync spans do)
        assert [c["name"] for c in tree["children"]] == [
            "admission", "queue_wait", "stage", "dispatch", "pipeline_wait",
            "device_join", "reply"]
        assert tree["attrs"]["outcome"] == "ok"
    # stage latency histograms saw every retired group
    for st in ("queue_wait", "device_join", "reply"):
        hs = tel.registry.find("stage_ms", stage=st)
        assert sum(h.count for h in hs) == srv.stats.batches
    lat = tel.registry.find("request_latency_ms")
    assert sum(h.count for h in lat) == len(traces)


def test_requeued_request_span_covers_swap(monkeypatch=None):
    """A group admitted under gen 0 and dispatched after a swap still
    produces a complete span, with the requeue recorded on the trace and
    in the event log."""
    old, new = _KeyedEngine(1.0), _KeyedEngine(2.0)
    sw = SwappableEngine(old)
    srv, tel = _traced_server(sw, batch_size=8)
    b = CoalescingBatcher(srv, autostart=False)
    xs = np.full(8, 4.0) + np.arange(8) * 4
    tk = b.submit(_pts(xs), _pts(xs))            # queued under gen 0
    sw.swap(new)                                 # published before dispatch
    b.start()
    tk.result(timeout=10)
    b.close()
    (tr,) = tel.spans.traces("async")
    assert tr.complete(obs.ASYNC_STAGES)
    assert tr.attrs["requeues"] == 1
    assert tr.attrs["generation"] == 1
    assert abs(tr.stage_sum - tr.e2e_seconds) <= 0.05 * tr.e2e_seconds
    (ev,) = tel.events.events("requeue")
    assert ev["from_gen"] == 0 and ev["to_gen"] == 1


def test_shed_request_traced_with_shed_outcome():
    srv, tel = _traced_server(_KeyedEngine(), batch_size=8)
    b = CoalescingBatcher(srv, autostart=False, max_queue=4, policy="shed")
    b.submit(_pts([0.0, 1.0]), _pts([0.0, 1.0]))
    with pytest.raises(QueueFull):
        b.submit(_pts([2.0, 3.0, 4.0]), _pts([2.0, 3.0, 4.0]))
    b.start()
    b.flush()
    b.drain(timeout=10)
    b.close()
    shed = [t for t in tel.spans.traces("async")
            if t.attrs["outcome"] == "shed"]
    assert len(shed) == 1
    assert shed[0].closed and shed[0].complete(obs.ASYNC_STAGES)
    assert set(shed[0].stages) == set(obs.ASYNC_STAGES)
    (ev,) = tel.events.events("shed")
    assert ev["n"] == 3 and ev["max_queue"] == 4
    assert srv.stats.shed == 3


def test_sync_spans_complete_and_telescope():
    srv, tel = _traced_server(_KeyedEngine(), batch_size=8)
    xs = np.arange(12, dtype=np.float32)
    srv.query(_pts(xs), _pts(xs))
    (tr,) = tel.spans.traces("sync")
    assert tr.closed and tr.complete(obs.SYNC_STAGES)
    assert abs(tr.stage_sum - tr.e2e_seconds) <= 0.05 * tr.e2e_seconds
    (h,) = tel.registry.find("sync_batch_ms")
    assert h.count == 1


# ------------------------------------------- registry across hot-swap (load)

def test_registry_series_reset_per_generation_under_load():
    """Per-bucket series are generation-tagged: after a swap the live view
    rows restart at zero while the retired generation's series stay frozen
    in the registry (the serve totals keep accumulating)."""
    old, new = _KeyedEngine(1.0), _KeyedEngine(2.0)
    sw = SwappableEngine(old)
    srv, tel = _traced_server(sw, batch_size=8)
    b = CoalescingBatcher(srv, autostart=True, max_wait_ms=2.0)
    xs = np.full(8, 4.0) + np.arange(8) * 4      # key 0, one full batch
    b.submit(_pts(xs), _pts(xs)).result(timeout=10)
    pb0 = srv.stats.per_bucket[0]
    assert pb0.queries == 8
    sw.swap(new)
    b.submit(_pts(xs), _pts(xs)).result(timeout=10)
    b.close()
    pb1 = srv.stats.per_bucket[0]
    assert pb1 is not pb0                        # fresh row, new generation
    assert pb1.labels["gen"] == "1" and pb0.labels["gen"] == "0"
    assert pb1.queries == 8                      # restarted, not resumed
    assert pb0.queries == 8                      # retired series frozen
    assert srv.stats.queries == 16               # serve totals accumulate
    assert srv.stats.swaps == 1
    gens = {dict(m.labels)["gen"]
            for m in tel.registry.series("bucket_queries_total")}
    assert gens == {"0", "1"}


# ------------------------------------------------------------------- export

def test_prometheus_export_reproduces_serve_stats():
    srv, tel = _traced_server(_KeyedEngine(), batch_size=8)
    xs = np.arange(20, dtype=np.float32)
    srv.query(_pts(xs), _pts(xs))
    text = obs.prometheus_text(tel.registry)
    parsed = obs.parse_prometheus(text)          # raises on malformed lines

    def total(name):
        return sum(parsed[name].values())

    assert total("serve_queries_total") == srv.stats.queries == 20
    assert total("serve_batches_total") == srv.stats.batches
    assert total("bucket_queries_total") == 20
    assert total("serve_seconds_total") == pytest.approx(
        srv.stats.seconds, rel=1e-9)
    # histograms export cumulative buckets with a +Inf terminal
    inf_rows = [k for k in parsed["sync_batch_ms_bucket"]
                if dict(k)["le"] == "+Inf"]
    assert inf_rows and sum(
        parsed["sync_batch_ms_bucket"][k] for k in inf_rows) == 1
    assert total("sync_batch_ms_count") == 1


def test_json_snapshot_round_trips():
    reg = obs.MetricsRegistry()
    reg.counter("a_total", srv="s").inc(3)
    reg.histogram("b_ms").record(2.5)
    snap = json.loads(obs.json_snapshot(reg, extra_field="x"))
    assert snap["extra_field"] == "x"
    (c,) = snap["counters"]
    assert c["name"] == "a_total" and c["value"] == 3
    (h,) = snap["histograms"]
    assert h["count"] == 1 and h["sum"] == 2.5


def test_event_log_ring_and_jsonl(tmp_path):
    ev = obs.EventLog(capacity=4)
    ev.emit("swap", generation=1, decision="replan")
    ev.emit("drift", drift=0.4)
    for i in range(4):
        ev.emit("shed", n=i)
    assert ev.counts() == {"shed": 4}            # ring evicted the oldest
    assert [e["n"] for e in ev.events("shed")] == [0, 1, 2, 3]
    seqs = [e["seq"] for e in ev.events()]
    assert seqs == sorted(seqs)
    p = tmp_path / "events.jsonl"
    assert ev.dump_jsonl(str(p)) == 4
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert [ln["kind"] for ln in lines] == ["shed"] * 4
    ev.enabled = False
    assert ev.emit("swap") is None
    assert ev.counts() == {"shed": 4}


# ------------------------------------------- serve loop on the profiler clock

LOOP_STAGES = ["serve.stage", "serve.dispatch", "serve.join", "serve.fetch",
               "serve.scatter", "serve.observe"]


def _serve_lines(tmp_path, timeline):
    """One full group served under a CPU profiler session; the ``serve.*``
    host events of each profiler line that has any, in time order."""
    srv, tel = _traced_server(_KeyedEngine(), batch_size=8)
    tel.timeline = timeline
    b = CoalescingBatcher(srv, autostart=False, max_wait_ms=60_000)
    jax.profiler.start_trace(str(tmp_path))
    try:
        b.start()
        time.sleep(0.05)                # the loop waits on an empty queue
        xs = np.full(8, 4.0) + np.arange(8) * 4
        b.submit(_pts(xs), _pts(xs)).result(timeout=10)
        b.close()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for ln in plane.lines:
            evs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                          ev.name) for ev in ln.events
                         if ev.name.startswith("serve."))
            if evs:
                lines.append(evs)
    return lines


def test_timeline_spans_land_on_loop_and_caller_lines(tmp_path):
    lines = _serve_lines(tmp_path, timeline=True)
    assert len(lines) == 2
    (loop,) = [ln for ln in lines if any(e[2] == "serve.wait" for e in ln)]
    (caller,) = [ln for ln in lines if ln is not loop]
    assert [e[2] for e in caller] == ["serve.route", "serve.enqueue"]
    names = [e[2] for e in loop]
    collapsed = [n for i, n in enumerate(names)
                 if i == 0 or n != names[i - 1]]
    # waits on the empty queue, one group through the pipeline, then at
    # most the waits before close
    assert collapsed[:7] == ["serve.wait"] + LOOP_STAGES
    assert set(collapsed[7:]) <= {"serve.wait"}
    for ln in (loop, caller):           # one thread: spans never overlap
        assert all(a[1] <= b[0] for a, b in zip(ln, ln[1:]))


def test_timeline_off_puts_no_serve_event_on_the_trace(tmp_path):
    assert _serve_lines(tmp_path, timeline=False) == []


@pytest.mark.parametrize("reason", ["full", "deadline", "forced"])
def test_launch_lag_runs_from_when_the_group_could_ship(reason):
    """Each query's lag runs from when its group could first ship (the
    batch_size-th arrival, the oldest arrival plus the deadline, or the
    flush latch), or from its own later arrival, to the launch: never
    negative, never more than its queue wait."""
    srv, tel = _traced_server(_KeyedEngine(), batch_size=8)
    wait_ms = 1.0 if reason == "deadline" else 60_000
    b = CoalescingBatcher(srv, autostart=False, max_wait_ms=wait_ms)
    n = 8 if reason == "full" else 3
    tickets = []
    for i in range(n):                  # one key-0 query per submit
        tickets.append(b.submit(_pts([4.0 * i]), _pts([4.0 * i])))
        time.sleep(0.002)
    if reason == "forced":
        before = time.perf_counter()
        b.flush()
        after = time.perf_counter()
    time.sleep(0.01)
    b.start()
    for tk in tickets:
        tk.result(timeout=10)
    b.close()
    st = srv.stats
    assert st.batches == 1 and getattr(st, f"{reason}_flushes") == 1
    traces = sorted(tel.spans.traces("async"), key=lambda tr: tr.t_start)
    arrived = [tr.t_start + tr.stages["admission"] for tr in traces]
    waits = [tr.stages["queue_wait"] for tr in traces]
    launch = arrived[0] + waits[0]
    lag = st.launch_lag_seconds

    def lag_from(ready):
        return sum(launch - max(ready, a) for a in arrived)

    assert 0.0 <= lag <= sum(waits)
    if reason == "full":
        assert lag == pytest.approx(n * (launch - arrived[-1]), abs=1e-6)
    elif reason == "deadline":
        assert lag == pytest.approx(lag_from(arrived[0] + 1e-3), abs=1e-6)
    else:
        assert lag_from(after) - 1e-6 <= lag <= lag_from(before) + 1e-6


def test_admission_counters_count_calls_and_time_routing():
    srv, _ = _traced_server(_KeyedEngine(), batch_size=8)
    b = CoalescingBatcher(srv, autostart=False)
    sizes = [1, 3, 5, 2]
    for k in sizes:
        xs = np.arange(k, dtype=np.float32)
        b.submit(_pts(xs), _pts(xs))
    st = srv.stats
    assert st.submit_calls == len(sizes)
    assert st.submitted == sum(sizes)
    assert 0.0 < st.route_seconds <= st.admit_seconds
    b.start()
    assert b.drain(timeout=10)
    b.close()


def test_retired_batches_equal_batches_after_drain():
    srv, _ = _traced_server(_KeyedEngine(), batch_size=8)
    b = CoalescingBatcher(srv, autostart=True, max_wait_ms=1.0)
    rng = np.random.default_rng(4)
    for _ in range(12):
        xs = rng.integers(0, 64, size=5).astype(np.float32)
        b.submit(_pts(xs), _pts(xs))
    assert b.drain(timeout=10)
    b.close()
    st = srv.stats
    assert st.batches > 1
    assert st.retired_batches == st.batches
    assert st.retire_seconds > 0.0
    assert st.launch_lag_seconds >= 0.0
