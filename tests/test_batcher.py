"""Continuous batcher: coalescing, flush reasons, backpressure, swap safety.

Most tests drive the :class:`~repro.serving.batcher.CoalescingBatcher`
through a deterministic multi-key fake engine (no device work, no timing
flakiness); the identity tests at the bottom go through the real packed
engine against the synchronous ``PathServer`` path.
"""

import threading
import time

import numpy as np
import pytest

from repro.core.compression import compress_to_fraction
from repro.core.grid import build_ehl
from repro.core.packed import pack_bucketed
from repro.indexing import SwappableEngine
from repro.serving.batcher import CoalescingBatcher, QueueFull
from repro.serving.engine import PathServer
from repro.serving.query_engine import JnpEngine, QueryEngine


class _KeyedEngine(QueryEngine):
    """Deterministic 4-key engine: answer = s.x + 1000 * val.

    Routing depends only on the query (floor of s.x mod 4), so expected
    answers are computable without the engine — coalescing/scatter bugs
    show up as wrong values, not just wrong stats.
    """

    name = "keyed"
    static_shapes = True
    num_buckets = 4

    def __init__(self, val: float = 0.0):
        self.val = val
        self.dispatched = []        # (bucket, rows) per batch() call

    def buckets_of(self, s, t):
        return (np.asarray(s)[:, 0].astype(np.int64) % 4).astype(np.int32)

    def bucket_width(self, bucket: int) -> int:
        return 128

    def batch(self, s, t, bucket: int = 0):
        self.dispatched.append((bucket, len(s)))
        return (np.asarray(s)[:, 0] + 1000.0 * self.val).astype(np.float32)

    def batch_argmin(self, s, t, bucket: int = 0):
        d = self.batch(s, t, bucket)
        z = np.zeros(len(d), np.int32)
        return d, z, z, z, z


def _mk(val=0.0, batch_size=8, **kw):
    srv = PathServer(_KeyedEngine(val), batch_size=batch_size)
    kw.setdefault("autostart", False)
    return srv, CoalescingBatcher(srv, **kw)


def _pts(xs):
    xs = np.asarray(xs, np.float32)
    return np.stack([xs, np.zeros_like(xs)], axis=1)


def _expect(xs, val=0.0):
    return np.asarray(xs, np.float32) + np.float32(1000.0 * val)


# ------------------------------------------------------------ flush reasons

def test_full_batch_flush_and_identity():
    srv, b = _mk(batch_size=8)
    xs = np.full(8, 4.0) + np.arange(8) * 4      # all key 0, fills exactly
    tk = b.submit(_pts(xs), _pts(xs))
    b.start()
    out = tk.result(timeout=10)
    b.close()
    np.testing.assert_array_equal(out, _expect(xs))
    assert srv.stats.full_flushes == 1
    assert srv.stats.deadline_flushes == 0
    assert srv.stats.per_bucket[0].full_flushes == 1
    assert srv.stats.per_bucket[0].slots == 8
    assert srv.stats.per_bucket[0].occupancy == 1.0


def test_deadline_flush_ships_partial_group():
    srv, b = _mk(batch_size=8, max_wait_ms=5.0, autostart=True)
    xs = np.array([4.0, 8.0, 12.0])              # key 0, under batch_size
    t0 = time.perf_counter()
    tk = b.submit(_pts(xs), _pts(xs))
    out = tk.result(timeout=10)                  # only the deadline ships it
    waited = time.perf_counter() - t0
    b.close()
    np.testing.assert_array_equal(out, _expect(xs))
    assert waited >= 0.004                       # not shipped early
    assert srv.stats.deadline_flushes == 1
    assert srv.stats.full_flushes == 0
    assert srv.stats.per_bucket[0].deadline_flushes == 1


def test_forced_flush_overrides_deadline():
    srv, b = _mk(batch_size=8, max_wait_ms=60_000.0, autostart=True)
    tk = b.submit(_pts([4.0]), _pts([4.0]))
    b.flush()
    out = tk.result(timeout=10)                  # long before the deadline
    b.close()
    np.testing.assert_array_equal(out, _expect([4.0]))
    assert srv.stats.forced_flushes == 1
    assert srv.stats.deadline_flushes == 0


def test_mixed_keys_coalesce_across_submits():
    """Interleaved keys from many submits regroup into per-key full batches
    and scatter back to each ticket in submit order."""
    srv, b = _mk(batch_size=8)
    rng = np.random.default_rng(3)
    xs = rng.integers(0, 64, size=48).astype(np.float32)   # keys 0..3 mixed
    tickets = [b.submit(_pts(xs[i:i + 3]), _pts(xs[i:i + 3]))
               for i in range(0, 48, 3)]
    b.start()
    b.flush()
    assert b.drain(timeout=10)
    b.close()
    for i, tk in enumerate(tickets):
        np.testing.assert_array_equal(tk.result(timeout=1),
                                      _expect(xs[3 * i:3 * i + 3]))
    eng = srv.engine
    # coalescing: every dispatched batch holds a single key's queries
    keys = (np.asarray(xs).astype(np.int64) % 4)
    per_key = {k: int((keys == k).sum()) for k in range(4)}
    batches = sum(-(-n // 8) for n in per_key.values())
    assert len(eng.dispatched) == batches
    for k, bstats in srv.stats.per_bucket.items():
        assert bstats.admitted == per_key[k]
        assert bstats.queries == per_key[k]
        assert bstats.occupancy <= 1.0


def test_argmin_tickets_round_trip():
    srv, b = _mk(batch_size=8)
    xs = np.array([4.0, 5.0, 6.0])
    tk = b.submit(_pts(xs), _pts(xs), want_argmin=True)
    b.start()
    b.flush()
    out = tk.result(timeout=10)
    b.close()
    assert len(out) == 5
    np.testing.assert_array_equal(out[0], _expect(xs))
    # distance-only and argmin groups must not share a dispatch even on
    # the same routing key
    assert srv.stats.batches == 3       # keys 0,1,2 x one argmin group each


# ------------------------------------------------------------- backpressure

def test_backpressure_shed_raises_queue_full():
    srv, b = _mk(batch_size=8, max_queue=4, policy="shed")
    b.submit(_pts([0.0, 1.0]), _pts([0.0, 1.0]))
    with pytest.raises(QueueFull):
        b.submit(_pts([2.0, 3.0, 4.0]), _pts([2.0, 3.0, 4.0]))
    assert srv.stats.shed == 3
    assert srv.stats.submitted == 2          # rejected queries not admitted
    assert b.queue_depth == 2


def test_backpressure_block_waits_for_drain():
    srv, b = _mk(batch_size=4, max_queue=4, policy="block",
                 max_wait_ms=5.0, autostart=True)
    xs = np.arange(12, dtype=np.float32) * 4     # key 0: three full batches
    done = []

    def feed():
        for lo in range(0, 12, 4):               # 2nd/3rd chunk must wait
            done.append(b.submit(_pts(xs[lo:lo + 4]), _pts(xs[lo:lo + 4])))

    th = threading.Thread(target=feed)
    th.start()
    th.join(timeout=10)
    assert not th.is_alive()
    out = np.concatenate([tk.result(timeout=10) for tk in done])
    b.close()
    np.testing.assert_array_equal(out, _expect(xs))
    assert srv.stats.admission_waits >= 1
    assert srv.stats.queue_depth_peak <= 4


# ---------------------------------------------------------------- pipeline

def test_double_buffer_keeps_two_groups_in_flight():
    srv, b = _mk(batch_size=8, depth=2)
    xs = np.full(24, 4.0) + np.arange(24) * 4    # key 0: three full groups
    tk = b.submit(_pts(xs), _pts(xs))
    b.start()
    out = tk.result(timeout=10)
    b.close()
    np.testing.assert_array_equal(out, _expect(xs))
    assert srv.stats.pipeline_peak == 2
    assert srv.stats.full_flushes == 3


# -------------------------------------------------------------- swap safety

def test_superseded_group_requeues_without_slot_accounting():
    """A group admitted under generation 0 but dispatched after a swap is
    re-routed under the live generation: answered by the new engine, one
    requeue counted, and the per-bucket slot accounting never sees the
    aborted dispatch (occupancy stays <= 1)."""
    old, new = _KeyedEngine(1.0), _KeyedEngine(2.0)
    sw = SwappableEngine(old)
    srv = PathServer(sw, batch_size=8)
    b = CoalescingBatcher(srv, autostart=False)
    xs = np.full(8, 4.0) + np.arange(8) * 4
    tk = b.submit(_pts(xs), _pts(xs))            # queued under gen 0
    sw.swap(new)                                 # published before dispatch
    b.start()
    out = tk.result(timeout=10)
    b.close()
    np.testing.assert_array_equal(out, _expect(xs, 2.0))   # new engine wins
    assert old.dispatched == []                  # stale gen never dispatched
    assert srv.stats.requeued_batches == 1
    assert srv.stats.generation == 1 and srv.stats.swaps == 1
    bstats = srv.stats.per_bucket[0]
    assert bstats.batches == 1 and bstats.slots == 8
    assert bstats.occupancy <= 1.0


def test_inflight_batch_finishes_on_pinned_generation():
    """A swap published while a batch computes: the batch finishes on the
    engine it pinned (old answers) and is counted stale, the next group
    serves on the new generation."""
    old, new = _KeyedEngine(1.0), _KeyedEngine(2.0)
    sw = SwappableEngine(old)
    srv = PathServer(sw, batch_size=8)
    swap_once = []

    orig = old.batch

    def swapping_batch(s, t, bucket=0):
        out = orig(s, t, bucket)
        if not swap_once:
            swap_once.append(True)
            sw.swap(new)                 # mid-dispatch publish
        return out

    old.batch = swapping_batch
    b = CoalescingBatcher(srv, autostart=False)
    xs = np.full(8, 4.0) + np.arange(8) * 4
    tk1 = b.submit(_pts(xs), _pts(xs))
    b.start()
    out1 = tk1.result(timeout=10)
    tk2 = b.submit(_pts(xs), _pts(xs))
    out2 = tk2.result(timeout=10)
    b.close()
    np.testing.assert_array_equal(out1, _expect(xs, 1.0))  # pinned gen 0
    np.testing.assert_array_equal(out2, _expect(xs, 2.0))  # live gen 1
    assert srv.stats.stale_batches == 1
    assert srv.stats.swaps == 1 and srv.stats.generation == 1
    for bstats in srv.stats.per_bucket.values():
        assert bstats.occupancy <= 1.0


# -------------------------------------------------------- real-engine path

@pytest.fixture(scope="module")
def real_server(scene_s, graph_s, hl_s):
    idx = build_ehl(scene_s, 2.0, graph=graph_s, hl=hl_s)
    compress_to_fraction(idx, 0.3)
    srv = PathServer(JnpEngine(pack_bucketed(idx)), batch_size=16)
    srv.warmup(paths=True)
    return srv


def test_async_matches_sync_bitwise(real_server, queries_s):
    srv = real_server
    s = queries_s.s.astype(np.float32)
    t = queries_s.t.astype(np.float32)
    ref = srv.query(s, t)
    tickets = [srv.submit(s[i], t[i]) for i in range(len(s))]
    srv.flush()
    assert srv.drain(timeout=60)
    got = np.concatenate([tk.result(timeout=1) for tk in tickets])
    srv.stop_async()
    np.testing.assert_array_equal(ref, got)      # bitwise, padding-invariant
    for bstats in srv.stats.per_bucket.values():
        assert bstats.occupancy <= 1.0


def test_async_argmin_matches_sync_bitwise(real_server, queries_s):
    srv = real_server
    s = queries_s.s[:12].astype(np.float32)
    t = queries_s.t[:12].astype(np.float32)
    ref = srv._dispatch(s, t, want_argmin=True)
    tk = srv.submit(s, t, want_argmin=True)
    srv.flush()
    got = tk.result(timeout=60)
    srv.stop_async()
    assert len(got) == 5
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


@pytest.mark.parametrize("want_argmin", [False, True],
                         ids=["dist", "argmin"])
def test_launch_is_one_transfer_and_two_programs(real_server, queries_s,
                                                 monkeypatch, want_argmin):
    """One ``_launch`` stages both endpoint sides in one host->device
    transfer and dispatches one fold and one join — nothing else crosses
    to the device (implicit transfers are refused), and the launched batch
    answers bitwise what the synchronous path answers."""
    import collections

    import jax
    from repro.core import packed

    srv = real_server
    s = queries_s.s.astype(np.float32)
    t = queries_s.t.astype(np.float32)
    keys = srv.engine.buckets_of(s, t)
    m = keys == keys[0]                 # one routing key: one chunk
    s, t = s[m], t[m]
    b = CoalescingBatcher(srv, autostart=False)
    tk = b.submit(s, t, want_argmin=want_argmin)
    b.flush()
    chunk = b._pop_ready(block=False)
    assert chunk is not None and len(chunk[3]) == len(s)

    calls = collections.Counter()

    def counted(name, fn):
        def wrapper(*args, **kw):
            calls[name] += 1
            return fn(*args, **kw)
        return wrapper

    monkeypatch.setattr(jax, "device_put",
                        counted("device_put", jax.device_put))
    for fn in (packed._fold_endpoint, packed._join_endpoints):
        monkeypatch.setattr(packed, fn.__name__, counted(fn.entry, fn))
    with jax.transfer_guard_host_to_device("disallow"):
        flight = b._launch(*chunk)
    assert calls == {"device_put": 1, "fold_endpoint": 1,
                     "join_endpoints": 1}
    monkeypatch.undo()
    b._retire(flight)
    got = tk.result(timeout=1)
    ref = srv._dispatch(s, t, want_argmin=want_argmin)
    assert len(ref) == (5 if want_argmin else 1)
    for r, g in zip(ref, got if want_argmin else (got,)):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    b.close(drain=False)


@pytest.mark.parametrize("want_argmin", [False, True],
                         ids=["dist", "argmin"])
@pytest.mark.parametrize("bucket", (0, 1))   # the lattice's bucket ladder
def test_retire_copies_one_array_to_the_host(real_server, queries_s,
                                             monkeypatch, bucket,
                                             want_argmin):
    """Retiring one batch copies one device array to the host, argmin or
    not (``fetched_arrays`` and the ``np.asarray`` calls on device arrays
    inside ``_retire`` agree), and its tickets read bitwise what the
    synchronous ``batch`` / ``batch_argmin`` answer."""
    import jax

    srv = real_server
    s = queries_s.s.astype(np.float32)
    t = queries_s.t.astype(np.float32)
    m = srv.engine.buckets_of(s, t) == bucket
    s, t = s[m][:srv.batch_size], t[m][:srv.batch_size]
    assert len(s)
    b = CoalescingBatcher(srv, autostart=False)
    tk = b.submit(s, t, want_argmin=want_argmin)
    b.flush()
    flight = b._launch(*b._pop_ready(block=False))
    assert flight.key == bucket

    copies = []
    asarray = np.asarray

    def counted(a, *args, **kw):
        if isinstance(a, jax.Array):
            copies.append(a.shape)
        return asarray(a, *args, **kw)

    before = srv.stats.fetched_arrays
    monkeypatch.setattr(np, "asarray", counted)
    b._retire(flight)
    monkeypatch.undo()
    assert len(copies) == srv.stats.fetched_arrays - before == 1
    got = tk.result(timeout=1)
    with srv.engine.pin() as eng:
        ref = (eng.batch_argmin(s, t, bucket=bucket) if want_argmin
               else (eng.batch(s, t, bucket=bucket),))
    for r, g in zip(ref, got if want_argmin else (got,)):
        r = np.asarray(r)
        assert g.dtype == r.dtype
        np.testing.assert_array_equal(g.view(np.uint8), r.view(np.uint8))
    b.close(drain=False)
