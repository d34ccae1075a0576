"""Bucketed packed layout: memory win, slot accounting, serving behavior.

Engine-identity checks (host oracle / single-slab bitwise / backend
agreement / argmin parity) live in the parameterized conformance table in
``test_conformance.py``; this module keeps the layout- and serving-
specific properties:

* bucket-width/slot accounting consistency and the device-byte win over
  the single slab (plus exact analytic estimators);
* bucket dispatch covers every query and agrees with the per-bucket entry;
* the stacked launch: one ``fold_endpoint`` over both endpoint sides is
  row-independent, and the staged path equals the synchronous one;
* PathServer bucket routing + batched path extraction over the engines.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.packed import (HUB_PAD, bucket_width, dispatch_buckets,
                               pack_bucketed, pack_index,
                               query_batch_at_bucket,
                               query_batch_bucketed, slab_device_bytes)
from repro.core.query import path_length, query
from repro.serving.engine import PathServer


@pytest.fixture(scope="module")
def compressed(compressed_s):
    """Alias of the session-scoped compressed index + f64 truth."""
    return compressed_s


def test_bucket_width_is_pow2_multiple_of_lane():
    assert bucket_width(1, lane=128) == 128
    assert bucket_width(128, lane=128) == 128
    assert bucket_width(129, lane=128) == 256
    assert bucket_width(700, lane=128) == 1024


def test_bucketed_layout_consistency(compressed):
    idx, _ = compressed
    bx = pack_bucketed(idx)
    counts = idx.packed_label_counts()
    assert bx.num_regions == len(counts)
    rb = np.asarray(bx.region_bucket)
    rr = np.asarray(bx.region_row)
    for i, c in enumerate(counts):
        k, row = int(rb[i]), int(rr[i])
        # region sits in the smallest bucket that holds it, fully copied
        assert bx.widths[k] == bucket_width(max(1, int(c)))
        hub_row = np.asarray(bx.hub_ids[k][row])
        assert (hub_row != HUB_PAD).sum() == c
    used, total = bx.label_slots()
    assert used == int(counts.sum())
    assert used <= total


def test_bucketed_device_bytes_at_most_single_slab(compressed):
    idx, _ = compressed
    pk = pack_index(idx)
    bx = pack_bucketed(idx)
    assert bx.device_bytes() <= pk.device_bytes()
    # the analytic estimates (used to report layout footprints without
    # materializing them) are exact
    assert slab_device_bytes(idx) == pk.device_bytes()
    from repro.core.packed import bucketed_device_bytes
    assert bucketed_device_bytes(idx) == bx.device_bytes()
    # padding waste accounting agrees with the byte win
    used_b, total_b = bx.label_slots()
    used_p, total_p = pk.label_slots()
    assert used_b == used_p            # same live labels, different padding
    assert total_b <= total_p


def test_bucketed_random_points_match_oracle(compressed, scene_s, graph_s):
    """Property-style sweep: fresh random free points, several seeds."""
    from repro.core.geometry import random_free_points
    idx, _ = compressed
    bx = pack_bucketed(idx)
    for seed in (3, 17, 91):
        rng = np.random.default_rng(seed)
        s = random_free_points(scene_s, 16, rng)
        t = random_free_points(scene_s, 16, rng)
        truth = np.array([query(idx, si, ti, want_path=False)[0]
                          for si, ti in zip(s, t)])
        d = query_batch_bucketed(bx, s, t)
        np.testing.assert_allclose(d, truth, rtol=1e-4, atol=1e-4)


def test_dispatch_buckets_cover_every_query(compressed, queries_s):
    idx, _ = compressed
    bx = pack_bucketed(idx)
    b = dispatch_buckets(bx, queries_s.s, queries_s.t)
    assert b.shape == (len(queries_s.s),)
    assert (b >= 0).all() and (b < bx.num_buckets).all()
    # per-bucket entry point agrees with the routed wrapper on its own group
    for k in np.unique(b):
        m = b == k
        d_k = np.asarray(query_batch_at_bucket(
            bx, jnp.asarray(queries_s.s[m].astype(np.float32)),
            jnp.asarray(queries_s.t[m].astype(np.float32)), bucket=int(k)))
        d_r = query_batch_bucketed(bx, queries_s.s[m], queries_s.t[m])
        np.testing.assert_array_equal(d_r, d_k)


def test_path_server_bucket_routing(compressed, queries_s):
    idx, truth = compressed
    bx = pack_bucketed(idx)
    srv = PathServer(bx, batch_size=16)
    srv.warmup()
    d = srv.query(queries_s.s, queries_s.t)
    np.testing.assert_allclose(d, truth, rtol=1e-4, atol=1e-4)
    assert srv.stats.queries == len(truth)
    per = srv.stats.per_bucket
    assert per and sum(b.queries for b in per.values()) == len(truth)
    for b in per.values():
        assert 0.0 < b.occupancy <= 1.0
        assert b.width in bx.widths


def test_path_server_paths_are_optimal(compressed, queries_s):
    idx, truth = compressed
    bx = pack_bucketed(idx)
    srv = PathServer(bx, batch_size=16)
    d, paths = srv.query_paths(queries_s.s, queries_s.t, host_index=idx)
    np.testing.assert_allclose(d, truth, rtol=1e-4, atol=1e-4)
    for di, p in zip(d, paths):
        if np.isfinite(di):
            assert abs(path_length(p) - di) < 1e-3
        else:
            assert p == []


HOST_TOL = 1e-4      # f32 engine vs f64 oracle (test_conformance.py)
BUCKETS = (0, 1)     # the lattice index's whole bucket ladder (128, 256)


@pytest.mark.parametrize("want_argmin", [False, True],
                         ids=["dist", "argmin"])
@pytest.mark.parametrize("layout", ["f32", "bf16"])
@pytest.mark.parametrize("bucket", BUCKETS)
def test_stacked_fold_is_row_independent(conformance, bucket, layout,
                                         want_argmin):
    """One fold over the stacked [s; t] batch is, half for half and bit for
    bit, the fold of [s; s] and of [t; t]; a staged launch equals the
    synchronous batch bitwise; and the answers match the host oracle."""
    from repro.core.packed import _fold_endpoint, stack_endpoints
    from repro.serving.query_engine import JnpEngine

    bx = conformance.bucketed(layout)
    assert bx.num_buckets == len(BUCKETS)
    eng = JnpEngine(bx)
    s, t = conformance.s, conformance.t

    ms, mt = _fold_endpoint(bx, stack_endpoints(s, t), bucket=bucket)
    ss, _ = _fold_endpoint(bx, stack_endpoints(s, s), bucket=bucket)
    _, tt = _fold_endpoint(bx, stack_endpoints(t, t), bucket=bucket)
    for got, want in zip(ms + mt, ss + tt):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # the dispatch contract: every endpoint lives in a bucket <= the batch's
    m = eng.buckets_of(s, t) <= bucket
    assert m.any()
    sb, tb = s[m], t[m]
    staged = eng.fetch(eng.dispatch_staged(eng.stage(sb, tb), bucket=bucket,
                                           want_argmin=want_argmin), len(sb))
    sync = (eng.batch_argmin(sb, tb, bucket=bucket) if want_argmin
            else (eng.batch(sb, tb, bucket=bucket),))
    assert len(staged) == len(sync) == (5 if want_argmin else 1)
    for got, want in zip(staged, sync):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    d = np.asarray(staged[0])
    truth = conformance.run("host", "f32")[0][m]
    fin = np.isfinite(truth)
    assert np.array_equal(fin, np.isfinite(d))
    np.testing.assert_allclose(d[fin], truth[fin], rtol=HOST_TOL,
                               atol=HOST_TOL + 2.0 * conformance.qerr(layout))



ARGMIN_DTYPES = (np.float32, np.bool_, np.int32, np.int32, np.int32)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_packed_argmin_join_unpacks_bitwise(conformance, bucket):
    """An f32 argmin join is one packed [5, B] int32 array that unpacks,
    bit for bit (``d``'s inf included), into the five outputs the plain
    join computes from the same folded inputs; the library callers return
    those five columns, in the engine dtypes."""
    from repro.core.packed import (_edges_of, _fold_endpoint,
                                   _join_endpoints, _segvis, join_masked,
                                   query_batch_argmin, stack_endpoints,
                                   unpack_argmin)
    from repro.serving.query_engine import JnpEngine

    def plain(idx, s, t, bucket, hide=None):
        """Fold, then the unpacked join's five outputs and the packed one;
        ``hide``: a row whose s side reaches no label."""
        pts = stack_endpoints(s, t)
        ms, mt = _fold_endpoint(idx, pts, bucket=bucket)
        if hide is not None:
            ms = (ms[0], ms[1].at[hide].set(jnp.inf), ms[2])
        covis = _segvis(pts[0], pts[1], _edges_of(idx), False)
        ref = join_masked(ms, mt, pts[0], pts[1], covis, want_argmin=True)
        return ([np.asarray(r) for r in ref],
                _join_endpoints(idx, ms, mt, pts, want_argmin=True))

    bx = conformance.bucketed("f32")
    s, t = conformance.s, conformance.t
    # every query at this bucket (rows of a wider bucket read inf), one of
    # them not co-visible and cut off from its labels
    ref, _ = plain(bx, s, t, bucket)
    ref, packed = plain(bx, s, t, bucket, hide=int(np.argmin(ref[1])))
    assert packed.shape == (5, len(s)) and packed.dtype == jnp.int32
    assert np.isinf(ref[0]).any() and np.isfinite(ref[0]).any()
    got = unpack_argmin(packed)
    np.testing.assert_array_equal(got[0].view(np.int32),
                                  ref[0].view(np.int32))
    for g, r, dt in zip(got, ref, ARGMIN_DTYPES):
        assert g.dtype == dt
        np.testing.assert_array_equal(g, r)

    # the library callers: the same five host columns, same dtypes
    eng = JnpEngine(bx)
    m = eng.buckets_of(s, t) <= bucket
    pk = pack_index(conformance.idx)
    cases = ((query_batch_at_bucket(bx, s[m], t[m], bucket,
                                    want_argmin=True),
              plain(bx, s[m], t[m], bucket)[0]),
             (eng.batch_argmin(s[m], t[m], bucket=bucket),
              plain(bx, s[m], t[m], bucket)[0]),
             (query_batch_argmin(pk, s, t), plain(pk, s, t, None)[0]))
    for res, want in cases:
        assert len(res) == 5
        for r, w, dt in zip(res, want, ARGMIN_DTYPES):
            assert isinstance(r, np.ndarray) and r.dtype == dt
            np.testing.assert_array_equal(r.view(np.uint8), w.view(np.uint8))
