"""Sharded serving: planner balance, (shard, bucket) routing, bitwise
identity vs the single-device engine, and atomic multi-shard hot-swap.

Most tests run on the single real CPU device (conftest rule) with shards
round-robined onto it — the routing/merging/transfer code paths are
identical, the device_puts just degenerate to same-device copies.  The
acceptance gate (true 4-device mesh, 1k random queries, swap under load)
runs in a subprocess with ``--xla_force_host_platform_device_count=4``.
"""

import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core.compression import compress_to_fraction
from repro.core.grid import build_ehl
from repro.core.packed import (bucketed_device_bytes, pack_bucketed,
                               query_batch_bucketed)
from repro.core.workload import cluster_queries, uniform_queries
from repro.indexing import IndexManager
from repro.serving.engine import PathServer
from repro.sharding import (ShardPlanner, ShardedQueryEngine,
                            sharded_overhead_bytes)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
N_SHARDS = 4


@pytest.fixture(scope="module")
def sharded_setup(scene_s, graph_s, hl_s):
    idx = build_ehl(scene_s, 2.0, graph=graph_s, hl=hl_s)
    compress_to_fraction(idx, 0.3)
    bx = pack_bucketed(idx)
    planner = ShardPlanner(N_SHARDS)
    sharded = planner.build(idx)
    return idx, bx, sharded


# --------------------------------------------------------------- placement

def test_serving_mesh_refuses_fewer_devices_than_shards():
    from repro.launch.mesh import make_serving_mesh
    n = len(jax.devices()) + 1
    with pytest.raises(ValueError, match=f"need {n} devices"):
        make_serving_mesh(n)


def test_meshless_placement_stacks_shards_only_on_cpu(monkeypatch):
    from repro.launch.mesh import shard_devices
    cpu = jax.devices()
    assert shard_devices(None, len(cpu) + 1)[-1] == cpu[0]   # wraps on CPU
    one_tpu = [SimpleNamespace(platform="tpu", id=0)]
    monkeypatch.setattr(jax, "devices", lambda *a: one_tpu)
    with pytest.raises(ValueError, match="refusing to stack"):
        shard_devices(None, N_SHARDS)
    four_tpus = [SimpleNamespace(platform="tpu", id=i) for i in range(4)]
    monkeypatch.setattr(jax, "devices", lambda *a: four_tpus)
    assert shard_devices(None, N_SHARDS) == four_tpus


# ----------------------------------------------------------------- planner

def test_planner_balances_and_covers(sharded_setup):
    idx, bx, sharded = sharded_setup
    plan = sharded.plan
    assert plan.num_shards == N_SHARDS
    # every region placed, every shard non-empty
    assert plan.assignment.shape == (bx.num_regions,)
    assert sorted(np.unique(plan.assignment)) == list(range(N_SHARDS))
    # predicted slab balance within tolerance
    assert plan.imbalance <= plan.tol + 1e-9
    # realized per-shard device bytes within the acceptance bound
    per = sharded.per_shard_bytes()
    assert max(per) <= 1.15 * sharded.device_bytes() / N_SHARDS
    # label data is partitioned, not replicated: summed slab slots match
    used_sharded = sum(s.label_slots()[0] for s in sharded.shards)
    assert used_sharded == bx.label_slots()[0]


def test_planner_rejects_more_shards_than_regions(scene_s, graph_s, hl_s):
    idx = build_ehl(scene_s, 2.0, graph=graph_s, hl=hl_s)
    with pytest.raises(ValueError):
        ShardPlanner(10 ** 6).plan(idx)


# ------------------------------------------------------- routing + identity

def test_sharded_answers_bitwise_identical(sharded_setup, scene_s, graph_s):
    _, bx, sharded = sharded_setup
    eng = ShardedQueryEngine(sharded)
    qs = uniform_queries(scene_s, graph_s, 400, seed=3, require_path=False)
    s = qs.s.astype(np.float32)
    t = qs.t.astype(np.float32)
    ref = np.asarray(query_batch_bucketed(bx, s, t))
    out = eng.query(s, t)
    assert np.array_equal(np.isfinite(ref), np.isfinite(out))
    np.testing.assert_array_equal(np.where(np.isfinite(ref), ref, 0),
                                  np.where(np.isfinite(out), out, 0))
    # and through the full PathServer stack (fixed-shape padded batches)
    srv = PathServer(ShardedQueryEngine(sharded), batch_size=64)
    srv.warmup()
    d = srv.query(s, t)
    np.testing.assert_array_equal(np.where(np.isfinite(ref), ref, 0),
                                  np.where(np.isfinite(d), d, 0))
    assert len(srv.stats.per_shard) == N_SHARDS


def test_sharded_argmin_matches_single_device(sharded_setup, scene_s,
                                              graph_s):
    _, bx, sharded = sharded_setup
    eng = ShardedQueryEngine(sharded)
    qs = uniform_queries(scene_s, graph_s, 60, seed=5, require_path=False)
    s = qs.s.astype(np.float32)
    t = qs.t.astype(np.float32)
    ref = query_batch_bucketed(bx, s, t, want_argmin=True)
    out = eng.query(s, t, want_argmin=True)
    for r, o in zip(ref, out):
        r = np.asarray(r)
        fin = np.isfinite(r) if r.dtype.kind == "f" else np.ones_like(r, bool)
        np.testing.assert_array_equal(np.where(fin, r, 0),
                                      np.where(fin, np.asarray(o), 0))


def test_all_queries_on_one_shard_leaves_others_idle(sharded_setup, scene_s,
                                                     graph_s):
    """Single-destination batch: one shard serves, the rest see no
    sub-batch at all (the 'empty shard sub-batch' edge case)."""
    _, bx, sharded = sharded_setup
    eng = ShardedQueryEngine(sharded)
    qs = uniform_queries(scene_s, graph_s, 300, seed=9, require_path=False)
    s = qs.s.astype(np.float32)
    t = qs.t.astype(np.float32)
    keys = eng.buckets_of(s, t)
    # pick the busiest destination shard and keep only its queries
    dest = np.array([eng.router.decode_key(int(k))[0] for k in keys])
    k = np.bincount(dest, minlength=N_SHARDS).argmax()
    m = dest == k
    assert m.sum() >= 3
    out = eng.query(s[m], t[m])
    ref = np.asarray(query_batch_bucketed(bx, s[m], t[m]))
    np.testing.assert_array_equal(np.where(np.isfinite(ref), ref, 0),
                                  np.where(np.isfinite(out), out, 0))
    st = eng.shard_stats()
    for j in range(N_SHARDS):
        if j != k:
            assert st[j].batches == 0 and st[j].slots == 0
    assert st[k].batches >= 1 and st[k].slots == int(m.sum())


def test_merge_preserves_input_order(sharded_setup, scene_s, graph_s):
    _, bx, sharded = sharded_setup
    eng = ShardedQueryEngine(sharded)
    qs = uniform_queries(scene_s, graph_s, 200, seed=13, require_path=False)
    s = qs.s.astype(np.float32)
    t = qs.t.astype(np.float32)
    base = eng.query(s, t)
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(s))
    shuffled = eng.query(s[perm], t[perm])
    np.testing.assert_array_equal(
        np.where(np.isfinite(base[perm]), base[perm], 0),
        np.where(np.isfinite(shuffled), shuffled, 0))


def test_cross_shard_queries_exist_and_match(sharded_setup, scene_s,
                                             graph_s):
    """Random endpoints must exercise the cross-shard gather path."""
    _, bx, sharded = sharded_setup
    eng = ShardedQueryEngine(sharded)
    qs = uniform_queries(scene_s, graph_s, 200, seed=17, require_path=False)
    s = qs.s.astype(np.float32)
    t = qs.t.astype(np.float32)
    keys = eng.buckets_of(s, t)
    pairs = {eng.router.decode_key(int(k))[:2] for k in keys}
    assert any(i != j for i, j in pairs), "no cross-shard traffic routed"
    eng.query(s, t)
    assert sum(st.gathers_out for st in eng.shard_stats()) > 0


def test_sharded_async_submit_matches_sync(sharded_setup, scene_s, graph_s):
    """The continuous-batching loop over the sharded engine (split-phase
    stage/join with cross-shard gathers overlapping the in-flight join)
    answers bitwise-identically to the synchronous sharded path."""
    _, _, sharded = sharded_setup
    srv = PathServer(ShardedQueryEngine(sharded), batch_size=32)
    srv.warmup()
    qs = uniform_queries(scene_s, graph_s, 150, seed=23, require_path=False)
    s = qs.s.astype(np.float32)
    t = qs.t.astype(np.float32)
    keys = srv.engine.buckets_of(s, t)
    assert any(srv.engine.router.decode_key(int(k))[0]
               != srv.engine.router.decode_key(int(k))[1]
               for k in keys), "no cross-shard traffic to pipeline"
    ref = srv.query(s, t)
    tickets = [srv.submit(s[i], t[i]) for i in range(len(s))]
    srv.flush()
    assert srv.drain(timeout=120)
    got = np.concatenate([tk.result(timeout=1) for tk in tickets])
    srv.stop_async()
    np.testing.assert_array_equal(ref, got)
    for bstats in srv.stats.per_bucket.values():
        assert bstats.occupancy <= 1.0
    assert len(srv.stats.per_shard) == N_SHARDS


# ------------------------------------------------------------ swap behavior

def test_pinned_generation_consistent_during_sharded_swap(scene_s, graph_s,
                                                          hl_s):
    """A request pinned before a multi-shard swap must resolve every call
    (routing + all sub-batches) against the old shard set; the swap flips
    all shards at once for new requests."""
    idx = build_ehl(scene_s, 2.0, graph=graph_s, hl=hl_s)
    budget = int(bucketed_device_bytes(idx) * 0.5) \
        + sharded_overhead_bytes(idx, N_SHARDS)
    mgr = IndexManager(idx, budget, batch_size=32, min_queries=60,
                       replan_threshold=0.10, min_dwell=0, probe_n=16,
                       num_shards=N_SHARDS, seed=13)
    qs = cluster_queries(scene_s, graph_s, 2, 150, seed=31,
                         require_path=False)
    s = qs.s.astype(np.float32)
    t = qs.t.astype(np.float32)
    mgr.recorder.record(s, t)

    old_engine = mgr.engine.current
    old_index = old_engine.index
    cm = mgr.engine.pin()
    pinned = cm.__enter__()                  # in-flight request, gen 0
    assert pinned is old_engine

    assert mgr.maybe_adapt() is True         # swap published under load
    assert mgr.generation == 1
    new_engine = mgr.engine.current
    assert new_engine is not old_engine
    assert new_engine.index is not old_index
    # one generation across ALL shards: the new engine's shard set is
    # entirely new, the pinned one's entirely old — no mixed set exists
    assert all(a is not b for a, b in zip(new_engine.index.shards,
                                          old_index.shards))
    assert pinned.index is old_index
    d_old = pinned.query(s[:40], t[:40])     # still served by the old set
    d_new = mgr.engine.query(s[:40], t[:40])
    fin = np.isfinite(d_old)
    np.testing.assert_array_equal(fin, np.isfinite(d_new))
    np.testing.assert_array_equal(np.where(fin, d_old, 0),
                                  np.where(fin, d_new, 0))
    assert mgr.engine.retired_generations() == [0]
    cm.__exit__(None, None, None)            # drain -> old shard set freed
    assert mgr.engine.retired_generations() == []
    assert mgr.engine.drops == 1


def test_path_server_requests_never_mix_generations(scene_s, graph_s, hl_s,
                                                    monkeypatch):
    """Every engine call inside one PathServer request hits one engine
    object even when a swap lands mid-request."""
    idx = build_ehl(scene_s, 2.0, graph=graph_s, hl=hl_s)
    budget = int(bucketed_device_bytes(idx) * 0.5) \
        + sharded_overhead_bytes(idx, N_SHARDS)
    mgr = IndexManager(idx, budget, batch_size=16, min_queries=40,
                       replan_threshold=0.10, min_dwell=0, probe_n=8,
                       num_shards=N_SHARDS, seed=5)
    srv = PathServer(mgr.engine, batch_size=16, recorder=mgr.recorder)

    served_by: list = []
    orig = ShardedQueryEngine.batch

    def spy(self, s, t, bucket=0):
        served_by.append(id(self))
        if len(served_by) == 2:
            # a swap lands while this request is mid-flight
            qs = cluster_queries(scene_s, graph_s, 2, 80, seed=61,
                                 require_path=False)
            mgr.recorder.record(qs.s, qs.t)
            assert mgr.maybe_adapt() is True
        return orig(self, s, t, bucket=bucket)

    monkeypatch.setattr(ShardedQueryEngine, "batch", spy)
    qs = uniform_queries(scene_s, graph_s, 120, seed=7, require_path=False)
    srv.query(qs.s.astype(np.float32), qs.t.astype(np.float32))
    assert len(served_by) >= 3                  # several sub-batches
    assert len(set(served_by)) == 1             # ...all on one generation
    assert mgr.generation == 1
    assert srv.stats.stale_batches > 0          # observed as stale, not mixed


# ------------------------------------------------ acceptance: 4-device mesh

def test_sharded_acceptance_on_forced_4_device_mesh():
    """The ISSUE gate, on a real (forced) 4-device host platform: answers
    bitwise-identical to the single-device engine on >= 1k random queries,
    per-shard bytes within 1.15x of fair share, one shard per device, and
    a hot-swap under load publishing one generation."""
    code = textwrap.dedent("""
        import numpy as np
        import jax
        assert len(jax.devices()) == 4, jax.devices()
        from repro.core.maps import make_map
        from repro.core.visgraph import build_visgraph
        from repro.core.hublabel import build_hub_labels
        from repro.core.grid import build_ehl
        from repro.core.compression import compress_to_fraction
        from repro.core.packed import (bucketed_device_bytes, pack_bucketed,
                                       query_batch_bucketed)
        from repro.core.workload import cluster_queries, uniform_queries
        from repro.indexing import IndexManager
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import PathServer
        from repro.sharding import (ShardPlanner, ShardedQueryEngine,
                                    sharded_overhead_bytes)

        scene = make_map("rooms-S", seed=1)
        graph = build_visgraph(scene)
        hl = build_hub_labels(graph)
        idx = build_ehl(scene, 2.0, graph=graph, hl=hl)
        compress_to_fraction(idx, 0.3)
        bx = pack_bucketed(idx)
        mesh = make_serving_mesh(4)
        sharded = ShardPlanner(4).build(idx)
        eng = ShardedQueryEngine(sharded, mesh=mesh)
        # one shard per distinct mesh device
        devs = {str(d) for d in eng.router.devices}
        assert len(devs) == 4, devs
        per = sharded.per_shard_bytes()
        assert max(per) <= 1.15 * sharded.device_bytes() / 4, per

        qs = uniform_queries(scene, graph, 1000, seed=42,
                             require_path=False)
        s = qs.s.astype(np.float32); t = qs.t.astype(np.float32)
        ref = np.asarray(query_batch_bucketed(bx, s, t))
        out = eng.query(s, t)
        fin = np.isfinite(ref)
        assert np.array_equal(fin, np.isfinite(out))
        assert np.array_equal(np.where(fin, ref, 0), np.where(fin, out, 0))

        # hot-swap under load: requests keep flowing while the manager
        # builds/validates/swaps; answers stay bitwise-stable and exactly
        # one generation is published across all four shards
        idx2 = build_ehl(scene, 2.0, graph=graph, hl=hl)
        budget = int(bucketed_device_bytes(idx2) * 0.5) \\
            + sharded_overhead_bytes(idx2, 4)
        mgr = IndexManager(idx2, budget, batch_size=64, min_queries=60,
                           replan_threshold=0.10, min_dwell=0, probe_n=32,
                           num_shards=4, mesh=mesh, seed=13,
                           validate_tol=0.0)
        srv = PathServer(mgr.engine, batch_size=64, recorder=mgr.recorder)
        srv.warmup()
        cq = cluster_queries(scene, graph, 2, 200, seed=31,
                             require_path=False)
        cs = cq.s.astype(np.float32); ct = cq.t.astype(np.float32)
        d0 = srv.query(cs, ct)
        mgr.maybe_adapt(block=False)         # swap off the serving path
        import time
        while mgr.swaps == 0:                # serve under load until it lands
            d = srv.query(cs, ct)
            f = np.isfinite(d0)
            assert np.array_equal(f, np.isfinite(d))
            assert np.array_equal(np.where(f, d0, 0), np.where(f, d, 0))
            mgr.join(timeout=0.05)
        mgr.join()
        d1 = srv.query(cs, ct)
        f = np.isfinite(d0)
        assert np.array_equal(np.where(f, d0, 0), np.where(f, d1, 0))
        assert mgr.generation == 1 and mgr.validation_failures == 0
        assert srv.stats.generation == 1
        assert max(mgr.engine.per_shard_bytes()) <= 1.15 * budget / 4
        print("SHARDED_ACCEPTANCE_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_ACCEPTANCE_OK" in out.stdout


# ------------------------------------------------------- edge clipping (§10)

def _chambered_scene():
    """Four near-closed chambers around a center junction (>= 128 edges).

    Visibility — and therefore label via reach — is chamber-local except
    through the doors, so per-shard clipped edge subsets genuinely shrink:
    the regime the §10 shard edge clipping targets.  Open suite maps see
    map-wide, where clips legitimately keep everything.
    """
    from repro.core.geometry import Scene

    def rect(x0, y0, x1, y1):
        return np.array([[x0, y0], [x1, y0], [x1, y1], [x0, y1]], float)

    W = 120.0
    polys = [rect(58, 0, 62, 55), rect(58, 65, 62, 120),
             rect(0, 58, 55, 62), rect(65, 58, 120, 62)]
    rng = np.random.default_rng(0)
    for cx, cy in ((0, 0), (62, 0), (0, 62), (62, 62)):
        for i in range(12):
            x0 = cx + 4 + (i % 4) * 13 + rng.uniform(0, 3)
            y0 = cy + 4 + (i // 4) * 15 + rng.uniform(0, 3)
            w, h = rng.uniform(4, 7, 2)
            polys.append(rect(x0, y0, x0 + w, y0 + h))
    return Scene.build(polys, W, W)


def test_shard_edge_clipping_drops_bytes_and_stays_bitwise():
    """Per-shard edge subsets beat full replication on occluded maps.

    Asserts the §10 clip (a) keeps strictly fewer edges than replication
    on most shards, (b) drops summed edge bytes below the replicated
    baseline, and (c) never changes an answer — the clipped sharded engine
    is bitwise-identical to the single-device full-edge engine, which is
    the proof the clip boxes really cover every owned visibility segment.
    """
    from repro.core.visgraph import build_visgraph
    from repro.core.hublabel import build_hub_labels

    scene = _chambered_scene()
    E = scene.edges.shape[0]
    assert E >= 128          # above one lane, so clipping can change bytes
    graph = build_visgraph(scene)
    idx = build_ehl(scene, 4.0, graph=graph, hl=build_hub_labels(graph))
    bx = pack_bucketed(idx)
    full_edge_bytes = int(sum(np.prod(a.shape) * 4 for a in
                              (bx.edges_a, bx.edges_b, bx.edges_c))) + \
        (bx.grid.device_bytes() if bx.grid else 0)

    S = 12
    sharded = ShardPlanner(S).build(idx)
    kept = [int(m.sum()) for m in sharded.edge_masks]
    assert all(len(m) == E for m in sharded.edge_masks)
    assert sum(k < E for k in kept) >= S // 3, (
        f"clipping kept everything almost everywhere: {kept}")
    assert sum(sharded.edge_bytes()) < S * full_edge_bytes, (
        "summed clipped edge bytes did not beat full replication")

    qs = uniform_queries(scene, graph, 120, seed=3, require_path=False)
    s = qs.s.astype(np.float32)
    t = qs.t.astype(np.float32)
    ref = np.asarray(query_batch_bucketed(bx, s, t))
    out = ShardedQueryEngine(sharded).query(s, t)
    assert np.array_equal(np.isfinite(ref), np.isfinite(out))
    np.testing.assert_array_equal(np.where(np.isfinite(ref), ref, 0),
                                  np.where(np.isfinite(out), out, 0))
