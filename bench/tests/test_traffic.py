"""The traffic generator: free-space endpoints, Cluster-k rectangles,
float32 points, and one plan per seed.

Run by path: ``python -m pytest bench/tests``.
"""

import json
import os

import numpy as np
import pytest

import small  # noqa: F401  (puts the harness on sys.path)
import scene
import traffic

BENCH = small.BENCH


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def room64():
    cfg = _config("room64-wa10")
    return cfg, scene.make_map(cfg)


def test_lattice_has_a_junction_per_crossing_and_a_wall_per_edge_segment(
        room64):
    cfg, mp = room64
    n = cfg["rooms_per_side"]
    assert mp.width == mp.height == n * cfg["room_cells"]
    assert len(mp.polygons) == (n - 1) ** 2 + 4 * (n - 1)
    assert len(mp.pieces) == 2 * (n - 1) ** 2 + 4 * (n - 1)


def test_program_polygons_and_reference_pieces_are_one_obstacle_set(room64):
    """The program's input (non-convex junctions) and the reference's
    convex pieces say the same of every point of the box."""
    from repro.core import Scene
    from repro.core.geometry import points_strictly_inside

    _, mp = room64
    sc = Scene.build(mp.polygons, mp.width, mp.height)
    pts = np.random.default_rng(3).uniform(0, mp.width, size=(20000, 2))
    np.testing.assert_array_equal(points_strictly_inside(sc, pts),
                                  mp.strictly_inside(pts))


@pytest.mark.parametrize("mix", ["single-uniform", "single-cluster4"])
def test_every_point_in_free_space_and_float32(room64, mix):
    cfg, mp = room64
    plan = traffic.make_plan(mp, _mix(mix), cfg, 50.0, 2.0, seed=2**40 + 7)
    for pts in (plan.s, plan.t):
        assert pts.dtype == np.float32
        assert not mp.strictly_inside(pts.astype(np.float64)).any()
        assert (pts >= 0).all() and (pts[:, 0] <= mp.width).all() \
            and (pts[:, 1] <= mp.height).all()


def test_cluster_endpoints_inside_their_rectangles(room64):
    cfg, mp = room64
    cl = cfg["clusters"]
    rects = np.array(traffic.cluster_rects(mp, cl["k"], cl["seed"],
                                           cl["side_frac"]))
    assert len(rects) == 4
    plan = traffic.make_plan(mp, _mix("single-cluster4"), cfg, 4000.0, 2.0,
                             seed=31)
    for pts in (plan.s, plan.t):
        p = pts.astype(np.float64)[:, None]
        inside = ((p[..., 0] >= rects[:, 0]) & (p[..., 0] <= rects[:, 2])
                  & (p[..., 1] >= rects[:, 1]) & (p[..., 1] <= rects[:, 3]))
        assert inside.any(1).all()
        # both endpoints of a query pick their rectangles independently
        assert len(np.unique(inside.argmax(1))) == 4


def test_same_seed_same_plan_other_seed_other_points(room64):
    cfg, mp = room64
    mix = _mix("single-uniform")
    a = traffic.make_plan(mp, mix, cfg, 500.0, 1.0, seed=5)
    b = traffic.make_plan(mp, mix, cfg, 500.0, 1.0, seed=5)
    c = traffic.make_plan(mp, mix, cfg, 500.0, 1.0, seed=6)
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.s, b.s)
    assert not np.array_equal(a.s[:10], c.s[:10])


def test_fixed_arrivals_are_evenly_spaced(room64):
    cfg, mp = room64
    mix = dict(_mix("single-uniform"), arrivals="fixed",
               queries_per_request=16)
    plan = traffic.make_plan(mp, mix, cfg, 100.0, 1.0, 3)
    np.testing.assert_allclose(np.diff(plan.due), 0.01)
    assert plan.due[-1] < mix["ramp_s"] + 1.0
    assert len(plan.request(0)[0]) == 16


def test_poisson_arrivals_keep_the_rate(room64):
    cfg, mp = room64
    plan = traffic.make_plan(mp, _mix("single-uniform"), cfg, 2000.0, 10.0,
                             seed=2**40 + 9)
    span = _mix("single-uniform")["ramp_s"] + 10.0
    assert len(plan.due) == pytest.approx(2000.0 * span, rel=0.03)
    assert plan.due[-1] < span


# sha256 of due, s and t (first 24 hex digits) as the generator drew them
# before mixes could ask for routes: 3200 requests/s, 2 s, two seeds
DRAWN = {("room64-b20", "single-uniform", 5): "a489926cbf6d7060e749937f",
         ("room64-b20", "single-uniform", 2**31 + 977):
             "c1c971e17ea38c08462bedcf",
         ("room64-wa10", "single-cluster4", 5): "ecec3b9ef0fd0ee6edccd845",
         ("room64-wa10", "single-cluster4", 2**31 + 977):
             "f308e8752fd5b90a87e9d01a"}


def _digest(plan):
    import hashlib
    h = hashlib.sha256()
    for a in (plan.due, plan.s, plan.t):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:24]


@pytest.mark.parametrize("key", sorted(DRAWN, key=str))
def test_existing_mixes_draw_the_same_plan_bit_for_bit(key):
    """A mix without ``path_share`` draws exactly the plan it drew before
    path requests existed, and asks for no route; the same mix with the
    key draws the same due times and points."""
    name, mix, seed = key
    cfg = _config(name)
    mp = scene.make_map(cfg)
    plan = traffic.make_plan(mp, _mix(mix), cfg, 3200.0, 2.0, seed)
    assert _digest(plan) == DRAWN[key]
    assert plan.path.dtype == bool and not plan.path.any()
    routed = traffic.make_plan(mp, dict(_mix(mix), path_share=0.3), cfg,
                               3200.0, 2.0, seed)
    assert _digest(routed) == DRAWN[key]


def test_path_share_draws_its_share_from_its_own_stream(room64):
    cfg, mp = room64
    mix = dict(_mix("single-uniform"), path_share=0.3)
    a = traffic.make_plan(mp, mix, cfg, 2000.0, 10.0, seed=2**33 + 1)
    b = traffic.make_plan(mp, mix, cfg, 2000.0, 10.0, seed=2**33 + 1)
    c = traffic.make_plan(mp, mix, cfg, 2000.0, 10.0, seed=2**33 + 2)
    assert a.path.shape == a.due.shape
    np.testing.assert_array_equal(a.path, b.path)
    assert not np.array_equal(a.path, c.path)
    assert a.path.mean() == pytest.approx(0.3, abs=0.01)
    for share in (-0.1, 1.5):
        with pytest.raises(ValueError):
            traffic.make_plan(mp, dict(mix, path_share=share), cfg, 10.0,
                              1.0, 1)
