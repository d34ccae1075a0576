"""The path cell's committed mix, ``single-path`` (every request asks for
its route), on a CPU-sized cell: its plan, a sound run, the two route
faults and the bfloat16 control, and the ``unwind_us.tail`` reader.

Run by path: ``python -m pytest bench/tests``.
"""

import numpy as np
import pytest

import small
import check
import control
import measure
import openloop
import reference
import scene
import spec
import traffic
from test_check import _run, fresh_programs  # noqa: F401
from test_traffic import DRAWN, _config, _digest, _mix

MIX = "single-path"


@pytest.mark.parametrize("seed", [5, 2**31 + 977])
def test_path_mix_draws_its_distance_twins_plan(seed):
    """``single-path`` draws the due times and points of
    ``single-uniform`` (the digests pinned before mixes could ask for
    routes), and every request of it asks for a route."""
    cfg = _config("room64-b20")
    mp = scene.make_map(cfg)
    plan = traffic.make_plan(mp, _mix(MIX), cfg, 3200.0, 2.0, seed)
    assert _digest(plan) == DRAWN[("room64-b20", "single-uniform", seed)]
    assert plan.path.dtype == bool and plan.path.all()


def test_committed_path_mix_is_correct(tmp_path):
    cell = small.SmallCell(MIX)
    out, compared = _run(cell, tmp_path)
    assert out["correct"], compared
    assert list(compared) == ["max_rel_err", "unanswered", "path_rel_err",
                              "bad_paths"]
    assert 0 <= compared["path_rel_err"]["value"] < 2e-6
    assert compared["bad_paths"] == {"value": 0, "limit": 0}
    assert 0 <= compared["max_rel_err"]["value"] < 2e-6


def test_committed_path_mix_with_second_best_route_is_not_correct(
        tmp_path, monkeypatch):
    """Inside the join, one query of every argmin batch takes its row's
    second-best label while ``d`` stays as it was (the fault of
    ``test_check``'s route test): only ``path_rel_err`` fails."""
    import jax.numpy as jnp
    from repro.core import packed
    from repro.kernels import ops

    join = packed._join_masked

    def second_best(masked_s, masked_t, s, t, covis, use_kernels,
                    want_argmin, qerr2=None):
        out = join(masked_s, masked_t, s, t, covis, use_kernels,
                   want_argmin, qerr2=qerr2)
        if not want_argmin:
            return out
        hub_s, vd_s, vid_s = masked_s
        hub_t, vd_t, vid_t = masked_t
        rowmin = ops.label_join_rowmin_ref(hub_s, vd_s, hub_t, vd_t)
        i = jnp.argmin(rowmin, axis=-1)
        iota = jnp.arange(rowmin.shape[-1])[None]
        rest = jnp.where(iota == i[:, None], jnp.inf, rowmin)
        i2 = jnp.argmin(rest, axis=-1)
        gap = rest.min(-1) - rowmin.min(-1)
        gap = jnp.where(jnp.isfinite(gap) & ~covis, gap, -1.0)
        take = (jnp.arange(len(i)) == jnp.argmax(gap)) & (gap > 0)
        hub2 = jnp.take_along_axis(hub_s, i2[:, None], 1)
        j2 = jnp.argmin(jnp.where(hub_t == hub2, vd_t, jnp.inf), axis=-1)
        via_s2 = jnp.take_along_axis(vid_s, i2[:, None], 1)[:, 0]
        via_t2 = jnp.take_along_axis(vid_t, j2[:, None], 1)[:, 0]
        d, cv, via_s, hub, via_t = out[:5]
        return (d, cv, jnp.where(take, via_s2, via_s),
                jnp.where(take, hub2[:, 0], hub),
                jnp.where(take, via_t2, via_t)) + tuple(out[5:])

    monkeypatch.setattr(packed, "_join_masked", second_best)
    cell = small.SmallCell(MIX)
    out, compared = _run(cell, tmp_path)
    assert not out["correct"]
    assert compared["path_rel_err"]["value"] > cell.limit
    assert compared["max_rel_err"]["value"] <= cell.limit
    assert compared["bad_paths"]["value"] == 0
    assert compared["unanswered"]["value"] == 0


def test_committed_path_mix_with_a_waypoint_dropped_is_not_correct(
        tmp_path, monkeypatch):
    """One polyline of every argmin ticket loses an inner waypoint where
    it is produced, so that a leg cuts through a wall."""
    cell = small.SmallCell(MIX)
    mp = scene.make_map(cell.config)
    build = openloop.polylines

    def dropped(index, s, t, result):
        out = build(index, s, t, result)
        for k, p in enumerate(out):
            for w in range(1, len(p) - 1):
                if reference.blocked(mp, p[w - 1:w], p[w + 1:w + 2],
                                     pairwise=False)[0]:
                    out[k] = np.delete(p, w, axis=0)
                    return out
        return out

    monkeypatch.setattr(openloop, "polylines", dropped)
    out, compared = _run(cell, tmp_path)
    assert not out["correct"]
    assert compared["bad_paths"]["value"] > 0
    assert compared["max_rel_err"]["value"] <= cell.limit


@pytest.mark.parametrize("seed", [3, 2**35 + 5])
def test_committed_path_mix_control_in_bfloat16_is_not_correct(seed):
    cell = small.SmallCell(MIX)
    compared = control.run(cell, seed, 2.0)
    assert not check.passed(compared)
    assert compared["max_rel_err"]["value"] > 3 * cell.limit
    assert compared["unanswered"]["value"] == 0


def test_unwind_reader_reads_the_mean_per_path_request():
    read = spec.reader("unwind_us.tail")
    m = measure.Measurement(30, 1.0, 10, [1.0], unwind_s=[2e-4, 4e-4],
                            path_requests=2)
    assert read(m) == pytest.approx(300.0)
    assert read(measure.Measurement(30, 1.0, 10, [1.0])) is None
