"""``correct`` on a CPU-sized cell: the harness's whole run with the chip
check skipped, sound and with the timed path broken underneath, and the
control (bfloat16 labels in the program's place) against the limit.

Run by path: ``python -m pytest bench/tests``.  These drive the jnp engine
on the CPU; they take a minute or two.
"""

import gc
import time

import jax
import numpy as np
import pytest

import small
import check
import control
import openloop
import reference
import run
import scene
import traffic

TPU_PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _run(cell, tmp_path, seed=2**33 + 1):
    out, compared = run.run_cell(cell, seed, 1.5, False, jax.devices(),
                                 t_start=time.perf_counter(),
                                 cache=str(tmp_path / "cache"))
    return out, compared


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("mix,scores", [("single-uniform", "uniform"),
                                        ("single-cluster4", "history")])
def test_sound_run_is_correct(tmp_path, mix, scores):
    out, compared = _run(small.SmallCell(mix, scores), tmp_path)
    assert out["correct"], compared
    assert compared["unanswered"]["value"] == 0
    assert 0 <= compared["max_rel_err"]["value"] < 2e-6
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "p50_ms"}


def test_answer_altered_where_produced_is_not_correct(tmp_path,
                                                      monkeypatch):
    """One answer of every batch made 1% longer inside the join."""
    from repro.core import packed

    join = packed._join_masked

    def altered(*a, **kw):
        d = join(*a, **kw)
        return d.at[0].multiply(1.01)

    monkeypatch.setattr(packed, "_join_masked", altered)
    out, compared = _run(small.SmallCell("single-uniform"), tmp_path)
    assert not out["correct"]
    assert compared["max_rel_err"]["value"] > compared["max_rel_err"][
        "limit"]


def test_answer_that_never_comes_is_not_correct(tmp_path, monkeypatch):
    """The first batch's answers are never written to their tickets."""
    from repro.serving import batcher

    write = batcher.Ticket._write
    dropped = []

    def drop_first(self, slots, cols):
        if not dropped:
            dropped.append(self)
            return
        write(self, slots, cols)

    monkeypatch.setattr(batcher.Ticket, "_write", drop_first)
    monkeypatch.setattr(openloop, "GRACE_S", 1.0)
    cell = small.SmallCell("single-uniform")
    cell.mix["ramp_s"] = 0.0           # the first request is in the window
    out, compared = _run(cell, tmp_path)
    assert not out["correct"]
    assert compared["unanswered"]["value"] >= 1


def test_path_mix_is_correct(tmp_path):
    """30% of requests ask for routes: their polylines have the reference's
    length and keep to free space."""
    cell = small.SmallCell("single-uniform", path_share=0.3)
    out, compared = _run(cell, tmp_path)
    assert out["correct"], compared
    assert list(compared) == ["max_rel_err", "unanswered", "path_rel_err",
                              "bad_paths"]
    assert 0 <= compared["path_rel_err"]["value"] < 2e-6
    assert compared["path_rel_err"]["limit"] == cell.limit
    assert compared["bad_paths"] == {"value": 0, "limit": 0}
    assert 0 <= compared["max_rel_err"]["value"] < 2e-6


def test_route_through_the_second_best_hub_is_not_correct(tmp_path,
                                                          monkeypatch):
    """Inside the join, one query of every argmin batch takes its row's
    second-best label i, in the row where that one is longest beyond the
    best, while ``d`` stays as it was: the distance is right and the
    route is not, and only ``path_rel_err`` says so."""
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
        row = jnp.arange(len(i)) == jnp.argmax(gap)
        take = row & (gap > 0)           # strictly longer, or no change
        hub2 = jnp.take_along_axis(hub_s, i2[:, None], 1)
        j2 = jnp.argmin(jnp.where(hub_t == hub2, vd_t, jnp.inf), axis=-1)
        via_s2 = jnp.take_along_axis(vid_s, i2[:, None], 1)[:, 0]
        via_t2 = jnp.take_along_axis(vid_t, j2[:, None], 1)[:, 0]
        d, cv, via_s, hub, via_t = out[:5]
        return (d, cv, jnp.where(take, via_s2, via_s),
                jnp.where(take, hub2[:, 0], hub),
                jnp.where(take, via_t2, via_t)) + tuple(out[5:])

    monkeypatch.setattr(packed, "_join_masked", second_best)
    cell = small.SmallCell("single-uniform", path_share=0.3)
    out, compared = _run(cell, tmp_path)
    assert not out["correct"]
    assert compared["path_rel_err"]["value"] > cell.limit
    assert compared["max_rel_err"]["value"] <= cell.limit
    assert compared["bad_paths"]["value"] == 0
    assert compared["unanswered"]["value"] == 0


def test_route_with_a_waypoint_dropped_is_not_correct(tmp_path,
                                                      monkeypatch):
    """One polyline of every argmin ticket loses an inner waypoint where
    it is produced, so that a leg cuts through a wall."""
    cell = small.SmallCell("single-uniform", path_share=0.3)
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


def test_bad_paths_counts_endpoints_and_legs():
    mp = scene.make_map(small.SMALL_MAP)
    ref = reference.Reference(mp)
    s, t = traffic.endpoints(mp, {"endpoints": "uniform"}, {}, 64,
                             np.random.default_rng(9))
    s, t = s.astype(np.float32), t.astype(np.float32)
    truth = ref.distances(s, t)
    blocked = reference.blocked(mp, s.astype(np.float64),
                                t.astype(np.float64), pairwise=False)
    k = int(np.argmax(blocked))          # a pair with a wall between
    assert blocked[k] and np.isfinite(truth[k])
    line = np.stack([s[k], t[k]]).astype(np.float64)
    assert check.bad_paths(mp, [line], s[k:k + 1], t[k:k + 1]) == 1
    free = int(np.argmin(blocked))
    direct = np.stack([s[free], t[free]]).astype(np.float64)
    assert check.bad_paths(mp, [direct], s[free:free + 1],
                           t[free:free + 1]) == 0
    for end in (0, -1):                  # off the given s or t by an ulp
        shifted = direct.copy()
        shifted[end] = np.nextafter(shifted[end], np.inf)
        assert check.bad_paths(mp, [shifted], s[free:free + 1],
                               t[free:free + 1]) == 1
    assert check.bad_paths(mp, [np.zeros((0, 2))], s[:1], t[:1]) == 0
    assert check.path_rel_err([np.zeros((0, 2))], truth[:1]) == np.inf
    assert check.path_rel_err([direct], truth[free:free + 1]) \
        == pytest.approx(0.0, abs=1e-12)


def test_slab_dtype_other_than_float32_is_refused(tmp_path, capsys):
    """A config that states bfloat16 slabs is refused before any result
    line, with the reason, instead of being served float32."""
    cell = small.SmallCell("single-uniform")
    cell.config["slab_dtype"] = "bfloat16"
    with pytest.raises(SystemExit) as e:
        _run(cell, tmp_path)
    assert "slab_dtype 'bfloat16'" in str(e.value)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("seed", [1, 2, 2**35 + 3])
def test_control_in_bfloat16_is_not_correct(seed):
    """bfloat16 labels in the program's place fail the harness's own
    comparison, by three times its limit or more."""
    cell = small.SmallCell("single-uniform")
    compared = control.run(cell, seed, 2.0)
    assert not check.passed(compared)
    assert compared["max_rel_err"]["value"] > 3 * cell.limit
    assert compared["unanswered"]["value"] == 0


def test_reference_agrees_with_the_program_oracle():
    """Second witness: the program's float64 visibility-graph A* on the
    same map gives the reference's distances."""
    from repro.core import Scene, astar, build_visgraph

    mp = scene.make_map(small.SMALL_MAP)
    g = build_visgraph(Scene.build(mp.polygons, mp.width, mp.height))
    s, t = traffic.endpoints(mp, {"endpoints": "uniform"}, {}, 200,
                             np.random.default_rng(4))
    d = reference.Reference(mp).distances(s, t)
    truth = np.array([astar(g, a, b)[0] for a, b in zip(s, t)])
    np.testing.assert_allclose(d, truth, rtol=1e-12, atol=1e-9)


def test_max_rel_err_counts_reachability():
    assert check.max_rel_err(np.array([1.0, np.inf]),
                             np.array([1.0, 2.0])) == np.inf
    assert check.max_rel_err(np.zeros(0), np.zeros(0)) == np.inf
    assert check.max_rel_err(np.array([10.0]), np.array([10.01])) \
        == pytest.approx(0.01 / 10.01)


class _Ticket:
    """A ticket that finishes at a set time (``openloop`` waits on it)."""

    def __init__(self, at):
        self.at = at

    @property
    def done(self):
        return time.perf_counter() >= self.at

    def wait(self, timeout):
        left = self.at - time.perf_counter()
        time.sleep(max(0.0, min(left, timeout)))
        return self.done

    def result(self, timeout):
        return np.zeros(1, np.float32)


def test_a_ticket_is_stamped_when_it_finishes_not_in_order():
    """The first request finishes last; the second is seen done when it
    finishes, not when the first does."""
    finish = [0.30, 0.02, 0.05]

    class Server:
        def __init__(self):
            self.n = 0

        def submit(self, s, t):
            tk = _Ticket(time.perf_counter() + finish[self.n])
            self.n += 1
            return tk

    plan = traffic.Plan(due=np.array([0.0, 0.001, 0.002]),
                        s=np.zeros((3, 2), np.float32),
                        t=np.zeros((3, 2), np.float32), per_request=1)
    t0 = time.perf_counter()
    run = openloop.offer(Server(), plan, t0, t0 + 1.0)
    took = run.done - run.submitted
    assert took[0] == pytest.approx(0.30, abs=0.02)
    assert took[1] == pytest.approx(0.02, abs=0.01)
    assert took[2] == pytest.approx(0.05, abs=0.01)


def test_a_path_request_is_stamped_once_its_polylines_exist(monkeypatch):
    """Both tickets finish together; the distance request is stamped
    before the path request's unwinding (50 ms here) starts, and the path
    request when its polylines are in hand."""
    class Server:
        def __init__(self):
            self.argmin = []

        def submit(self, s, t, want_argmin=False):
            self.argmin.append(want_argmin)
            return _Ticket(time.perf_counter() + 0.05 - 0.001 * len(
                self.argmin))

    routes = []

    def polylines(index, s, t, result):
        time.sleep(0.05)
        routes.append(time.perf_counter())
        return [np.zeros((2, 2))]

    monkeypatch.setattr(openloop, "polylines", polylines)
    plan = traffic.Plan(due=np.array([0.0, 0.001]),
                        s=np.zeros((2, 2), np.float32),
                        t=np.zeros((2, 2), np.float32), per_request=1,
                        path=np.array([True, False]))
    server = Server()
    t0 = time.perf_counter()
    run = openloop.offer(server, plan, t0, t0 + 1.0, index=object())
    assert server.argmin == [True, False]
    assert run.done[1] < run.done[0] - 0.04
    assert run.done[0] >= routes[0]
    assert run.unwind_s[0] == pytest.approx(0.05, abs=0.02)
    assert np.isnan(run.unwind_s[1])
    assert list(run.paths) == [0] and run.paths[0][0].shape == (2, 2)
    with pytest.raises(ValueError):
        openloop.offer(server, plan, t0, t0 + 1.0)


def test_gc_watch_times_each_collection():
    import measure
    with measure.GcWatch() as w:
        junk = [[i] for i in range(10000)]
        t0 = time.perf_counter()
        gc.collect()
        t1 = time.perf_counter()
        del junk
    full = [e for e in w.events if e[2] == 2]
    assert len(full) == 1
    start, seconds, _ = full[0]
    assert t0 <= start and start + seconds <= t1
    assert w.between(t0, t1) == full
