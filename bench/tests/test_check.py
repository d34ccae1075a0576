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
