"""Pallas kernels vs pure-jnp oracles: shape sweeps + hypothesis properties.

Kernels run in interpret mode on CPU — the kernel *bodies* execute exactly as
they would inside Mosaic, so agreement here validates the kernel math and the
BlockSpec/padding plumbing.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
pytest.importorskip("hypothesis")  # test dep (pyproject [test]); skip, not error
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels.label_join import label_join_rowmin
from repro.kernels.segvis import segvis, segvis_tiles


def _rand_segs(rng, n, e):
    p = rng.uniform(0, 10, (n, 2)).astype(np.float32)
    q = rng.uniform(0, 10, (n, 2)).astype(np.float32)
    ea = rng.uniform(0, 10, (e, 2)).astype(np.float32)
    eb = rng.uniform(0, 10, (e, 2)).astype(np.float32)
    return map(jnp.asarray, (p, q, ea, eb))


@pytest.mark.parametrize("n", [1, 7, 256, 300])
@pytest.mark.parametrize("e", [1, 64, 512, 700])
def test_segvis_kernel_matches_ref_shapes(n, e):
    rng = np.random.default_rng(n * 1000 + e)
    p, q, ea, eb = _rand_segs(rng, n, e)
    ref = ops.segvis_ref(p, q, ea, eb)
    ker = segvis(p, q, ea, eb, interpret=True)
    assert (np.asarray(ref) == np.asarray(ker)).all()


@pytest.mark.parametrize("seg_blk,edge_blk", [(128, 128), (256, 512), (512, 256)])
def test_segvis_block_shape_invariance(seg_blk, edge_blk):
    rng = np.random.default_rng(5)
    p, q, ea, eb = _rand_segs(rng, 333, 257)
    ref = ops.segvis_ref(p, q, ea, eb)
    ker = segvis(p, q, ea, eb, seg_blk=seg_blk, edge_blk=edge_blk,
                 interpret=True)
    assert (np.asarray(ref) == np.asarray(ker)).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_segvis_property_blocked_iff_any_edge_blocks(seed):
    """Decomposition property: vis(all edges) == AND over single edges."""
    rng = np.random.default_rng(seed)
    p, q, ea, eb = _rand_segs(rng, 16, 8)
    full = np.asarray(ops.segvis_ref(p, q, ea, eb))
    single = np.stack([np.asarray(ops.segvis_ref(p, q, ea[i:i+1], eb[i:i+1]))
                       for i in range(ea.shape[0])])
    assert (full == single.all(axis=0)).all()


def _rand_join(rng, b, l, hubs=64, dtype=np.float32):
    hub_s = np.sort(rng.integers(0, hubs, (b, l)).astype(np.int32), axis=1)
    hub_t = np.sort(rng.integers(0, hubs, (b, l)).astype(np.int32), axis=1)
    vd_s = rng.uniform(0, 100, (b, l)).astype(dtype)
    vd_t = rng.uniform(0, 100, (b, l)).astype(dtype)
    # sprinkle infinities (invisible via labels)
    vd_s[rng.random((b, l)) < 0.2] = np.inf
    vd_t[rng.random((b, l)) < 0.2] = np.inf
    return map(jnp.asarray, (hub_s, vd_s, hub_t, vd_t))


@pytest.mark.parametrize("b", [1, 5, 8, 33])
@pytest.mark.parametrize("l", [16, 128, 384])
def test_label_join_kernel_matches_ref_shapes(b, l):
    rng = np.random.default_rng(b * 7919 + l)
    hs, vs, ht, vt = _rand_join(rng, b, l)
    ref = ops.label_join_ref(hs, vs, ht, vt)
    ker = ops.label_join_kernel(hs, vs, ht, vt, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker), rtol=1e-6)


@pytest.mark.parametrize("b_blk,t_blk", [(1, 128), (8, 128), (16, 256)])
def test_label_join_block_invariance(b_blk, t_blk):
    rng = np.random.default_rng(11)
    hs, vs, ht, vt = _rand_join(rng, 19, 200)
    ref = ops.label_join_rowmin_ref(hs, vs, ht, vt)
    ker = label_join_rowmin(hs, vs, ht, vt, b_blk=b_blk, t_blk=t_blk,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker), rtol=1e-6)


@pytest.mark.parametrize("l,t_blk", [(200, 128), (640, 128), (900, 384),
                                     (2048, 128)])
def test_label_join_s_tiling_bitwise(l, t_blk):
    """Tiling the s-side label axis (one to four S_BLK tiles, padded)
    leaves every row join bit-identical to the reference."""
    rng = np.random.default_rng(l)
    hs, vs, ht, vt = _rand_join(rng, 9, l)
    ref = ops.label_join_rowmin_ref(hs, vs, ht, vt)
    ker = label_join_rowmin(hs, vs, ht, vt, t_blk=t_blk, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_label_join_property_matches_bruteforce(seed):
    """Against an O(L^2) python brute force with exact merge-join semantics."""
    rng = np.random.default_rng(seed)
    hs, vs, ht, vt = _rand_join(rng, 4, 24, hubs=8)
    ref = np.asarray(ops.label_join_ref(hs, vs, ht, vt))
    hs, vs, ht, vt = map(np.asarray, (hs, vs, ht, vt))
    for b in range(4):
        best = np.inf
        for i in range(24):
            for j in range(24):
                if hs[b, i] == ht[b, j]:
                    best = min(best, vs[b, i] + vt[b, j])
        assert (ref[b] == pytest.approx(best, rel=1e-6)) or \
               (np.isinf(ref[b]) and np.isinf(best))


def test_label_join_hubdense_matches_ref():
    rng = np.random.default_rng(3)
    hs, vs, ht, vt = _rand_join(rng, 9, 64, hubs=32)
    ref = np.asarray(ops.label_join_ref(hs, vs, ht, vt))
    dense = np.asarray(ops.label_join_hubdense_ref(hs, vs, ht, vt, num_hubs=32))
    np.testing.assert_allclose(ref, dense, rtol=1e-6)


def test_all_inf_labels_give_inf():
    b, l = 4, 128
    hs = jnp.zeros((b, l), jnp.int32)
    vs = jnp.full((b, l), jnp.inf, jnp.float32)
    out = ops.label_join_kernel(hs, vs, hs, vs, interpret=True)
    assert np.isinf(np.asarray(out)).all()


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", None)
            if inner is not None:
                yield from _pallas_calls(getattr(inner, "jaxpr", inner))


_I32 = jnp.zeros((8, 256), jnp.int32)
_F32 = jnp.zeros((8, 256), jnp.float32)
_PTS = jnp.zeros((256, 2), jnp.float32)
_TILE = jnp.zeros((256, 128), jnp.float32)


@pytest.mark.parametrize("fn,args,semantics", [
    (label_join_rowmin, (_I32, _F32, _I32, _F32), ("parallel", "parallel")),
    (segvis, (_PTS, _PTS, _PTS, _PTS, _PTS), ("parallel", "arbitrary")),
    (segvis_tiles, (_PTS, _PTS) + (_TILE,) * 6, ("parallel", "arbitrary")),
], ids=["label_join", "segvis", "segvis_tiles"])
def test_kernels_pass_tpu_compiler_params(fn, args, semantics):
    """Each kernel hands Mosaic a real ``pltpu.CompilerParams`` with its
    grid's dimension semantics — a dropped hint would pass silently."""
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, interpret=False))(*args)
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert calls
    for eqn in calls:
        params = eqn.params["compiler_params"]["mosaic_tpu"]
        assert isinstance(params, pltpu.CompilerParams)
        assert params.dimension_semantics == semantics
