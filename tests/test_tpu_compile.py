"""The served path's Pallas kernels compile for a TPU v5e (no chip needed).

The TPU compiler ships with libtpu, so it compiles for a chip that is
described and not attached.  Each case compiles one kernel at a width the
served index really produces, or a served jit entry (the stacked endpoint
fold and the join) as the engine launches it, and asserts the Mosaic
``tpu_custom_call`` is in the executable — what interpret-mode tests
cannot see: a tile the chip refuses, or more VMEM than a kernel may use
(``label_join_rowmin`` at L=2048 once ran out of VMEM here).

Nothing touches libtpu at import: the topology is described inside a
module-scoped fixture, which skips where no v5e can be described.
"""

import functools
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.label_join import label_join_rowmin
from repro.kernels.segvis import segvis, segvis_tiles

B = 256                       # serving batch
WIDTHS = (128, 256, 512, 1024, 2048)   # rooms-L bucket ladder at 0.3
EDGES = 256                   # rooms-L's packed edge count
TILE_N, TILE_S = 32768, 192   # grid-pruned segments x gathered edge slots


@pytest.fixture(scope="module")
def one_chip():
    """A single described v5e device; compile cache off meanwhile (an
    entry compiled for a described chip cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                 # no libtpu / no description
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", old_cache)
        if old_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = old_log


def _described(tree, sharding):
    """The same pytree as abstract arrays placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("width", WIDTHS)
def test_label_join_rowmin_compiles(one_chip, width):
    ids = ((B, width), jnp.int32)
    vd = ((B, width), jnp.float32)
    compiled = _compile(
        lambda hs, vs, ht, vt: label_join_rowmin(hs, vs, ht, vt,
                                                 interpret=False),
        one_chip, ids, vd, ids, vd)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", WIDTHS)
def test_segvis_dense_compiles(one_chip, width):
    seg = ((B * width, 2), jnp.float32)
    edge = ((EDGES, 2), jnp.float32)
    compiled = _compile(
        lambda p, q, ea, eb, ec: segvis(p, q, ea, eb, ec, interpret=False),
        one_chip, seg, seg, edge, edge, edge)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", WIDTHS)
def test_segvis_stacked_fold_compiles(one_chip, width):
    """One fold program covers both endpoint sides: 2 * B * width
    segments, the same tiles over a grid twice as long."""
    seg = ((2 * B * width, 2), jnp.float32)
    edge = ((EDGES, 2), jnp.float32)
    compiled = _compile(
        lambda p, q, ea, eb, ec: segvis(p, q, ea, eb, ec, interpret=False),
        one_chip, seg, seg, edge, edge, edge)
    assert "tpu_custom_call" in compiled.as_text()


def test_segvis_tiles_compiles(one_chip):
    seg = ((TILE_N, 2), jnp.float32)
    tile = ((TILE_N, TILE_S), jnp.float32)
    compiled = _compile(
        lambda p, q, *tiles: segvis_tiles(p, q, *tiles, interpret=False),
        one_chip, seg, seg, *[tile] * 6)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bucket", (0, 1))    # the lattice's bucket ladder
def test_served_fold_and_join_compile(one_chip, compressed_s, monkeypatch,
                                      bucket):
    """The served entries as the engine launches them: one fold over the
    stacked [2, B, 2] endpoints, one join over its two masked halves, each
    compiled with the Pallas kernels for the chip."""
    from repro.core.packed import (_fold_endpoint, _join_endpoints,
                                   pack_bucketed)
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    bx = pack_bucketed(compressed_s[0])
    assert bx.num_buckets == 2
    bxd = _described(bx, one_chip)
    pts = jax.ShapeDtypeStruct((2, B, 2), jnp.float32, sharding=one_chip)
    fold = functools.partial(_fold_endpoint.jit, bucket=bucket,
                             use_kernels=True)
    try:
        ms, mt = _described(jax.eval_shape(fold, bxd, pts), one_chip)
        assert ms[1].shape == mt[1].shape == (B, bx.widths[bucket])
        for low in (_fold_endpoint.jit.lower(bxd, pts, bucket=bucket,
                                             use_kernels=True),
                    _join_endpoints.jit.lower(bxd, ms, mt, pts,
                                              use_kernels=True)):
            assert "tpu_custom_call" in low.compile().as_text()
    finally:
        # the traces above hold compiled-mode kernels: no later call on
        # the CPU may reuse them
        jax.clear_caches()
