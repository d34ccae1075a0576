"""The served path's Pallas kernels compile for a TPU v5e (no chip needed).

The TPU compiler ships with libtpu, so it compiles for a chip that is
described and not attached.  Each case compiles one kernel at a width the
served index really produces and asserts the Mosaic ``tpu_custom_call`` is
in the executable — what interpret-mode tests cannot see: a tile the chip
refuses, or more VMEM than a kernel may use (``label_join_rowmin`` at
L=2048 once ran out of VMEM here).

Nothing touches libtpu at import: the topology is described inside a
module-scoped fixture, which skips where no v5e can be described.
"""

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


def test_segvis_tiles_compiles(one_chip):
    seg = ((TILE_N, 2), jnp.float32)
    tile = ((TILE_N, TILE_S), jnp.float32)
    compiled = _compile(
        lambda p, q, *tiles: segvis_tiles(p, q, *tiles, interpret=False),
        one_chip, seg, seg, *[tile] * 6)
    assert "tpu_custom_call" in compiled.as_text()
