"""Pallas TPU kernels: batched segment-vs-obstacle visibility predicate.

The query-phase hot spot of EHL on TPU (DESIGN.md §3): every query point must
test visibility against every via vertex of its region — N = B*L segments
against E obstacle edges, ~25 fused VPU ops per (segment, edge) pair with an
OR-reduction over edges.  Two forms:

* :func:`segvis` — dense: every segment against every edge, O(N*E).
  Segments stream through the grid's parallel axis in ``(2, SEG_BLK)``
  coordinate tiles (coords transposed so the lane dimension is the segment
  index); edges stream through an arbitrary-order reduction axis in
  ``(2, EDGE_BLK)`` tiles that stay resident in VMEM while a whole segment
  tile is processed.
* :func:`segvis_tiles` — grid-pruned: each segment carries its own ``[S]``
  pre-gathered edge slots (``repro.core.edgegrid``), O(N*S) with
  S = E_local << E on edge-heavy maps.  The [SEG_BLK, TILE_BLK] predicate
  tile never leaves VMEM; only the per-segment OR accumulator is written
  back.

Both kernels inline the exact predicate body of ``kernels.ref.blocked_pairs``
(DESIGN.md §5 convention: touching != blocked, interior penetration =
blocked, degenerate edges never block), so kernel/ref and dense/grid swaps
are bitwise-identical.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref


DEF_SEG_BLK = 256
DEF_EDGE_BLK = 512
DEF_TILE_BLK = 512


# The predicate tile IS ``ref.blocked_pairs`` — pure jnp arithmetic traces
# unchanged inside a Pallas kernel body, so the banded §5 convention (and
# ``ref.SIGN_BAND``) has exactly one jnp definition shared by the reference
# and both kernels; the float64 host twin lives in ``core.geometry``.
_blocked_tile = _ref.blocked_pairs


def _segvis_kernel(p_ref, q_ref, ea_ref, eb_ref, ec_ref, out_ref):
    """Grid = (num_seg_blocks, num_edge_blocks); out revisited over axis 1."""
    j = pl.program_id(1)

    px = p_ref[0, :][:, None]       # [SB,1]
    py = p_ref[1, :][:, None]
    qx = q_ref[0, :][:, None]
    qy = q_ref[1, :][:, None]
    ax = ea_ref[0, :][None, :]      # [1,EB]
    ay = ea_ref[1, :][None, :]
    bx = eb_ref[0, :][None, :]
    by = eb_ref[1, :][None, :]
    cx = ec_ref[0, :][None, :]
    cy = ec_ref[1, :][None, :]

    blocked = _blocked_tile(px, py, qx, qy, ax, ay, bx, by, cx, cy)
    blocked = blocked.any(axis=1).astype(jnp.int32)     # [SB]

    @pl.when(j == 0)
    def _init():
        out_ref[0, :] = blocked

    @pl.when(j > 0)
    def _acc():
        out_ref[0, :] = out_ref[0, :] | blocked


# repolint: disable=jit-registry -- build-time visibility kernel; never on the serving path
@functools.partial(jax.jit, static_argnames=("seg_blk", "edge_blk", "interpret"))
def segvis(p: jnp.ndarray, q: jnp.ndarray, ea: jnp.ndarray, eb: jnp.ndarray,
           ec: jnp.ndarray | None = None, *,
           seg_blk: int = DEF_SEG_BLK, edge_blk: int = DEF_EDGE_BLK,
           interpret: bool = False) -> jnp.ndarray:
    """[N] bool visibility via the Pallas kernel (pads handled here).

    Padding is loss-free: padded segments are degenerate points at the
    origin (no strict sign can fire), padded edges are degenerate repeats of
    the last edge slot (repeats never change the OR-reduction).  ``ec``
    defaults to ``eb`` — vertex rule off — when adjacency is unknown.
    """
    if ec is None:
        ec = eb
    N = p.shape[0]
    E = ea.shape[0]
    n_pad = (-N) % seg_blk
    e_pad = (-E) % edge_blk
    pT = jnp.pad(p.astype(jnp.float32), ((0, n_pad), (0, 0))).T  # [2, Np]
    qT = jnp.pad(q.astype(jnp.float32), ((0, n_pad), (0, 0))).T
    mode = "edge" if E else "constant"
    eaT = jnp.pad(ea.astype(jnp.float32), ((0, e_pad), (0, 0)), mode=mode).T
    ebT = jnp.pad(eb.astype(jnp.float32), ((0, e_pad), (0, 0)), mode=mode).T
    ecT = jnp.pad(ec.astype(jnp.float32), ((0, e_pad), (0, 0)), mode=mode).T
    Np = N + n_pad
    Ep = E + e_pad

    out = pl.pallas_call(
        _segvis_kernel,
        grid=(Np // seg_blk, Ep // edge_blk),
        in_specs=[
            pl.BlockSpec((2, seg_blk), lambda i, j: (0, i)),
            pl.BlockSpec((2, seg_blk), lambda i, j: (0, i)),
            pl.BlockSpec((2, edge_blk), lambda i, j: (0, j)),
            pl.BlockSpec((2, edge_blk), lambda i, j: (0, j)),
            pl.BlockSpec((2, edge_blk), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, seg_blk), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pT, qT, eaT, ebT, ecT)
    return out[0, :N] == 0


def _segvis_tiles_kernel(p_ref, q_ref, ax_ref, ay_ref, bx_ref, by_ref,
                         cx_ref, cy_ref, out_ref):
    """Grid = (num_seg_blocks, num_tile_blocks); out revisited over axis 1.

    Unlike the dense kernel, every edge-coordinate tile is [SEG_BLK,
    TILE_BLK]: segment i's row holds its own gathered edges, so the
    reduction axis is per-segment slots instead of the shared edge list.
    """
    j = pl.program_id(1)

    px = p_ref[0, :][:, None]       # [SB,1]
    py = p_ref[1, :][:, None]
    qx = q_ref[0, :][:, None]
    qy = q_ref[1, :][:, None]

    blocked = _blocked_tile(px, py, qx, qy,
                            ax_ref[...], ay_ref[...],
                            bx_ref[...], by_ref[...],
                            cx_ref[...], cy_ref[...])
    blocked = blocked.any(axis=1).astype(jnp.int32)     # [SB]

    @pl.when(j == 0)
    def _init():
        out_ref[0, :] = blocked

    @pl.when(j > 0)
    def _acc():
        out_ref[0, :] = out_ref[0, :] | blocked


# repolint: disable=jit-registry -- build-time visibility kernel; never on the serving path
@functools.partial(jax.jit, static_argnames=("seg_blk", "tile_blk",
                                             "interpret"))
def segvis_tiles(p: jnp.ndarray, q: jnp.ndarray,
                 ax: jnp.ndarray, ay: jnp.ndarray,
                 bx: jnp.ndarray, by: jnp.ndarray,
                 cx: jnp.ndarray, cy: jnp.ndarray, *,
                 seg_blk: int = DEF_SEG_BLK, tile_blk: int = DEF_TILE_BLK,
                 interpret: bool = False) -> jnp.ndarray:
    """[N] bool visibility over per-segment [N, S] gathered edge tiles.

    Kernel twin of ``ref.segvis_tiles_ref``.  Zero-padding is loss-free
    both ways: padded segments are degenerate origin points, padded slots
    are degenerate zero edges — neither can fire a strict sign rule.
    """
    N, S = ax.shape
    n_pad = (-N) % seg_blk
    s_blk = min(tile_blk, max(128, S))
    s_pad = (-S) % s_blk
    pT = jnp.pad(p.astype(jnp.float32), ((0, n_pad), (0, 0))).T  # [2, Np]
    qT = jnp.pad(q.astype(jnp.float32), ((0, n_pad), (0, 0))).T
    tiles = [jnp.pad(a.astype(jnp.float32), ((0, n_pad), (0, s_pad)))
             for a in (ax, ay, bx, by, cx, cy)]
    Np = N + n_pad
    Sp = S + s_pad

    seg_spec = pl.BlockSpec((2, seg_blk), lambda i, j: (0, i))
    tile_spec = pl.BlockSpec((seg_blk, s_blk), lambda i, j: (i, j))
    out = pl.pallas_call(
        _segvis_tiles_kernel,
        grid=(Np // seg_blk, Sp // s_blk),
        in_specs=[seg_spec, seg_spec] + [tile_spec] * 6,
        out_specs=pl.BlockSpec((1, seg_blk), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pT, qT, *tiles)
    return out[0, :N] == 0
