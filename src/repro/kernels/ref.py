"""Pure-jnp oracles for the Pallas kernels (bit-for-bit semantics).

Every kernel in this package has its reference here; tests sweep shapes and
assert allclose(kernel(interpret=True), ref).  These references are also the
ops of the jnp engine, the serving default on every backend.
"""

from __future__ import annotations

import jax.numpy as jnp


def cross3(ax, ay, bx, by, px, py):
    """2D cross product (b - a) x (p - a), broadcasting."""
    return (bx - ax) * (py - ay) - (by - ay) * (px - ax)


# Zero-band width in units of machine epsilon.  A cross product whose two
# partial products mathematically cancel (endpoint exactly on a vertex or
# edge line, degenerate a == b edge, degenerate p == q segment) can come back
# as a few-ulp residual instead of 0.0 once XLA/Mosaic contracts the
# ``t1 - t2`` expression into an fma — the residual is bounded by ~1 ulp of
# the larger partial product, regardless of how the compiler fuses.  An 8x
# margin keeps every exact-contact class inside the band under any fusion
# while the band itself (~1e-6 relative) stays far below any genuine
# non-degenerate cross on map-scale coordinates.
SIGN_BAND = 8.0


def filtered_signs(t1, t2):
    """(pos, neg) of ``t1 - t2`` with a fusion-proof relative zero band.

    ``|t1 - t2| <= SIGN_BAND * eps * (|t1| + |t2|)`` classifies as zero
    (neither pos nor neg), so the §5 degenerate rules see exact contact as
    contact no matter how the backend compiled the arithmetic.
    """
    eps = SIGN_BAND * jnp.finfo(jnp.result_type(t1, t2)).eps
    d = t1 - t2
    tau = eps * (jnp.abs(t1) + jnp.abs(t2))
    return d > tau, d < -tau


def blocked_pairs(px, py, qx, qy, ax, ay, bx, by, cx, cy):
    """Per-(segment, edge) blocking predicate — the DESIGN.md §5 convention.

    All ten operands broadcast together.  Touching never blocks; interior
    penetration always blocks, including the degenerate entries:

    * proper crossing (both sign straddles, signs outside the zero band);
    * a segment endpoint on the open edge (in-band) with the other endpoint
      strictly on the interior (left, CCW) side;
    * the edge's b-vertex on the open segment (in-band, projection strictly
      interior) with the boundary arms ``a`` and ``c``
      (:attr:`Scene.edge_next`) strictly straddling it.

    Every sign test runs through :func:`filtered_signs`, so the predicate is
    stable under compiler fusion (fma contraction) and float32 coordinate
    rounding: a segment anchored exactly on a vertex stays "touching", never
    a phantom proper crossing.  Passing ``c == b`` disables the vertex rule
    (no adjacency information), and degenerate edges ``a == b`` never block
    — the padding guarantee (opposite filtered signs of two in-band values
    would need a residual larger than the band, which cannot happen).  This
    is the single predicate body shared by the jnp reference and both Pallas
    kernels (dense and grid-gathered tiles), so grid pruning and kernel/ref
    swaps stay bitwise-identical.
    """
    pos1, neg1 = filtered_signs((bx - ax) * (py - ay), (by - ay) * (px - ax))
    pos2, neg2 = filtered_signs((bx - ax) * (qy - ay), (by - ay) * (qx - ax))
    pos3, neg3 = filtered_signs((qx - px) * (ay - py), (qy - py) * (ax - px))
    pos4, neg4 = filtered_signs((qx - px) * (by - py), (qy - py) * (bx - px))
    pos5, neg5 = filtered_signs((qx - px) * (cy - py), (qy - py) * (cx - px))
    straddle12 = (pos1 & neg2) | (neg1 & pos2)
    straddle34 = (pos3 & neg4) | (neg3 & pos4)
    proper = straddle12 & straddle34
    zero1 = ~pos1 & ~neg1
    zero2 = ~pos2 & ~neg2
    touch_pen = ((zero1 & pos2) | (zero2 & pos1)) & straddle34
    dx = qx - px
    dy = qy - py
    tb = (bx - px) * dx + (by - py) * dy
    l2 = dx * dx + dy * dy
    tau = SIGN_BAND * jnp.finfo(jnp.result_type(l2)).eps * l2
    on_seg = (~pos4 & ~neg4) & (tb > tau) & (tb < l2 - tau)
    vert_pen = on_seg & ((pos3 & neg5) | (neg3 & pos5))
    return proper | touch_pen | vert_pen


def segvis_ref(p: jnp.ndarray, q: jnp.ndarray,
               ea: jnp.ndarray, eb: jnp.ndarray,
               ec: jnp.ndarray | None = None) -> jnp.ndarray:
    """[N] bool — True where segment p[i]->q[i] is blocked by NO edge.

    Sign-rule convention of :func:`blocked_pairs` (touching != blocked,
    interior penetration = blocked).  p, q: [N,2]; ea, eb, ec: [E,2];
    ``ec`` defaults to ``eb`` (vertex rule off) when adjacency is unknown.
    """
    if ec is None:
        ec = eb
    blocked = blocked_pairs(
        p[:, 0, None], p[:, 1, None], q[:, 0, None], q[:, 1, None],
        ea[None, :, 0], ea[None, :, 1], eb[None, :, 0], eb[None, :, 1],
        ec[None, :, 0], ec[None, :, 1])
    return ~blocked.any(axis=1)


def segvis_tiles_ref(p: jnp.ndarray, q: jnp.ndarray,
                     ax: jnp.ndarray, ay: jnp.ndarray,
                     bx: jnp.ndarray, by: jnp.ndarray,
                     cx: jnp.ndarray, cy: jnp.ndarray) -> jnp.ndarray:
    """[N] bool visibility over per-segment gathered edge tiles.

    The grid-pruned form: each segment i carries its own [S] edge slots
    (``repro.core.edgegrid.gather_edge_tiles``); unused slots hold the
    degenerate sentinel (a == b == c), which :func:`blocked_pairs` never
    blocks on.  Same predicate body as :func:`segvis_ref`, so results are
    bitwise-identical whenever the tiles cover every blocking edge.
    """
    blocked = blocked_pairs(
        p[:, 0, None], p[:, 1, None], q[:, 0, None], q[:, 1, None],
        ax, ay, bx, by, cx, cy)
    return ~blocked.any(axis=1)


def label_join_rowmin_ref(hub_s: jnp.ndarray, vd_s: jnp.ndarray,
                          hub_t: jnp.ndarray, vd_t: jnp.ndarray
                          ) -> jnp.ndarray:
    """[B, L] — per s-label: vd_s[i] + min over t-labels with equal hub.

    The dense-TPU form of the paper's sorted merge-join (Eq. 3): hub match is
    an L x L equality mask instead of a two-pointer scan.

    Accepts quantized (bf16/f16) ``vd`` inputs: they are widened in-register
    and the distance sum always accumulates in f32 (DESIGN.md §11).
    """
    inf = jnp.float32(jnp.inf)
    vd_s = vd_s.astype(jnp.float32)
    vd_t = vd_t.astype(jnp.float32)
    eq = hub_s[:, :, None] == hub_t[:, None, :]           # [B,L,L]
    matchmin = jnp.min(jnp.where(eq, vd_t[:, None, :], inf), axis=-1)
    return vd_s + matchmin


def label_join_ref(hub_s, vd_s, hub_t, vd_t) -> jnp.ndarray:
    """[B] — Eq. 3 distance through the best common hub."""
    return label_join_rowmin_ref(hub_s, vd_s, hub_t, vd_t).min(axis=-1)


def label_join_hubdense_ref(hub_s, vd_s, hub_t, vd_t, num_hubs: int
                            ) -> jnp.ndarray:
    """[B] — beyond-paper 'hub-scatter' join: segmented min into dense hub
    space then a min-plus reduction.  O(B*(L+H)) instead of O(B*L^2) and
    shardable over the label axis (each shard scatters locally, combine with
    a min-reduction collective).  Pads (hub id >= num_hubs) are dropped.
    """
    inf = jnp.float32(jnp.inf)
    vd_s = vd_s.astype(jnp.float32)
    vd_t = vd_t.astype(jnp.float32)
    B, L = hub_s.shape
    safe_s = jnp.clip(hub_s, 0, num_hubs - 1)
    safe_t = jnp.clip(hub_t, 0, num_hubs - 1)
    valid_s = hub_s < num_hubs
    valid_t = hub_t < num_hubs
    dense_s = jnp.full((B, num_hubs), inf).at[
        jnp.arange(B)[:, None], safe_s].min(jnp.where(valid_s, vd_s, inf))
    dense_t = jnp.full((B, num_hubs), inf).at[
        jnp.arange(B)[:, None], safe_t].min(jnp.where(valid_t, vd_t, inf))
    return (dense_s + dense_t).min(axis=-1)
