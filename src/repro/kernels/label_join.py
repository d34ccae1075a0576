"""Pallas TPU kernel: hub-label merge-join (paper Eq. 3), dense form.

The CPU EHL join is a two-pointer scan over two sorted label lists — a
pointer-chasing pattern with data-dependent branches that maps terribly onto
the VPU.  TPU adaptation (DESIGN.md §3): compute the full ``[L, L]`` hub
equality mask and reduce with min-plus.  O(L^2) flops instead of O(L), but
branch-free, layout-regular and entirely VMEM-resident — the standard TPU
trade of redundant flops for regularity.  The kernel emits the *row join*
``out[b, i] = vd_s[b, i] + min_{j : hub_t[b,j] == hub_s[b,i]} vd_t[b, j]``
so the output tile keeps the lane-aligned [B_BLK, L] shape; the final
min-over-L happens in the jit wrapper (fused by XLA).

Memory: the grid tiles the batch and the s-side label axis, so a grid step
holds [B_BLK, S_BLK] s-side tiles, the whole [B_BLK, L] t-side rows, and one
[B_BLK, S_BLK, T_BLK] broadcast temp in VMEM.  At B_BLK=8, S_BLK=512,
T_BLK=128 the temp is 2 MB at every L; untiled on the s side it grows with
L and the chip's compiler refuses L=2048 for lack of VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEF_B_BLK = 8
S_BLK = 512
DEF_T_BLK = 128


def _join_kernel(hub_s_ref, vd_s_ref, hub_t_ref, vd_t_ref, out_ref,
                 *, t_blk: int):
    L = hub_t_ref.shape[1]
    hub_s = hub_s_ref[...]             # [BB, S] int32
    vd_s = vd_s_ref[...]               # [BB, S] f32
    inf = jnp.float32(jnp.inf)

    def body(k, matchmin):
        hub_t = hub_t_ref[:, pl.ds(k * t_blk, t_blk)]       # [BB, T]
        vd_t = vd_t_ref[:, pl.ds(k * t_blk, t_blk)]
        eq = hub_s[:, :, None] == hub_t[:, None, :]         # [BB, S, T]
        cand = jnp.min(jnp.where(eq, vd_t[:, None, :], inf), axis=-1)
        return jnp.minimum(matchmin, cand)

    matchmin = jax.lax.fori_loop(
        0, L // t_blk, body, jnp.full(hub_s.shape, inf, dtype=jnp.float32))
    out_ref[...] = vd_s + matchmin


# repolint: disable=jit-registry -- kernel microbench entry; serving wraps it via packed join entries
@functools.partial(jax.jit,
                   static_argnames=("b_blk", "t_blk", "interpret"))
def label_join_rowmin(hub_s: jnp.ndarray, vd_s: jnp.ndarray,
                      hub_t: jnp.ndarray, vd_t: jnp.ndarray,
                      *, b_blk: int = DEF_B_BLK, t_blk: int = DEF_T_BLK,
                      interpret: bool = False) -> jnp.ndarray:
    """[B, L] row join via the Pallas kernel (pads handled here).

    Pad rows use hub id HUB_PAD on the s side only — HUB_PAD == HUB_PAD
    matches pad-to-pad, but vd is +inf there so the min is unaffected.
    Quantized (bf16/f16) ``vd`` inputs are widened in-register; the kernel
    body always accumulates the distance sum in f32 (DESIGN.md §11).
    """
    B, L = hub_s.shape
    b_pad = (-B) % b_blk
    l_pad = (-L) % t_blk
    # s tiles: whole t tiles, at most S_BLK wide; L pads to whole s tiles
    s_blk = min(max(t_blk, S_BLK // t_blk * t_blk), L + l_pad)
    l_pad += (-(L + l_pad)) % s_blk
    inf = jnp.float32(jnp.inf)

    def padded(x, fill):
        return jnp.pad(x, ((0, b_pad), (0, l_pad)), constant_values=fill)

    hs = padded(hub_s.astype(jnp.int32), 2 ** 30)
    ht = padded(hub_t.astype(jnp.int32), 2 ** 30)
    vs = padded(vd_s.astype(jnp.float32), inf)
    vt = padded(vd_t.astype(jnp.float32), inf)
    Bp, Lp = hs.shape

    s_spec = pl.BlockSpec((b_blk, s_blk), lambda i, j: (i, j))
    t_spec = pl.BlockSpec((b_blk, Lp), lambda i, j: (i, 0))
    out = pl.pallas_call(
        functools.partial(_join_kernel, t_blk=t_blk),
        grid=(Bp // b_blk, Lp // s_blk),
        in_specs=[s_spec, s_spec, t_spec, t_spec],
        out_specs=s_spec,
        out_shape=jax.ShapeDtypeStruct((Bp, Lp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(hs, vs, ht, vt)
    return out[:B, :L]


def label_join(hub_s, vd_s, hub_t, vd_t, **kw) -> jnp.ndarray:
    """[B] Eq. 3 distances (min over the row join)."""
    return label_join_rowmin(hub_s, vd_s, hub_t, vd_t, **kw).min(axis=-1)
