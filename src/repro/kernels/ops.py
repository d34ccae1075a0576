"""Jit'd public wrappers: kernel / reference dispatch.

``*_kernel`` entry points run the Pallas kernels: compiled by Mosaic on the
TPU, in the Pallas interpreter on the CPU (the test backend, where the exact
kernel bodies run), and nowhere else.  ``*_ref`` entry points are the
pure-jnp oracles.  ``repro.core.packed.query_batch`` picks via its
``use_kernels`` flag; tests assert both paths agree.
"""

from __future__ import annotations

import jax

from . import ref as _ref
from .label_join import label_join as _label_join_pallas
from .label_join import label_join_rowmin as _label_join_rowmin_pallas
from .segvis import segvis as _segvis_pallas
from .segvis import segvis_tiles as _segvis_tiles_pallas


def _interpret() -> bool:
    """Compiled on the TPU, interpreted on the CPU; no other backend."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"the Pallas TPU kernels have no {backend!r} "
                           "lowering; serve with the jnp backend there")
    return backend == "cpu"


# -- references (the jnp engine's ops) ---------------------------------------
segvis_ref = _ref.segvis_ref
segvis_tiles_ref = _ref.segvis_tiles_ref
label_join_ref = _ref.label_join_ref
label_join_rowmin_ref = _ref.label_join_rowmin_ref
label_join_hubdense_ref = _ref.label_join_hubdense_ref


def segvis_kernel(p, q, ea, eb, ec=None, **kw):
    kw.setdefault("interpret", _interpret())
    return _segvis_pallas(p, q, ea, eb, ec, **kw)


def segvis_tiles_kernel(p, q, ax, ay, bx, by, cx, cy, **kw):
    kw.setdefault("interpret", _interpret())
    return _segvis_tiles_pallas(p, q, ax, ay, bx, by, cx, cy, **kw)


def label_join_kernel(hub_s, vd_s, hub_t, vd_t, **kw):
    kw.setdefault("interpret", _interpret())
    return _label_join_pallas(hub_s, vd_s, hub_t, vd_t, **kw)


def label_join_rowmin_kernel(hub_s, vd_s, hub_t, vd_t, **kw):
    kw.setdefault("interpret", _interpret())
    return _label_join_rowmin_pallas(hub_s, vd_s, hub_t, vd_t, **kw)
