"""Dense tensor forms of an EHL/EHL* index — the TPU-resident artifact.

The host-side index (``repro.core.grid``) stores ragged per-region label
lists.  The online engine needs contiguous, gatherable tensors.  Two layouts
are provided (DESIGN.md §4):

* :class:`PackedIndex` — the single ``[R, Lmax]`` slab: every region padded
  to the global maximum label count.  Simple, one jit cache entry, but one
  oversized merged region inflates both ``device_bytes()`` and the O(L^2)
  label join for *every* query — the padding waste EHL*'s budget is supposed
  to eliminate.
* :class:`BucketedIndex` — regions grouped into power-of-two width buckets
  (multiples of ``lane``), each bucket its own dense slab, plus a
  ``region -> (bucket, row)`` indirection behind the cell mapper.
  ``device_bytes()`` then tracks the true EHL* budget, and queries dispatch
  per bucket so they only pay for the label width their regions actually
  need (``query_batch_at_bucket`` / the PathServer router).

Shared across layouts:

* ``edges_a/b/c``: flat obstacle-edge tensors for the query-time visibility
  predicate (``a``/``b`` endpoints plus the CCW next vertex ``c`` for the
  through-vertex rule; DESIGN.md §5 convention — touching != blocked,
  interior penetration = blocked).  Padding slots are provably degenerate
  (a == b == c), and at least one exists — the grid sentinel points at it.
* ``grid``: optional :class:`~repro.core.edgegrid.EdgeGrid` that prunes the
  visibility predicate from O(L·E) to O(L·E_local) (DESIGN.md §10);
  attached by the packers when it pays (or forced via ``edge_grid=True``),
  bitwise-identical to the dense predicate either way.
* ``mapper``: cell -> region row (single slab) or cell -> region id
  (bucketed), so point location stays O(1).
* one distance/join core — :func:`_mask_labels` (per-endpoint visibility +
  distance fold) feeding :func:`_join_masked` (hub join + co-visibility
  override) — used by every entry point; plain distances and argmin (path
  unwinding) are the same code path with a flag, for both the jnp
  reference and the Pallas kernels.  The sharded router calls the two
  halves on different devices (``gather_masked_labels`` /
  ``covis_blocked`` / ``join_masked``) with byte-identical results.

Everything is float32/int32 in the reference layout; the host oracle is
float64 — tests compare with ~1e-5 tolerances.

**Quantized slabs (DESIGN.md §11).**  Both layouts optionally store their
label slabs in a compressed on-device format (:class:`SlabLayout`):

* distances as bf16/f16 (per-bucket fallback to f32 when a finite distance
  would overflow the narrow dtype — f16 tops out at 65504);
* hub and via ids delta-encoded per region row into u16 against per-row
  i32 bases (pad sentinel ``0xFFFF``; per-bucket fallback to raw i32 when
  a row's id range exceeds what u16 can carry);
* the 8-byte-per-slot ``via_xy`` slab replaced by one shared ``[V, 2]``
  float32 vertex table gathered through the via id — exact, because the
  packers always filled ``via_xy`` with ``graph.nodes[via]``.

20 bytes/slot become 6.  The gathers decode in-register — ids back to
exact int32, distances widened to f32 — so every downstream op (visibility
fold, join, kernels) runs unchanged, and a *f32-layout* artifact compiles
the exact pre-quantization program (the layout is static aux).  Distances
come back within ``2*qerr`` of the f32 engine (``qerr`` is the measured
max quantization error, a device scalar riding the artifact); argmin
winners stay **bitwise-identical** via the residual rescue: the argmin
entries also emit an ambiguity mask (join margin within the quantization
error bound) and ambiguous rows are recomputed through
:func:`gather_masked_exact` with exact f32 distance rows from the
host-side :class:`ResidualTable` — the same arithmetic the f32 engine
runs, so the spliced winners (and path answers) match it bit for bit.
"""

from __future__ import annotations

import dataclasses
import threading
from functools import wraps

import numpy as np

import jax
import jax.numpy as jnp

try:                            # jax's own low-precision dtype package
    import ml_dtypes
    _BF16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:             # pragma: no cover - ml_dtypes ships with jax
    ml_dtypes = None
    _BF16 = None

from .edgegrid import (EdgeGrid, build_edge_grid, ell_bytes, plan_grid,
                       segvis_grid)
from .grid import EHLIndex

HUB_PAD = np.int32(2 ** 30)     # sorts after every real hub id
U16_PAD = np.uint16(0xFFFF)     # delta-encoded pad sentinel (u16 id slabs)


@dataclasses.dataclass(frozen=True)
class SlabLayout:
    """On-device slab dtypes — static (lives in pytree aux, keys jit caches).

    ``dist``: f32 | bf16 | f16 — label-distance storage dtype.
    ``ids``:  i32 | u16       — hub/via id storage (u16 = per-row delta).

    The f32/i32 default reproduces the historical layout bit for bit; any
    quantized layout also drops the per-slot ``via_xy`` pair in favor of
    the shared vertex table.
    """

    dist: str = "f32"
    ids: str = "i32"

    def __post_init__(self):
        if self.dist not in ("f32", "bf16", "f16"):
            raise ValueError(f"unknown distance dtype {self.dist!r}")
        if self.ids not in ("i32", "u16"):
            raise ValueError(f"unknown id dtype {self.ids!r}")

    @property
    def quantized(self) -> bool:
        return self.dist != "f32" or self.ids != "i32"

    @property
    def dist_dtype(self):
        if self.dist == "bf16":
            return _BF16
        return np.dtype(np.float16) if self.dist == "f16" \
            else np.dtype(np.float32)


LAYOUT_F32 = SlabLayout()


def slab_layout(name: str) -> SlabLayout:
    """CLI spelling -> layout: 'f32'/'off' | 'bf16' | 'f16'."""
    if name in ("f32", "off", "none", ""):
        return LAYOUT_F32
    if name in ("bf16", "f16"):
        return SlabLayout(dist=name, ids="u16")
    raise ValueError(f"unknown slab layout {name!r} (f32 | bf16 | f16)")


@dataclasses.dataclass(frozen=True)
class LayoutBytes:
    """Analytic byte costs of a :class:`SlabLayout` (see :func:`dtype_bytes`)."""
    per_slot: int               # bytes per label slot (slab area term)
    per_row: int                # bytes per slab row (delta-encoding bases)
    per_vertex: int             # bytes per graph vertex (shared xy table)


def dtype_bytes(layout: SlabLayout = LAYOUT_F32) -> LayoutBytes:
    """Single source of per-slot/per-row/per-vertex byte math.

    Every analytic estimator (:func:`slab_device_bytes`,
    :func:`bucketed_device_bytes`, the shard planner's balance weights and
    ``sharded_overhead_bytes``) routes through this helper, so planner
    decisions, per-shard budget gates and bench padding-waste rows all
    agree with the real slab dtypes.  Estimates assume no per-bucket
    fallback (the realized ``device_bytes()`` is authoritative when a
    bucket overflowed its narrow dtype).
    """
    if not layout.quantized:
        return LayoutBytes(per_slot=4 + 8 + 4 + 4,  # hub + xy + d + vid
                           per_row=0, per_vertex=0)
    id_b = 2 if layout.ids == "u16" else 4
    dist_b = layout.dist_dtype.itemsize
    return LayoutBytes(per_slot=2 * id_b + dist_b,  # hub_enc + d + via_enc
                       per_row=(8 if layout.ids == "u16" else 0),
                       per_vertex=8)                # shared [V, 2] f32 table


class ResidualTable:
    """Host-side exact f32 distance rows — the residual the rescue reads.

    Per bucket, the pre-quantization float32 ``via_d`` slab plus int32
    routing mirrors (mapper / region -> bucket / row), ~4 bytes per label
    slot of host memory.  Only *distances* are kept: the device slabs
    already decode hub/via ids to their exact int32 values, so the rescue
    only has to replace the quantized distance term
    (:func:`gather_masked_exact`).  Host-resident, never uploaded whole —
    ambiguous batches gather [B, W] rows and ship just those.
    """

    def __init__(self, d_slabs, region_bucket, region_row, mapper,
                 widths, nx: int, ny: int, cell_size: float):
        self.d = [np.ascontiguousarray(np.asarray(a, np.float32))
                  for a in d_slabs]
        self.region_bucket = np.asarray(region_bucket, np.int32)
        self.region_row = np.asarray(region_row, np.int32)
        self.mapper = np.asarray(mapper, np.int32)
        self.widths = tuple(int(w) for w in widths)
        self.nx, self.ny = int(nx), int(ny)
        self.cell_size = float(cell_size)

    def locate(self, pts: np.ndarray) -> np.ndarray:
        """[B] region ids — the same float32 floor-divide as
        :func:`locate_regions`, so host rows match device gathers exactly."""
        p = np.asarray(pts, np.float32)
        cs = np.float32(self.cell_size)
        ix = np.clip((p[:, 0] / cs).astype(np.int32), 0, self.nx - 1)
        iy = np.clip((p[:, 1] / cs).astype(np.int32), 0, self.ny - 1)
        return self.mapper[iy * self.nx + ix]

    def gather_d(self, regions: np.ndarray, width: int) -> np.ndarray:
        """[B, width] exact f32 distance rows, inf-padded — the host mirror
        of the distance plane of :func:`_gather_bucketed`."""
        regions = np.asarray(regions)
        out = np.full((len(regions), width), np.inf, np.float32)
        b = self.region_bucket[regions]
        r = self.region_row[regions]
        for k, w in enumerate(self.widths):
            if w > width:
                continue        # wider buckets stay padding, as on device
            m = b == k
            if m.any():
                rows = np.minimum(r[m], self.d[k].shape[0] - 1)
                out[np.nonzero(m)[0][:, None],
                    np.arange(w)[None, :]] = self.d[k][rows]
        return out


class TraceCounter:
    """Counts jit *traces* of the serving entry points below.

    A trace is 1:1 with a fresh XLA compilation for that (static args,
    shapes, dtypes) cache entry, so serving code can assert "warmup left
    nothing cold": snapshot ``TRACES.count``, serve, and require the count
    unchanged.  Bumps happen inside the traced bodies — they run at trace
    time only, never per call.

    ``count`` stays the in-process fast path; each bump also lands on an
    entry-labeled ``jit_traces_total{entry=}`` counter in the process-wide
    metrics registry so cold-compile events show up in the Prometheus/JSON
    exports next to the serving series they perturb (DESIGN.md §12/§13).

    Two profiling hooks ride along (DESIGN.md §13): a *thread-local*
    count (``thread_count()``) lets :class:`repro.obs.CompileCapture`
    detect "this call traced" without crediting a background build
    thread's compile to a foreground serving call, and ``profiler`` is
    the installed capture (None when profiling is off — the only cost
    then is one attribute read per entry call).
    """

    def __init__(self):
        self.count = 0
        self.profiler = None            # CompileCapture | None
        self._tl = threading.local()
        self._metrics = {}

    def thread_count(self) -> int:
        return getattr(self._tl, "count", 0)

    def bump(self, entry: str = "") -> None:
        self.count += 1
        self._tl.count = self.thread_count() + 1
        m = self._metrics.get(entry)
        if m is None:
            # deferred: repro.obs is import-light (numpy + stdlib), but
            # binding lazily keeps module import order unconstrained
            from repro.obs import REGISTRY
            m = (REGISTRY.counter("jit_traces_total", entry=entry)
                 if entry else REGISTRY.counter("jit_traces_total"))
            self._metrics[entry] = m
        m.inc()


TRACES = TraceCounter()

#: The jit entry taxonomy: every ``@_jit_entry("name")`` in the tree, in
#: rough serving-path order.  Static so tests, docs, and the
#: ``jit-registry`` checker can enumerate the surface without tracing;
#: the checker fails CI if this tuple and the decorators ever drift.
TRACE_ENTRIES = (
    "fold_endpoint",
    "join_endpoints",
    "gather_labels_at_width",
    "join_gathered",
    "gather_masked_labels",
    "covis_blocked",
    "join_masked",
    "gather_masked_exact",
    "gather_quant_rows",
    "dequant_masked_labels",
)


def _jit_entry(entry: str, **jit_kw):
    """``jax.jit`` for a named serving entry, routed via the profiler.

    With no profiler installed the wrapper is one attribute read + one
    ``is None`` per call on top of the jit dispatch.  With one installed
    (:func:`repro.obs.enable_profile`) the call goes through
    ``CompileCapture.call``, which times the call and — when the entry's
    ``TRACES.bump(entry)`` fired on this thread, i.e. the call traced —
    attributes compile wall-time and XLA ``cost_analysis()`` to the
    entry label.  The traced body must call ``TRACES.bump(entry)`` with
    the same name.
    """
    def deco(fn):
        jf = jax.jit(fn, **jit_kw)

        @wraps(fn)
        def wrapper(*args, **kw):
            prof = TRACES.profiler
            if prof is None:
                return jf(*args, **kw)
            return prof.call(entry, jf, args, kw)

        wrapper.jit = jf                # the underlying jit callable
        wrapper.entry = entry
        return wrapper
    return deco


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def padded_edge_count(num_edges: int, lane: int = 128) -> int:
    """Packed edge-tensor length: lane-aligned with >= 1 degenerate slot."""
    return _round_up(num_edges + 1, lane)


def bucket_width(n_labels: int, lane: int = 128) -> int:
    """Smallest power-of-two multiple of ``lane`` holding ``n_labels``."""
    w = lane
    while w < n_labels:
        w *= 2
    return w


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedIndex:
    """Single-slab layout: pytree of device arrays (static geometry in aux)."""

    hub_ids: jnp.ndarray    # [R, L] int32 (or u16 delta vs hub_base), sorted
    via_xy: jnp.ndarray     # [R, L, 2] float32, or None (quantized: vert_xy)
    via_d: jnp.ndarray      # [R, L] float32/bf16/f16 (+inf on pads)
    via_ids: jnp.ndarray    # [R, L] int32 (-1 pads) or u16 delta vs vid_base
    mapper: jnp.ndarray     # [C] int32 cell -> region row
    edges_a: jnp.ndarray    # [E, 2] float32 (degenerate-padded)
    edges_b: jnp.ndarray    # [E, 2] float32
    edges_c: jnp.ndarray    # [E, 2] float32 CCW next vertex (§5 vertex rule)
    grid: EdgeGrid | None   # edge-grid pruning (DESIGN.md §10), or None
    # static metadata
    nx: int
    ny: int
    cell_size: float
    width: float
    height: float
    # quantized-layout extras (§11) — all None under the f32 layout
    vert_xy: jnp.ndarray | None = None      # [V, 2] f32 shared vertex table
    hub_base: jnp.ndarray | None = None     # [R] i32 per-row hub id base
    vid_base: jnp.ndarray | None = None     # [R] i32 per-row via id base
    qerr: jnp.ndarray | None = None         # f32 scalar max |f32(dq) - d|
    layout: SlabLayout = LAYOUT_F32
    residual: ResidualTable | None = dataclasses.field(
        default=None, repr=False, compare=False)   # host-side, not a leaf

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.hub_ids, self.via_xy, self.via_d, self.via_ids,
                    self.mapper, self.edges_a, self.edges_b, self.edges_c,
                    self.grid, self.vert_xy, self.hub_base, self.vid_base,
                    self.qerr)
        aux = (self.nx, self.ny, self.cell_size, self.width, self.height,
               self.layout)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:9], *aux[:5],
                   vert_xy=children[9], hub_base=children[10],
                   vid_base=children[11], qerr=children[12], layout=aux[5])

    # -- properties ----------------------------------------------------------
    @property
    def num_regions(self) -> int:
        return self.hub_ids.shape[0]

    @property
    def label_width(self) -> int:
        return self.hub_ids.shape[1]

    @property
    def num_edges(self) -> int:
        return self.edges_a.shape[0]

    def device_bytes(self) -> int:
        arrs = (self.hub_ids, self.via_xy, self.via_d, self.via_ids,
                self.mapper, self.edges_a, self.edges_b, self.edges_c,
                self.vert_xy, self.hub_base, self.vid_base)
        base = sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in arrs if a is not None)
        return int(base) + (self.grid.device_bytes() if self.grid else 0)

    def label_slots(self) -> tuple[int, int]:
        """(used, total) label slots — padding waste is total - used."""
        used = int(_used_mask(self.hub_ids).sum())
        return used, int(np.prod(self.hub_ids.shape))

    def quant_stats(self) -> dict:
        """Realized quantization record (fallbacks are loud, not silent)."""
        return _quant_stats(self.layout, (self.hub_ids,), (self.via_d,),
                            (self.via_ids,), self.qerr)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BucketedIndex:
    """Width-bucketed layout: one dense slab per power-of-two label width.

    Region ``r`` lives at ``(region_bucket[r], region_row[r])``; slab ``k``
    has shape ``[R_k, widths[k]]``.  The mapper resolves cells to region ids
    (not rows), so point location composes with the indirection in O(1).
    """

    hub_ids: tuple          # per bucket: [R_k, W_k] int32 or u16 delta
    via_xy: tuple           # per bucket: [R_k, W_k, 2] float32 (or () §11)
    via_d: tuple            # per bucket: [R_k, W_k] f32/bf16/f16 (+inf pads)
    via_ids: tuple          # per bucket: [R_k, W_k] int32 (-1 pads) or u16
    mapper: jnp.ndarray     # [C] int32 cell -> region id
    region_bucket: jnp.ndarray  # [R] int32 region id -> bucket
    region_row: jnp.ndarray     # [R] int32 region id -> row in its slab
    edges_a: jnp.ndarray    # [E, 2] float32 (degenerate-padded)
    edges_b: jnp.ndarray    # [E, 2] float32
    edges_c: jnp.ndarray    # [E, 2] float32 CCW next vertex (§5 vertex rule)
    grid: EdgeGrid | None   # edge-grid pruning (DESIGN.md §10), or None
    # static metadata
    nx: int
    ny: int
    cell_size: float
    width: float
    height: float
    widths: tuple           # per-bucket label width, strictly increasing
    # quantized-layout extras (§11) — all None/() under the f32 layout
    vert_xy: jnp.ndarray | None = None      # [V, 2] f32 shared vertex table
    hub_base: tuple = ()                    # per bucket: [R_k] i32 row base
    vid_base: tuple = ()                    # per bucket: [R_k] i32 row base
    qerr: jnp.ndarray | None = None         # f32 scalar max |f32(dq) - d|
    layout: SlabLayout = LAYOUT_F32
    residual: ResidualTable | None = dataclasses.field(
        default=None, repr=False, compare=False)   # host-side, not a leaf

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.hub_ids, self.via_xy, self.via_d, self.via_ids,
                    self.mapper, self.region_bucket, self.region_row,
                    self.edges_a, self.edges_b, self.edges_c, self.grid,
                    self.vert_xy, self.hub_base, self.vid_base, self.qerr)
        aux = (self.nx, self.ny, self.cell_size, self.width, self.height,
               self.widths, self.layout)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:11], *aux[:6],
                   vert_xy=children[11], hub_base=children[12],
                   vid_base=children[13], qerr=children[14], layout=aux[6])

    # -- properties ----------------------------------------------------------
    @property
    def num_buckets(self) -> int:
        return len(self.widths)

    @property
    def num_regions(self) -> int:
        return self.region_bucket.shape[0]

    @property
    def label_width(self) -> int:
        """Widest bucket — what a single slab would pad everything to."""
        return self.widths[-1] if self.widths else 0

    @property
    def num_edges(self) -> int:
        return self.edges_a.shape[0]

    def device_bytes(self) -> int:
        slabs = sum(np.prod(a.shape) * a.dtype.itemsize
                    for group in (self.hub_ids, self.via_xy, self.via_d,
                                  self.via_ids, self.hub_base, self.vid_base)
                    for a in group)
        fixed = sum(np.prod(a.shape) * a.dtype.itemsize for a in
                    (self.mapper, self.region_bucket, self.region_row,
                     self.edges_a, self.edges_b, self.edges_c))
        if self.vert_xy is not None:
            fixed += np.prod(self.vert_xy.shape) * self.vert_xy.dtype.itemsize
        return (int(slabs) + int(fixed)
                + (self.grid.device_bytes() if self.grid else 0))

    def bucket_stats(self) -> list[dict]:
        """Per-bucket occupancy: regions, used/total label slots, waste."""
        out = []
        for k, w in enumerate(self.widths):
            hub = np.asarray(self.hub_ids[k])
            used = int(_used_mask(hub).sum())
            total = int(np.prod(hub.shape))
            out.append(dict(bucket=k, width=w, regions=hub.shape[0],
                            used_slots=used, total_slots=total,
                            waste=1.0 - used / max(1, total)))
        return out

    def quant_stats(self) -> dict:
        """Realized quantization record (fallbacks are loud, not silent)."""
        return _quant_stats(self.layout, self.hub_ids, self.via_d,
                            self.via_ids, self.qerr)

    def label_slots(self) -> tuple[int, int]:
        """(used, total) label slots across all buckets."""
        st = self.bucket_stats()
        return (sum(s["used_slots"] for s in st),
                sum(s["total_slots"] for s in st))


# ---------------------------------------------------------------------------
# packing (host -> device layouts)
# ---------------------------------------------------------------------------

def _host_packs(index: EHLIndex):
    """Live regions in rid order with their packed (ragged) label arrays."""
    live = sorted(index.regions.keys())
    packs = [index.pack_region(index.regions[rid]) for rid in live]
    return live, packs


def _fill_row(arrs, i, p):
    hub_ids, via_xy, via_d, via_ids = arrs
    k = len(p["hubs"])
    hub_ids[i, :k] = p["hubs"]
    via_xy[i, :k] = p["via_xy"]
    via_d[i, :k] = p["d"]
    via_ids[i, :k] = p["vias"]


def _alloc_slab(rows: int, width: int):
    return (np.full((rows, width), HUB_PAD, dtype=np.int32),
            np.zeros((rows, width, 2), dtype=np.float32),
            np.full((rows, width), np.inf, dtype=np.float32),
            np.full((rows, width), -1, dtype=np.int32))


# ---------------------------------------------------------------------------
# quantized slab encoding (DESIGN.md §11)
# ---------------------------------------------------------------------------

def _used_mask(hub_arr) -> np.ndarray:
    """Real-label mask for either id encoding (u16 sentinel vs HUB_PAD)."""
    a = np.asarray(hub_arr)
    return (a != np.uint16(U16_PAD)) if a.dtype == np.uint16 \
        else (a != HUB_PAD)


def encode_delta_u16(ids: np.ndarray, valid: np.ndarray):
    """Per-row delta encoding of an id slab into u16 + [R] i32 bases.

    Returns ``(enc, base)`` with pad slots at the ``0xFFFF`` sentinel, or
    ``(None, None)`` when any row's id range exceeds 65534 — the caller
    must then keep the raw i32 slab (the loud per-bucket fallback).
    """
    ids = np.asarray(ids, np.int64)
    any_valid = valid.any(axis=1)
    lo = np.where(valid, ids, np.iinfo(np.int64).max).min(axis=1)
    lo = np.where(any_valid, lo, 0)
    hi = np.where(valid, ids, np.iinfo(np.int64).min).max(axis=1)
    hi = np.where(any_valid, hi, 0)
    if int((hi - lo).max(initial=0)) > 0xFFFE:      # 0xFFFF is the pad
        return None, None
    enc = np.where(valid, ids - lo[:, None], 0xFFFF)
    return enc.astype(np.uint16), lo.astype(np.int32)


def encode_dist(d: np.ndarray, dtype) -> tuple:
    """Quantize a f32 distance slab; returns ``(dq, qerr)``.

    ``(None, 0.0)`` when any *finite* distance overflows to inf in the
    narrow dtype (f16 tops out at 65504) — per-bucket fallback to f32.
    +inf pads are representable in every dtype and round-trip exactly.
    """
    d = np.asarray(d, np.float32)
    with np.errstate(over="ignore"):
        dq = d.astype(dtype)
        back = dq.astype(np.float32)
    finite = np.isfinite(d)
    if np.any(finite & ~np.isfinite(back)):
        return None, 0.0
    err = np.abs(back[finite] - d[finite])
    return dq, float(err.max(initial=0.0))


def _quantize_slab(arrs, layout: SlabLayout):
    """Encode one (hub, xy, d, vid) f32 slab into the quantized layout.

    Returns ``(hub, d, vid, hub_base, vid_base, qerr)`` — ids u16-delta
    (or raw i32 on range overflow, per bucket), distances in
    ``layout.dist_dtype`` (or f32 on finite-overflow, per bucket).  The
    ``via_xy`` plane is dropped entirely: it is always
    ``vert_xy[via_id]`` (see ``EHLIndex.pack_region``), so the shared
    vertex table replaces it exactly.
    """
    hub, _, d, vid = arrs
    R = hub.shape[0]
    zeros = np.zeros(R, np.int32)
    hub_q, hub_base = hub, zeros
    vid_q, vid_base = vid, zeros
    if layout.ids == "u16":
        enc, base = encode_delta_u16(hub, hub != HUB_PAD)
        if enc is not None:
            hub_q, hub_base = enc, base
        enc, base = encode_delta_u16(vid, vid >= 0)
        if enc is not None:
            vid_q, vid_base = enc, base
    d_q, qerr = d, 0.0
    if layout.dist != "f32":
        dq, err = encode_dist(d, layout.dist_dtype)
        if dq is not None:
            d_q, qerr = dq, err
    return hub_q, d_q, vid_q, hub_base, vid_base, qerr


def _quant_stats(layout: SlabLayout, hub_ids, via_d, via_ids, qerr) -> dict:
    """Per-bucket realized encoding + fallback flags (never silent)."""
    return dict(
        layout=layout,
        qerr=(float(np.asarray(qerr)) if qerr is not None else 0.0),
        id_fallback=tuple(np.asarray(h).dtype != np.uint16
                          for h in hub_ids) if layout.ids == "u16" else (),
        vid_fallback=tuple(np.asarray(v).dtype != np.uint16
                           for v in via_ids) if layout.ids == "u16" else (),
        dist_fallback=tuple(
            np.asarray(d).dtype != layout.dist_dtype for d in via_d)
        if layout.dist != "f32" else ())


def _vert_table(index: EHLIndex) -> jnp.ndarray:
    """[V, 2] f32 shared vertex table — exactly the values the f32 packers
    wrote per slot (``via_xy = graph.nodes[via]`` cast to float32)."""
    return jnp.asarray(np.asarray(index.graph.nodes, np.float32))


def _cell_mapper(index: EHLIndex, live: list) -> np.ndarray:
    """[C] int32 cell -> dense index into the live-region ordering."""
    row_of = {rid: i for i, rid in enumerate(live)}
    mapper = np.zeros(index.mapper.size, dtype=np.int32)
    for ci, rid in enumerate(index.mapper):
        mapper[ci] = row_of[int(rid)]
    return mapper


def _pack_edges(scene_or_index, lane: int, mask: np.ndarray | None = None):
    """Pack (a, b, c) edge tensors, degenerate-padded with >= 1 sentinel.

    ``mask`` selects an edge subset (the per-shard clip path); order is
    preserved so duplicate registrations stay deterministic.  Every padding
    slot is the degenerate triple (a == b == c) — provably non-blocking
    under the §5 predicate for *every* query segment — and the last slot is
    always padding, so it doubles as the edge-grid sentinel.
    """
    scene = getattr(scene_or_index, "scene", scene_or_index)
    edges = scene.edges
    enext = scene.edge_next
    if mask is not None:
        edges = edges[mask]
        enext = enext[mask]
    E = edges.shape[0]
    Ep = padded_edge_count(E, lane)
    ea = np.zeros((Ep, 2), dtype=np.float32)
    eb = np.zeros((Ep, 2), dtype=np.float32)
    ec = np.zeros((Ep, 2), dtype=np.float32)
    if E:
        ea[:E] = edges[:, 0]
        eb[:E] = edges[:, 1]
        ec[:E] = enext
        ea[E:] = eb[E:] = ec[E:] = edges[0, 0]   # degenerate pads
    assert np.array_equal(ea[E:], eb[E:]) and np.array_equal(eb[E:], ec[E:]) \
        and Ep > E, "edge padding must be degenerate (a == b == c)"
    return ea, eb, ec


def _maybe_grid(ea: np.ndarray, eb: np.ndarray, num_real: int,
                scene, edge_grid: bool | None) -> EdgeGrid | None:
    """Build the edge grid when forced or when pruning pays.

    ``edge_grid=None`` (auto) attaches the grid only when the per-segment
    gathered tile is smaller than the dense edge list — on small suite maps
    the dense O(L·E) sweep is already cheaper than the walk's padding, on
    edge-heavy maps the grid wins by orders of magnitude.  ``True``/
    ``False`` force.  Deterministic, mirrored by the analytic byte helpers.
    """
    if edge_grid is False:
        return None
    if edge_grid is None:
        # decide host-side (plan_grid: no device arrays) before building —
        # on dense-favored maps the grid would be discarded right away
        gnx, gny, _, M = plan_grid(ea, eb, num_real, scene.width,
                                   scene.height)
        if 3 * max(gnx, gny) * M >= ea.shape[0]:
            return None
    return build_edge_grid(ea, eb, num_real, scene.width, scene.height,
                           sentinel=ea.shape[0] - 1)


_GRID_PLAN_CACHE: dict = {}


def _grid_bytes(index: EHLIndex, lane: int, edge_grid: bool | None) -> int:
    """Analytic twin of :func:`_maybe_grid` for the byte estimators.

    Pure host arithmetic (:func:`plan_grid`), memoized per scene — the
    budget searches in ``core.compression`` and the adaptive planner call
    the byte estimators every round, the scene never changes for an
    index's lifetime, and this must never build device arrays."""
    if edge_grid is False:
        return 0
    scene = index.scene
    E = scene.edges.shape[0]
    key = (hash(scene.edges.tobytes()), E, lane,
           float(scene.width), float(scene.height))
    plan = _GRID_PLAN_CACHE.get(key)
    if plan is None:
        ea, eb, _ = _pack_edges(index, lane)
        gnx, gny, _, M = plan_grid(ea, eb, E, scene.width, scene.height)
        if len(_GRID_PLAN_CACHE) >= 64:
            _GRID_PLAN_CACHE.clear()
        plan = _GRID_PLAN_CACHE[key] = (gnx, gny, M, ea.shape[0])
    gnx, gny, M, Ep = plan
    if edge_grid is None and 3 * max(gnx, gny) * M >= Ep:
        return 0                      # the auto policy stays dense
    return ell_bytes(gnx, gny, M)


def slab_label_slots(index: EHLIndex, lane: int = 128,
                     region_pad_multiple: int = 1) -> tuple[int, int]:
    """(used, total) label slots of the would-be single slab, analytically."""
    counts = index.packed_label_counts()
    R = _round_up(max(1, len(counts)), region_pad_multiple)
    L = _round_up(max(1, int(counts.max(initial=1))), lane)
    return int(counts.sum()), R * L


def slab_device_bytes(index: EHLIndex, lane: int = 128,
                      region_pad_multiple: int = 1,
                      edge_grid: bool | None = None,
                      layout: SlabLayout = LAYOUT_F32) -> int:
    """What ``pack_index(...).device_bytes()`` would be, without packing.

    Lets callers report the single-slab footprint for comparison against the
    bucketed layout without materializing the global-Lmax slab on device.
    """
    _, slots = slab_label_slots(index, lane, region_pad_multiple)
    lb = dtype_bytes(layout)
    counts = index.packed_label_counts()
    R = _round_up(max(1, len(counts)), region_pad_multiple)
    Ep = padded_edge_count(index.scene.edges.shape[0], lane)
    return (slots * lb.per_slot + R * lb.per_row
            + index.graph.num_nodes * lb.per_vertex
            + index.mapper.size * 4 + 3 * Ep * 2 * 4
            + _grid_bytes(index, lane, edge_grid))


def pack_index(index: EHLIndex, lane: int = 128,
               region_pad_multiple: int = 1,
               edge_grid: bool | None = None,
               layout: SlabLayout = LAYOUT_F32) -> PackedIndex:
    """Freeze a (possibly compressed) host index into one global-Lmax slab.

    ``edge_grid``: ``None`` attaches the §10 edge grid when pruning pays,
    ``True``/``False`` force it on/off.

    ``layout``: quantized layouts store distances narrow, ids u16-delta,
    drop ``via_xy`` for the shared vertex table, and attach the host-side
    :class:`ResidualTable` the exact-argmin rescue reads (DESIGN.md §11).
    """
    live, packs = _host_packs(index)
    R = _round_up(len(live), region_pad_multiple)

    Lmax = max((len(p["hubs"]) for p in packs), default=1)
    L = _round_up(max(Lmax, 1), lane)

    arrs = _alloc_slab(R, L)
    for i, p in enumerate(packs):
        _fill_row(arrs, i, p)

    mapper = _cell_mapper(index, live)
    ea, eb, ec = _pack_edges(index, lane)
    grid = _maybe_grid(ea, eb, index.scene.edges.shape[0], index.scene,
                       edge_grid)
    if layout.quantized:
        hub_q, d_q, vid_q, hb, vb, qerr = _quantize_slab(arrs, layout)
        residual = ResidualTable(
            (arrs[2],), np.zeros(R, np.int32), np.arange(R, dtype=np.int32),
            mapper, (L,), index.nx, index.ny, float(index.cell_size))
        return PackedIndex(
            hub_ids=jnp.asarray(hub_q), via_xy=None,
            via_d=jnp.asarray(d_q), via_ids=jnp.asarray(vid_q),
            mapper=jnp.asarray(mapper), edges_a=jnp.asarray(ea),
            edges_b=jnp.asarray(eb), edges_c=jnp.asarray(ec), grid=grid,
            nx=index.nx, ny=index.ny,
            cell_size=float(index.cell_size), width=float(index.scene.width),
            height=float(index.scene.height),
            vert_xy=_vert_table(index), hub_base=jnp.asarray(hb),
            vid_base=jnp.asarray(vb), qerr=jnp.float32(qerr),
            layout=layout, residual=residual)
    return PackedIndex(
        hub_ids=jnp.asarray(arrs[0]), via_xy=jnp.asarray(arrs[1]),
        via_d=jnp.asarray(arrs[2]), via_ids=jnp.asarray(arrs[3]),
        mapper=jnp.asarray(mapper), edges_a=jnp.asarray(ea),
        edges_b=jnp.asarray(eb), edges_c=jnp.asarray(ec), grid=grid,
        nx=index.nx, ny=index.ny,
        cell_size=float(index.cell_size), width=float(index.scene.width),
        height=float(index.scene.height))


def plan_buckets(index: EHLIndex, lane: int = 128
                 ) -> tuple[list, list, np.ndarray]:
    """Bucket assignment from the grid's pack metadata — no device arrays.

    Returns (per-region label counts, bucket widths, region -> bucket).
    Single definition shared by ``pack_bucketed`` and the analytic
    accounting helpers below.
    """
    counts = [max(1, int(c)) for c in index.packed_label_counts()]
    widths = sorted({bucket_width(c, lane) for c in counts}) or [lane]
    bucket_of_width = {w: k for k, w in enumerate(widths)}
    region_bucket = np.array([bucket_of_width[bucket_width(c, lane)]
                              for c in counts], dtype=np.int32)
    return counts, widths, region_bucket


def bucketed_device_bytes(index: EHLIndex, lane: int = 128,
                          edge_grid: bool | None = None,
                          layout: SlabLayout = LAYOUT_F32) -> int:
    """What ``pack_bucketed(...).device_bytes()`` would be, without packing."""
    counts, widths, region_bucket = plan_buckets(index, lane)
    lb = dtype_bytes(layout)
    slabs = sum(max(1, int((region_bucket == k).sum()))
                * (w * lb.per_slot + lb.per_row)
                for k, w in enumerate(widths))
    Ep = padded_edge_count(index.scene.edges.shape[0], lane)
    return (slabs + index.graph.num_nodes * lb.per_vertex
            + index.mapper.size * 4 + 2 * len(counts) * 4
            + 3 * Ep * 2 * 4 + _grid_bytes(index, lane, edge_grid))


def pack_bucketed(index: EHLIndex, lane: int = 128,
                  reuse_edges_from: "BucketedIndex | PackedIndex | None" = None,
                  edge_grid: bool | None = None,
                  layout: SlabLayout = LAYOUT_F32) -> BucketedIndex:
    """Freeze a host index into width-bucketed slabs (DESIGN.md §4).

    Each region goes into the smallest power-of-two-multiple-of-``lane``
    bucket that holds its label count, so padding waste is < 50% per region
    instead of being governed by the single largest merged region.

    ``reuse_edges_from``: repack-from-index fast path for the adaptive
    hot-swap loop — the scene (and thus the padded edge tensors and the
    edge grid built from them) never changes across recompressions, so the
    previous artifact's device-resident ``edges_a/b/c`` and ``grid`` are
    aliased instead of re-uploaded.  Region packs untouched since the last
    pack are already reused via the per-region ``packed`` cache
    (:meth:`EHLIndex.pack_region`).

    ``edge_grid``: ``None`` attaches the §10 edge grid when pruning pays,
    ``True``/``False`` force it on/off (ignored when reusing — the previous
    artifact's decision carries over with its arrays).
    """
    live, packs = _host_packs(index)
    counts, widths, region_bucket = plan_buckets(index, lane)
    region_row = np.zeros(len(live), dtype=np.int32)
    members: list[list[int]] = [[] for _ in widths]
    for i, b in enumerate(region_bucket):
        region_row[i] = len(members[b])
        members[b].append(i)

    slabs = []
    for k, w in enumerate(widths):
        arrs = _alloc_slab(max(1, len(members[k])), w)
        for row, i in enumerate(members[k]):
            _fill_row(arrs, row, packs[i])
        slabs.append(arrs)

    mapper = _cell_mapper(index, live)
    if reuse_edges_from is not None:
        ea, eb, ec = (reuse_edges_from.edges_a, reuse_edges_from.edges_b,
                      reuse_edges_from.edges_c)
        grid = reuse_edges_from.grid
    else:
        ea, eb, ec = _pack_edges(index, lane)
        grid = _maybe_grid(ea, eb, index.scene.edges.shape[0], index.scene,
                           edge_grid)
        ea, eb, ec = jnp.asarray(ea), jnp.asarray(eb), jnp.asarray(ec)
    if layout.quantized:
        quant = [_quantize_slab(a, layout) for a in slabs]
        residual = ResidualTable(
            [a[2] for a in slabs], region_bucket, region_row, mapper,
            widths, index.nx, index.ny, float(index.cell_size))
        return BucketedIndex(
            hub_ids=tuple(jnp.asarray(q[0]) for q in quant),
            via_xy=(),
            via_d=tuple(jnp.asarray(q[1]) for q in quant),
            via_ids=tuple(jnp.asarray(q[2]) for q in quant),
            mapper=jnp.asarray(mapper),
            region_bucket=jnp.asarray(region_bucket),
            region_row=jnp.asarray(region_row),
            edges_a=ea, edges_b=eb, edges_c=ec, grid=grid,
            nx=index.nx, ny=index.ny, cell_size=float(index.cell_size),
            width=float(index.scene.width), height=float(index.scene.height),
            widths=tuple(widths),
            vert_xy=_vert_table(index),
            hub_base=tuple(jnp.asarray(q[3]) for q in quant),
            vid_base=tuple(jnp.asarray(q[4]) for q in quant),
            qerr=jnp.float32(max((q[5] for q in quant), default=0.0)),
            layout=layout, residual=residual)
    return BucketedIndex(
        hub_ids=tuple(jnp.asarray(a[0]) for a in slabs),
        via_xy=tuple(jnp.asarray(a[1]) for a in slabs),
        via_d=tuple(jnp.asarray(a[2]) for a in slabs),
        via_ids=tuple(jnp.asarray(a[3]) for a in slabs),
        mapper=jnp.asarray(mapper),
        region_bucket=jnp.asarray(region_bucket),
        region_row=jnp.asarray(region_row),
        edges_a=ea, edges_b=eb, edges_c=ec, grid=grid,
        nx=index.nx, ny=index.ny, cell_size=float(index.cell_size),
        width=float(index.scene.width), height=float(index.scene.height),
        widths=tuple(widths))


# ---------------------------------------------------------------------------
# batched query engine (pure jnp; kernels plug in via repro.kernels.ops)
# ---------------------------------------------------------------------------

def locate_regions(idx, pts: jnp.ndarray) -> jnp.ndarray:
    """[B] region rows/ids for query points (floor-div + mapper, O(1)).

    Works for both layouts: PackedIndex's mapper yields slab rows,
    BucketedIndex's yields region ids (resolve via region_bucket/row).
    """
    ix = jnp.clip((pts[:, 0] / idx.cell_size).astype(jnp.int32), 0, idx.nx - 1)
    iy = jnp.clip((pts[:, 1] / idx.cell_size).astype(jnp.int32), 0, idx.ny - 1)
    return idx.mapper[iy * idx.nx + ix]


def _segvis(p, q, edges, use_kernels: bool):
    """Visibility dispatch: grid-pruned when the artifact carries a grid.

    ``edges`` is the (edges_a, edges_b, edges_c, grid) tuple; the grid path
    is bitwise-identical to the dense path (DESIGN.md §10 superset
    argument), so this choice is invisible to every caller.
    """
    from repro.kernels import ops

    ea, eb, ec, grid = edges
    if grid is not None:
        return segvis_grid(p, q, ea, eb, ec, grid, use_kernels=use_kernels)
    fn = ops.segvis_kernel if use_kernels else ops.segvis_ref
    return fn(p, q, ea, eb, ec)


def _mask_labels(labels, pts, edges, use_kernels: bool):
    """Per-endpoint half of Eq. 1-3: fold via visibility into distances.

    (hub [B,L], xy [B,L,2], d [B,L], vid [B,L]) -> (hub, vd, vid) where
    ``vd`` is inf wherever the via vertex is invisible from the query
    point.  Runs on whichever device holds the endpoint's labels — the
    sharded router calls it per shard with that shard's clipped edge set,
    which covers every segment of queries in its owned regions, so results
    match the single-device full-edge fold exactly.
    """
    hub, xy, d, vid = labels
    B, L = hub.shape
    vis = _segvis(jnp.repeat(pts, L, axis=0), xy.reshape(-1, 2),
                  edges, use_kernels).reshape(B, L)
    vd = jnp.where(vis, jnp.linalg.norm(pts[:, None] - xy, axis=-1) + d,
                   jnp.float32(jnp.inf))
    return hub, vd, vid


def _join_masked(masked_s, masked_t, s, t, covis, use_kernels: bool,
                 want_argmin: bool, qerr2=None):
    """Join half of Eq. 1-3 over visibility-masked labels.

    The join emits the row-min form ``rowmin[b,i] = vd_s[b,i] + min_{hub
    match j} vd_t[b,j]`` and the argmin pair is recovered with two cheap
    O(L) reductions.  ``covis`` overrides with the direct Euclidean
    distance (the label set does not witness co-visible pairs).

    ``qerr2`` (quantized layouts only, with ``want_argmin``): the summed
    per-side quantization error bounds.  A sixth ``amb`` [B] bool output
    flags rows whose argmin margin is within the error bound — their
    winner could differ from the f32 engine's, so the host rescues them
    against the exact residual rows (DESIGN.md §11).  Rows with a unique
    candidate (inf second-best) or no candidate at all (all-inf row) are
    provably unambiguous and excluded.
    """
    from repro.kernels import ops

    hub_s, vd_s, vid_s = masked_s
    hub_t, vd_t, vid_t = masked_t
    rowmin_join = (ops.label_join_rowmin_kernel if use_kernels
                   else ops.label_join_rowmin_ref)

    rowmin = rowmin_join(hub_s, vd_s, hub_t, vd_t)      # [B, L]
    d_label = rowmin.min(axis=-1)
    d_direct = jnp.linalg.norm(s - t, axis=-1)
    d = jnp.where(covis, d_direct, d_label)
    if not want_argmin:
        return d

    # winning (i, j): i minimizes the row join; with i's hub fixed, j is the
    # min-vd_t label sharing that hub (ties resolve to the first index, same
    # as the historical flat [L,L] argmin).
    inf = jnp.float32(jnp.inf)
    i = jnp.argmin(rowmin, axis=-1)                     # [B]
    hub_i = jnp.take_along_axis(hub_s, i[:, None], 1)   # [B, 1]
    vd_t_match = jnp.where(hub_t == hub_i, vd_t, inf)
    j = jnp.argmin(vd_t_match, axis=-1)                 # [B]
    via_s = jnp.take_along_axis(vid_s, i[:, None], 1)[:, 0]
    via_t = jnp.take_along_axis(vid_t, j[:, None], 1)[:, 0]
    hub = hub_i[:, 0]
    if qerr2 is None:
        return d, covis, via_s, hub, via_t

    # exact-argmin ambiguity: two candidates can swap order in exact f32
    # space only if their quantized margin is within twice the worst-case
    # per-candidate perturbation (qerr2 plus a few ulps of f32 rounding)
    L = rowmin.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    second_i = jnp.min(jnp.where(iota == i[:, None], inf, rowmin), -1)
    best_j = jnp.take_along_axis(vd_t_match, j[:, None], 1)[:, 0]
    second_j = jnp.min(jnp.where(iota == j[:, None], inf, vd_t_match), -1)
    thr = (jnp.float32(2.0) * qerr2
           + jnp.float32(64.0) * jnp.finfo(jnp.float32).eps
           * jnp.abs(d_label))
    amb = ((jnp.isfinite(second_i) & (second_i - d_label <= thr))
           | (jnp.isfinite(second_j) & (second_j - best_j <= thr)))
    return d, covis, via_s, hub, via_t, amb


def _labels_to_distances(labels_s, labels_t, s, t, edges,
                         use_kernels: bool, want_argmin: bool, qerr2=None):
    """Shared Eq. 1-3 core: per-endpoint labels -> distances (+ argmin ids).

    ``labels_*`` are (hub_ids [B,L], via_xy [B,L,2], via_d [B,L],
    via_ids [B,L]) gathered for each query endpoint; ``edges`` is the
    (edges_a, edges_b, edges_c, grid) tuple.  One code path serves
    ``query_batch``, ``query_batch_argmin``, the bucketed dispatch and
    (split across devices) the sharded router, for both the jnp reference
    ops and the Pallas kernels.
    """
    masked_s = _mask_labels(labels_s, s, edges, use_kernels)
    masked_t = _mask_labels(labels_t, t, edges, use_kernels)
    covis = _segvis(s, t, edges, use_kernels)           # [B]
    # materialize the masked triples: left to itself XLA fuses the O(W*E)
    # visibility fold into the O(W^2) join and re-evaluates per pair —
    # measurably slower for every layout, ruinously so for quantized
    # slabs whose fold also drags the decode gathers along (identity op,
    # so bitwise answers are untouched)
    masked_s, masked_t = jax.lax.optimization_barrier((masked_s, masked_t))
    return _join_masked(masked_s, masked_t, s, t, covis, use_kernels,
                        want_argmin, qerr2=qerr2)


def _decode_ids(enc: jnp.ndarray, base: jnp.ndarray, pad_val) -> jnp.ndarray:
    """u16 delta rows + per-row bases -> exact int32 ids (i32 passes through).

    The dtype check is a trace-time constant, so per-bucket i32 fallbacks
    compile to a plain passthrough — fallback handling costs nothing where
    it didn't happen.
    """
    if enc.dtype != jnp.uint16:
        return enc
    raw = base[:, None].astype(jnp.int32) + enc.astype(jnp.int32)
    return jnp.where(enc == jnp.uint16(U16_PAD), jnp.int32(pad_val), raw)


def _via_xy_of(vid: jnp.ndarray, vert_xy: jnp.ndarray) -> jnp.ndarray:
    """Reconstruct the per-slot via coordinates from the shared vertex table.

    Bitwise-equal to the f32 layout's ``via_xy`` plane: the packer writes
    ``graph.nodes[via]`` (cast f32) per slot and zeros for pads, which is
    exactly ``vert_xy[vid]`` masked at ``vid < 0``.
    """
    xy = vert_xy[jnp.clip(vid, 0, vert_xy.shape[0] - 1)]
    return jnp.where((vid >= 0)[..., None], xy, jnp.float32(0.0))


def _gather_packed(idx: PackedIndex, rows: jnp.ndarray):
    if not idx.layout.quantized:
        return (idx.hub_ids[rows], idx.via_xy[rows], idx.via_d[rows],
                idx.via_ids[rows])
    hub = _decode_ids(idx.hub_ids[rows], idx.hub_base[rows], HUB_PAD)
    vid = _decode_ids(idx.via_ids[rows], idx.vid_base[rows], -1)
    # materialize the decoded planes (see _gather_bucketed: XLA would
    # otherwise re-evaluate the decode gathers inside the visibility loop)
    return jax.lax.optimization_barrier(
        (hub, _via_xy_of(vid, idx.vert_xy),
         idx.via_d[rows].astype(jnp.float32), vid))


def _edges_of(idx) -> tuple:
    return (idx.edges_a, idx.edges_b, idx.edges_c, idx.grid)


def stack_endpoints(s, t) -> jnp.ndarray:
    """Both endpoint sides of a batch as one [2, B, 2] float32 device array.

    Host inputs are stacked on the host and cross in one transfer (one
    ``jax.device_put``); inputs already on the device are stacked there.
    :func:`query_stacked` folds and joins both sides from this one array.
    """
    if isinstance(s, jax.Array) or isinstance(t, jax.Array):
        return jnp.stack([jnp.asarray(s, jnp.float32),
                          jnp.asarray(t, jnp.float32)])
    return jax.device_put(np.stack([np.asarray(s, np.float32),
                                    np.asarray(t, np.float32)]))


@_jit_entry("fold_endpoint", static_argnames=("bucket", "use_kernels"))
def _fold_endpoint(idx, pts: jnp.ndarray, bucket=None,
                   use_kernels: bool = False):
    """locate + gather + visibility-fold both endpoint sides (own jit entry).

    ``pts`` is the [2, B, 2] stack of the s and t sides
    (:func:`stack_endpoints`); returns the (s, t) pair of masked triples.
    Every row folds on its own (locate, gather, visibility mask), so the
    2*B endpoints fold as one flat batch and the masked planes split back
    into halves — the same numbers as one fold per side, from one program.

    ``bucket=None`` gathers the single PackedIndex slab; an int gathers the
    bucketed layout at that dispatch bucket.  Splitting the fold from the
    O(W^2) join at a real jit boundary materializes the gathered planes:
    fused into one program, XLA folds the gather/decode chain into the
    visibility loop and re-evaluates it per edge — same flop count, ~2x
    wall on wide buckets for quantized layouts (``optimization_barrier``
    does not survive this backend's fusion pass).  The boundary changes no
    arithmetic: the sharded engine has always split here
    (``gather_masked_labels`` + ``join_masked``) and is bitwise-identical
    to the fused engine.
    """
    TRACES.bump("fold_endpoint")
    B = pts.shape[1]
    flat = pts.reshape(2 * B, 2)
    r = locate_regions(idx, flat)
    labels = (_gather_packed(idx, r) if bucket is None
              else _gather_bucketed(idx, r, bucket))
    masked = [m.reshape(2, B, -1)
              for m in _mask_labels(labels, flat, _edges_of(idx),
                                    use_kernels)]
    return tuple(m[0] for m in masked), tuple(m[1] for m in masked)


@_jit_entry("join_endpoints", static_argnames=("use_kernels", "want_argmin"))
def _join_endpoints(idx, masked_s, masked_t, pts: jnp.ndarray,
                    use_kernels: bool = False, want_argmin: bool = False):
    """Co-visibility + Eq. 1-3 join over folded endpoint sides (jit entry).

    ``pts`` is the same [2, B, 2] stack the fold read.  An f32 argmin join
    returns its five answers as one packed [5, B] int32 array
    (:func:`unpack_argmin`), so a batch crosses to the host in one copy.
    Quantized argmin joins return six arrays: the five answers and the
    rows flagged ambiguous against the summed per-side error bound
    (:func:`_join_masked`)."""
    TRACES.bump("join_endpoints")
    s, t = pts[0], pts[1]
    covis = _segvis(s, t, _edges_of(idx), use_kernels)
    qerr2 = (idx.qerr + idx.qerr
             if want_argmin and idx.layout.quantized else None)
    res = _join_masked(masked_s, masked_t, s, t, covis, use_kernels,
                       want_argmin, qerr2=qerr2)
    if not want_argmin or idx.layout.quantized:
        return res
    d, cv, via_s, hub, via_t = res
    return jnp.stack([jax.lax.bitcast_convert_type(d, jnp.int32),
                      cv.astype(jnp.int32), via_s, hub, via_t])


def unpack_argmin(packed, n: int | None = None) -> list:
    """Host (d, covis, via_s, hub, via_t) columns, rows ``[:n]``, of a
    packed [5, B] argmin join (:func:`_join_endpoints`): one device->host
    copy, then the :func:`empty_results` dtypes.  ``d`` is the float32
    view of its row, so every bit, inf included, is the join's own."""
    a = np.asarray(packed)[:, :n]
    return [a[0].view(np.float32), a[1].astype(bool), a[2], a[3], a[4]]


def query_stacked(idx, pts: jnp.ndarray, bucket: int | None = None,
                  use_kernels: bool = False, want_argmin: bool = False):
    """Eq. 1-3 over a stacked [2, B, 2] batch: two async jit dispatches.

    One ``fold_endpoint`` folds both endpoint sides and one
    ``join_endpoints`` joins them (see :func:`_fold_endpoint`); ``pts``
    comes from :func:`stack_endpoints`.  ``bucket``: None for the single
    PackedIndex slab, else the bucketed layout's dispatch bucket.  With
    ``want_argmin`` the result carries the winning (covis, via_s, hub,
    via_t) ids: for f32 layouts packed with ``d`` into one [5, B] int32
    array (:func:`unpack_argmin`); quantized layouts return the five
    arrays and a sixth ``amb`` — rows the caller must rescue against the
    residual (:func:`rescue_exact`).
    """
    ms, mt = _fold_endpoint(idx, pts, bucket=bucket, use_kernels=use_kernels)
    return _join_endpoints(idx, ms, mt, pts, use_kernels=use_kernels,
                           want_argmin=want_argmin)


def query_batch(idx: PackedIndex, s, t,
                use_kernels: bool = False) -> jnp.ndarray:
    """Batched Eq. 1-3: shortest distances for query pairs [B,2]x[B,2].

    use_kernels=True routes visibility + join through the Pallas kernels
    (``repro.kernels.ops``); False uses their jnp references — identical
    semantics, asserted by tests.  One transfer of both endpoint sides,
    then :func:`query_stacked`'s fold and join dispatches.
    """
    return query_stacked(idx, stack_endpoints(s, t), use_kernels=use_kernels)


def query_batch_argmin(idx: PackedIndex, s, t, use_kernels: bool = False):
    """Distances + winning (via_s, hub, via_t) label ids (path unwinding).

    Same single transfer and two dispatches as :func:`query_batch`; f32
    layouts return the five host columns (:func:`unpack_argmin`).
    Quantized layouts return the six device arrays — ``amb`` flags rows
    the caller must rescue against the residual (:func:`rescue_exact`)
    for exact argmin.
    """
    return _argmin_columns(idx, query_stacked(
        idx, stack_endpoints(s, t), use_kernels=use_kernels,
        want_argmin=True))


def _argmin_columns(idx, res) -> tuple:
    """An argmin join's answers as a tuple: f32 layouts' packed array
    unpacked on the host, quantized layouts' six arrays as they are."""
    return tuple(res if idx.layout.quantized else unpack_argmin(res))


# ---------------------------------------------------------------------------
# bucketed dispatch
# ---------------------------------------------------------------------------

def _gather_bucketed(bx: BucketedIndex, regions: jnp.ndarray, bucket: int,
                     width: int | None = None):
    """Gather per-query labels from buckets <= ``bucket``, padded to its width.

    One masked gather per source bucket (a handful of O(B*W) memory ops) in
    exchange for running the O(W^2) join and O(W*E) visibility at the
    dispatch width instead of the global Lmax.  Regions living in a *wider*
    bucket than ``bucket`` come back as pure padding (inf distances) — the
    caller must dispatch each query at the max of its endpoint buckets.

    ``width`` (>= ``widths[bucket]``) pads the gather beyond the bucket's
    own width.  The extra slots are HUB_PAD/inf — inert in the join — so a
    sharded query whose two endpoints live on shards with different bucket
    ladders can be joined at the pair's common width (``repro.sharding``).
    """
    W = bx.widths[bucket] if width is None else width
    B = regions.shape[0]
    hub = jnp.full((B, W), HUB_PAD, jnp.int32)
    xy = jnp.zeros((B, W, 2), jnp.float32)
    vd = jnp.full((B, W), jnp.inf, jnp.float32)
    vid = jnp.full((B, W), -1, jnp.int32)

    src_bucket = bx.region_bucket[regions]
    src_row = bx.region_row[regions]
    quantized = bx.layout.quantized
    for k in range(bucket + 1):
        rows = jnp.clip(src_row, 0, bx.hub_ids[k].shape[0] - 1)
        sel = src_bucket == k
        pad = ((0, 0), (0, W - bx.widths[k]))
        if quantized:
            # dequantize in the gather: decode ids against the per-row
            # bases, rebuild xy from the shared vertex table and widen the
            # distances — downstream masking/join code is dtype-blind and
            # identical to the f32 path.  The barrier materializes the
            # decoded planes once: without it XLA fuses the decode chain
            # into the O(W*E) visibility loop and re-evaluates the gathers
            # per edge (~2x wall on wide buckets, same flop count).
            hub_k = _decode_ids(bx.hub_ids[k][rows], bx.hub_base[k][rows],
                                HUB_PAD)
            vid_k = _decode_ids(bx.via_ids[k][rows], bx.vid_base[k][rows],
                                -1)
            xy_k = _via_xy_of(vid_k, bx.vert_xy)
            vd_k = bx.via_d[k][rows].astype(jnp.float32)
        else:
            hub_k, xy_k, vd_k, vid_k = (bx.hub_ids[k][rows],
                                        bx.via_xy[k][rows],
                                        bx.via_d[k][rows],
                                        bx.via_ids[k][rows])
        hub = jnp.where(sel[:, None],
                        jnp.pad(hub_k, pad, constant_values=HUB_PAD), hub)
        xy = jnp.where(sel[:, None, None],
                       jnp.pad(xy_k, pad + ((0, 0),)), xy)
        vd = jnp.where(sel[:, None],
                       jnp.pad(vd_k, pad, constant_values=np.inf), vd)
        vid = jnp.where(sel[:, None],
                        jnp.pad(vid_k, pad, constant_values=-1), vid)
    # materialize the merged planes: the select/pad merge chain (and, for
    # quantized layouts, the decode gathers feeding it) must not fuse into
    # the O(W*E) visibility fold downstream, which re-evaluates its input
    # expression per edge (identity op — bitwise answers untouched)
    return jax.lax.optimization_barrier((hub, xy, vd, vid))


def query_batch_at_bucket(bx: BucketedIndex, s, t, bucket: int,
                          use_kernels: bool = False,
                          want_argmin: bool = False):
    """Eq. 1-3 over one dispatch bucket (per-bucket fold + join jit entries).

    Every query's endpoint regions must live in buckets <= ``bucket``
    (i.e. ``bucket == max(endpoint buckets)`` after routing); the result is
    then bitwise-identical to the full-width ``query_batch`` because the
    extra slots it would have carried are all inf/HUB_PAD padding.  One
    transfer of both endpoint sides, one fold and one join dispatch
    (:func:`query_stacked`); argmin answers come back as
    :func:`query_batch_argmin` returns them.
    """
    res = query_stacked(bx, stack_endpoints(s, t), bucket=bucket,
                        use_kernels=use_kernels, want_argmin=want_argmin)
    return _argmin_columns(bx, res) if want_argmin else res


# ---------------------------------------------------------------------------
# sharded dispatch primitives (repro.sharding)
# ---------------------------------------------------------------------------

@_jit_entry("gather_labels_at_width", static_argnames=("width",))  # repolint: disable=jit-registry -- library-only full-gather API; no engine calls it, so warmup cannot reach it
def gather_labels_at_width(bx: BucketedIndex, regions: jnp.ndarray,
                           width: int):
    """Gather [B] regions' labels as dense [B, width] tensors.

    ``width`` must be >= the widest bucket any of ``regions`` lives in —
    the host router guarantees that by dispatching at ``max(endpoint
    widths)``.
    """
    TRACES.bump("gather_labels_at_width")
    bucket = max((k for k, w in enumerate(bx.widths) if w <= width),
                 default=0)
    return _gather_bucketed(bx, regions, bucket, width)


@_jit_entry("join_gathered", static_argnames=("use_kernels", "want_argmin"))  # repolint: disable=jit-registry -- library-only full-gather API; no engine calls it, so warmup cannot reach it
def join_gathered(labels_s, labels_t, s: jnp.ndarray, t: jnp.ndarray,
                  edges_a: jnp.ndarray, edges_b: jnp.ndarray,
                  edges_c: jnp.ndarray | None = None,
                  grid: EdgeGrid | None = None,
                  use_kernels: bool = False, want_argmin: bool = False,
                  qerr2=None):
    """Eq. 1-3 over pre-gathered label tensors (both sides [B, W]).

    Single-device convenience form (one edge set answers both sides).  The
    sharded router uses the split-phase entries below instead, so each
    side's visibility runs on the device whose clipped edge set covers it.
    ``qerr2``: see :func:`_join_masked` (quantized argmin ambiguity).
    """
    TRACES.bump("join_gathered")
    s = s.astype(jnp.float32)
    t = t.astype(jnp.float32)
    edges = (edges_a, edges_b, edges_b if edges_c is None else edges_c, grid)
    return _labels_to_distances(labels_s, labels_t, s, t, edges,
                                use_kernels, want_argmin, qerr2=qerr2)


@_jit_entry("gather_masked_labels", static_argnames=("width", "use_kernels"))
def gather_masked_labels(bx: BucketedIndex, regions: jnp.ndarray,
                         pts: jnp.ndarray, width: int,
                         use_kernels: bool = False):
    """Gather + visibility-fold one endpoint side on its owning shard.

    The device half of sharded routing (DESIGN.md §9/§10): the owning
    shard's edge subset is clipped to its owned regions dilated by their
    label reach, which covers every (query point -> via) segment of
    queries located in those regions — so the returned (hub, vd, vid)
    triple is byte-identical to the full-edge single-device fold.  For a
    cross-shard query the t-side triple then ships to the s-side device
    ([B, W] tensors, not slabs) for :func:`join_masked`.
    """
    TRACES.bump("gather_masked_labels")
    bucket = max((k for k, w in enumerate(bx.widths) if w <= width),
                 default=0)
    labels = _gather_bucketed(bx, regions, bucket, width)
    return _mask_labels(labels, pts.astype(jnp.float32), _edges_of(bx),
                        use_kernels)


@_jit_entry("covis_blocked", static_argnames=("use_kernels",))
def covis_blocked(s: jnp.ndarray, t: jnp.ndarray, edges_a, edges_b, edges_c,
                  grid: EdgeGrid | None = None,
                  use_kernels: bool = False) -> jnp.ndarray:
    """[B] int32 — 1 where a *local* edge blocks the direct s->t segment.

    The distributed co-visibility test: each shard whose owned bounding box
    the batch touches answers against its own clipped edges, and the router
    ORs the verdicts — the union of participating clips covers every edge
    the segment can cross, so the OR equals the single-device covis bit.
    """
    TRACES.bump("covis_blocked")
    s = s.astype(jnp.float32)
    t = t.astype(jnp.float32)
    vis = _segvis(s, t, (edges_a, edges_b, edges_c, grid), use_kernels)
    return (~vis).astype(jnp.int32)


@_jit_entry("join_masked", static_argnames=("use_kernels", "want_argmin"))
def join_masked(masked_s, masked_t, s: jnp.ndarray, t: jnp.ndarray,
                covis: jnp.ndarray, use_kernels: bool = False,
                want_argmin: bool = False, qerr2=None):
    """Eq. 1-3 join over visibility-masked label triples (both sides [B, W]).

    Runs on the s-side device; ``covis`` is the merged co-visibility bit
    from :func:`covis_blocked`.  With identical masked inputs this is
    bitwise-identical to the single-device ``query_batch_at_bucket`` tail —
    it is the same code.  ``qerr2``: see :func:`_join_masked` (quantized
    argmin ambiguity; pass the *sum* of the two shards' error bounds).
    """
    TRACES.bump("join_masked")
    s = s.astype(jnp.float32)
    t = t.astype(jnp.float32)
    return _join_masked(masked_s, masked_t, s, t, covis.astype(bool),
                        use_kernels, want_argmin, qerr2=qerr2)


# ---------------------------------------------------------------------------
# quantized layouts: exact-argmin rescue + cross-shard quantized wire
# ---------------------------------------------------------------------------

@_jit_entry("gather_masked_exact", static_argnames=("width", "use_kernels"))
def gather_masked_exact(idx, pts: jnp.ndarray, d_exact: jnp.ndarray,
                        width: int, use_kernels: bool = False):
    """Rescue gather: quantized slabs with the exact f32 distance rows.

    ``d_exact`` is the [B, width] residual gather
    (:meth:`ResidualTable.gather_d`) for these points.  Ids and via
    coordinates decode exactly from the device slabs, so substituting the
    exact distances makes the returned masked triple *bitwise-identical*
    to the f32 engine's visibility fold — the rescue join then reproduces
    the f32 argmin exactly.
    """
    TRACES.bump("gather_masked_exact")
    pts = pts.astype(jnp.float32)
    regions = locate_regions(idx, pts)
    if isinstance(idx, PackedIndex):
        hub, xy, _, vid = _gather_packed(idx, regions)
    else:
        bucket = max((k for k, w in enumerate(idx.widths) if w <= width),
                     default=0)
        hub, xy, _, vid = _gather_bucketed(idx, regions, bucket, width)
    return _mask_labels((hub, xy, d_exact.astype(jnp.float32), vid), pts,
                        _edges_of(idx), use_kernels)


def rescue_exact(idx, s, t, width: int, covis, use_kernels: bool = False):
    """Re-answer a batch with exact distances (host residual -> device).

    Full-batch recomputation (shapes match the quantized run, so traces
    are reused); the caller splices only the ambiguous rows.  ``covis`` is
    the quantized run's co-visibility bit — pure geometry, identical in
    both layouts.  Returns the exact 5-tuple.
    """
    res = idx.residual
    if res is None:
        raise ValueError("rescue_exact needs a quantized index with its "
                         "ResidualTable attached")
    s = np.asarray(s, np.float32)
    t = np.asarray(t, np.float32)
    ds = res.gather_d(res.locate(s), width)
    dt = res.gather_d(res.locate(t), width)
    ms = gather_masked_exact(idx, jnp.asarray(s), jnp.asarray(ds), width,
                             use_kernels=use_kernels)
    mt = gather_masked_exact(idx, jnp.asarray(t), jnp.asarray(dt), width,
                             use_kernels=use_kernels)
    return join_masked(ms, mt, jnp.asarray(s), jnp.asarray(t), covis,
                       use_kernels=use_kernels, want_argmin=True)


def splice_rescue(quant6, exact5) -> tuple:
    """Host splice: overwrite ambiguous rows of the quantized answers with
    the exact rescue rows.  Returns the engine's plain 5-tuple (numpy)."""
    d, cv, vs, hb, vt, amb = quant6
    outs = [np.asarray(a).copy() for a in (d, cv, vs, hb, vt)]
    m = np.asarray(amb)
    for o, e in zip(outs, exact5):
        o[m] = np.asarray(e)[m]
    return tuple(outs)


def wire_dtypes(bx: BucketedIndex) -> tuple:
    """(id_dtype, dist_dtype) of the cross-shard quantized wire.

    Unified per artifact: if *any* bucket fell back to raw i32 ids (range
    overflow) the whole wire ships i32; likewise any f32 distance fallback
    widens the distance plane.  Keeps the wire a single dtype so one trace
    serves every bucket mix.
    """
    id_dt = np.dtype(np.uint16)
    for arr in (*bx.hub_ids, *bx.via_ids):
        if np.dtype(arr.dtype) != np.uint16:
            id_dt = np.dtype(np.int32)
    dist_dt = np.dtype(bx.layout.dist_dtype)
    for arr in bx.via_d:
        if np.dtype(arr.dtype) != dist_dt:
            dist_dt = np.dtype(np.float32)
    return id_dt, dist_dt


def _gather_quant_plane(slabs, bases, src_bucket, src_row, widths,
                        bucket: int, W: int, wire_i32: bool, pad_raw,
                        B: int):
    """One id plane of the quantized wire gather (hub or via)."""
    if wire_i32:
        enc = jnp.full((B, W), jnp.int32(pad_raw), jnp.int32)
    else:
        enc = jnp.full((B, W), U16_PAD, jnp.uint16)
    base = jnp.zeros((B,), jnp.int32)
    for k in range(bucket + 1):
        rows = jnp.clip(src_row, 0, slabs[k].shape[0] - 1)
        sel = src_bucket == k
        pad = ((0, 0), (0, W - widths[k]))
        if wire_i32:
            plane = _decode_ids(slabs[k][rows], bases[k][rows], pad_raw)
            enc = jnp.where(sel[:, None],
                            jnp.pad(plane, pad, constant_values=pad_raw),
                            enc)
        else:
            enc = jnp.where(sel[:, None],
                            jnp.pad(slabs[k][rows], pad,
                                    constant_values=int(U16_PAD)), enc)
            base = jnp.where(sel, bases[k][rows], base)
    return enc, base


@_jit_entry("gather_quant_rows", static_argnames=("width", "use_kernels"))
def gather_quant_rows(bx: BucketedIndex, regions: jnp.ndarray,
                      pts: jnp.ndarray, width: int,
                      use_kernels: bool = False):
    """Owner-side half of the quantized cross-shard gather.

    Ships the *encoded* label rows — (hub_enc, hub_base, dq, via_enc,
    via_base, vis) — instead of the decoded f32 masked triple, cutting the
    wire from 12 to ~7 bytes per slot.  The visibility fold's verdict is
    computed here (the owner holds the clipped edge set) but the decode +
    distance sum happen on the joining device
    (:func:`dequant_masked_labels`), which reproduces the owner-side fold
    bit for bit (same expression, same input bits).
    """
    TRACES.bump("gather_quant_rows")
    pts = pts.astype(jnp.float32)
    bucket = max((k for k, w in enumerate(bx.widths) if w <= width),
                 default=0)
    id_dt, dist_dt = wire_dtypes(bx)
    wire_i32 = id_dt == np.int32
    src_bucket = bx.region_bucket[regions]
    src_row = bx.region_row[regions]
    B = regions.shape[0]
    henc, hbase = _gather_quant_plane(
        bx.hub_ids, bx.hub_base, src_bucket, src_row, bx.widths, bucket,
        width, wire_i32, HUB_PAD, B)
    venc, vbase = _gather_quant_plane(
        bx.via_ids, bx.vid_base, src_bucket, src_row, bx.widths, bucket,
        width, wire_i32, -1, B)
    dq = jnp.full((B, width), jnp.asarray(np.inf, dist_dt), dist_dt)
    for k in range(bucket + 1):
        rows = jnp.clip(src_row, 0, bx.via_d[k].shape[0] - 1)
        sel = src_bucket == k
        pad = ((0, 0), (0, width - bx.widths[k]))
        dq = jnp.where(sel[:, None],
                       jnp.pad(bx.via_d[k][rows].astype(dist_dt), pad,
                               constant_values=np.inf), dq)
    vid = _decode_ids(venc, vbase, -1)
    xy = _via_xy_of(vid, bx.vert_xy)
    vis = _segvis(jnp.repeat(pts, width, axis=0), xy.reshape(-1, 2),
                  _edges_of(bx), use_kernels).reshape(B, width)
    return henc, hbase, dq, venc, vbase, vis


@_jit_entry("dequant_masked_labels")
def dequant_masked_labels(henc, hbase, dq, venc, vbase, vis,
                          pts: jnp.ndarray, vert_xy: jnp.ndarray):
    """Joining-device half: decode shipped quantized rows into the masked
    triple — the same ``where(vis, norm + d, inf)`` expression as the
    owner-side fold, so the result is bitwise-identical to having shipped
    the decoded rows."""
    TRACES.bump("dequant_masked_labels")
    pts = pts.astype(jnp.float32)
    hub = _decode_ids(henc, hbase, HUB_PAD)
    vid = _decode_ids(venc, vbase, -1)
    xy = _via_xy_of(vid, vert_xy)
    vd = jnp.where(vis, jnp.linalg.norm(pts[:, None] - xy, axis=-1)
                   + dq.astype(jnp.float32), jnp.float32(jnp.inf))
    # materialize before the O(L^2) join fusion (see _gather_bucketed)
    return jax.lax.optimization_barrier((hub, vd, vid))


def _region_clip_boxes(index: EHLIndex, live: list, packs: list,
                       cell_region: np.ndarray) -> np.ndarray:
    """[R, 4] per-region visibility-reach boxes (xmin, ymin, xmax, ymax).

    The box spans the region's own cells *and* every via vertex its labels
    reach: any (query point -> via) segment of a query located in the
    region stays inside the box (a segment lies in the bounding box of its
    endpoints), and so does the region-local part of any s->t segment.
    Dilated by a small slack so float32 sign tests on nearly-touching
    edges can never disagree with the clip.
    """
    R = len(live)
    cs = float(index.cell_size)
    iy, ix = np.divmod(np.arange(index.mapper.size), index.nx)
    boxes = np.full((R, 4), np.inf)
    boxes[:, 2:] = -np.inf
    np.minimum.at(boxes[:, 0], cell_region, ix * cs)
    np.minimum.at(boxes[:, 1], cell_region, iy * cs)
    np.maximum.at(boxes[:, 2], cell_region, (ix + 1) * cs)
    np.maximum.at(boxes[:, 3], cell_region, (iy + 1) * cs)
    for r, p in enumerate(packs):
        xy = p["via_xy"]
        if len(xy):
            boxes[r, 0] = min(boxes[r, 0], xy[:, 0].min())
            boxes[r, 1] = min(boxes[r, 1], xy[:, 1].min())
            boxes[r, 2] = max(boxes[r, 2], xy[:, 0].max())
            boxes[r, 3] = max(boxes[r, 3], xy[:, 1].max())
    slack = 1e-3 * max(index.scene.width, index.scene.height)
    boxes[:, :2] -= slack
    boxes[:, 2:] += slack
    return boxes


def _shard_edge_mask(index: EHLIndex, clip_boxes: np.ndarray,
                     members: np.ndarray) -> np.ndarray:
    """[E] bool — edges whose bbox meets any owned region's clip box."""
    edges = index.scene.edges
    if edges.shape[0] == 0:
        return np.zeros(0, dtype=bool)
    ex0 = np.minimum(edges[:, 0, 0], edges[:, 1, 0])
    ex1 = np.maximum(edges[:, 0, 0], edges[:, 1, 0])
    ey0 = np.minimum(edges[:, 0, 1], edges[:, 1, 1])
    ey1 = np.maximum(edges[:, 0, 1], edges[:, 1, 1])
    bx = clip_boxes[members]                            # [Rk, 4]
    hit = ((ex0[None] <= bx[:, 2:3]) & (ex1[None] >= bx[:, 0:1]) &
           (ey0[None] <= bx[:, 3:4]) & (ey1[None] >= bx[:, 1:2]))
    return hit.any(axis=0)


def pack_bucketed_split(index: EHLIndex, region_shard: np.ndarray,
                        num_shards: int | None = None, lane: int = 128,
                        reuse_edges_from=None, reuse_edge_masks=None,
                        edge_grid: bool | None = None,
                        layout: SlabLayout = LAYOUT_F32):
    """Freeze a host index into per-shard width-bucketed slabs.

    The shard-aware sibling of :func:`pack_bucketed`: ``region_shard`` maps
    each live region (in live-rid order, as ``packed_label_counts``) to a
    shard; each shard gets its own :class:`BucketedIndex` holding only its
    regions' slabs, with the bucket ladder recomputed from its own label
    counts (a region's bucket *width* is invariant — smallest power-of-two
    multiple of ``lane`` — so sharded join widths match the unsharded
    dispatch widths exactly).

    **Edges are no longer replicated**: each shard carries only the edges
    whose bounding box meets one of its owned regions' clip boxes (region
    cells + every via vertex its labels reach, slack-dilated) — sufficient
    for both the label-visibility fold of queries it owns and its share of
    the distributed co-visibility test (DESIGN.md §9/§10).  Each subset
    gets its own edge grid per the ``edge_grid`` policy.

    Every shard's mapper covers the full grid; cells owned by other shards
    resolve to local row 0 — harmless, because the host-side routing table
    returned alongside is what decides which shard a query is sent to.

    ``reuse_edges_from`` (+ ``reuse_edge_masks``): previous-generation
    per-shard artifacts and their edge masks — a shard's device-resident
    edge tensors/grid are aliased iff its clip mask is unchanged (the
    recompression may have changed label reach, so masks are compared, not
    assumed).

    Returns ``(shards, route)``: the per-shard ``BucketedIndex`` list plus
    the host-side routing table — cell arrays (``cell_shard``,
    ``cell_local``, ``cell_bucket``, ``cell_row``, ``cell_width``) and the
    per-shard ``edge_mask`` list and owned bounding ``shard_rects`` the
    router's distributed covis test uses.
    """
    live, packs = _host_packs(index)
    R = len(live)
    region_shard = np.asarray(region_shard, dtype=np.int32)
    if region_shard.shape != (R,):
        raise ValueError(f"region_shard has shape {region_shard.shape}, "
                         f"index has {R} live regions")
    S = int(num_shards) if num_shards is not None \
        else int(region_shard.max(initial=-1)) + 1
    counts = index.packed_label_counts()
    if reuse_edges_from is None or hasattr(reuse_edges_from, "edges_a"):
        reuse_edges_from = [reuse_edges_from] * S
    if reuse_edge_masks is None:
        reuse_edge_masks = [None] * S

    # global region -> (local id, local bucket, local row) within its shard
    region_local = np.zeros(R, dtype=np.int32)
    region_lbucket = np.zeros(R, dtype=np.int32)
    region_lrow = np.zeros(R, dtype=np.int32)
    region_width = np.array([bucket_width(max(1, int(c)), lane)
                             for c in counts], dtype=np.int32)
    cell_region = _cell_mapper(index, live)
    clip_boxes = _region_clip_boxes(index, live, packs, cell_region)

    shards, edge_masks, shard_rects = [], [], np.zeros((S, 4))
    for k in range(S):
        members = np.nonzero(region_shard == k)[0]
        if members.size == 0:
            raise ValueError(f"shard {k} owns no regions — plan fewer "
                             "shards or rebalance")
        region_local[members] = np.arange(members.size, dtype=np.int32)
        widths_k = sorted({int(region_width[i]) for i in members})
        bucket_of_width = {w: b for b, w in enumerate(widths_k)}
        lbucket = np.array([bucket_of_width[int(region_width[i])]
                            for i in members], dtype=np.int32)
        lrow = np.zeros(members.size, dtype=np.int32)
        slab_members: list[list[int]] = [[] for _ in widths_k]
        for li, gi in enumerate(members):
            b = lbucket[li]
            lrow[li] = len(slab_members[b])
            slab_members[b].append(int(gi))
        region_lbucket[members] = lbucket
        region_lrow[members] = lrow

        slabs = []
        for b, w in enumerate(widths_k):
            arrs = _alloc_slab(max(1, len(slab_members[b])), w)
            for row, gi in enumerate(slab_members[b]):
                _fill_row(arrs, row, packs[gi])
            slabs.append(arrs)

        mask = _shard_edge_mask(index, clip_boxes, members)
        edge_masks.append(mask)
        # owned bounding rect: which batches this shard's covis test covers
        cells_k = np.nonzero(region_shard[cell_region] == k)[0]
        iy, ix = np.divmod(cells_k, index.nx)
        cs = float(index.cell_size)
        shard_rects[k] = (ix.min() * cs, iy.min() * cs,
                          (ix.max() + 1) * cs, (iy.max() + 1) * cs)

        reuse = reuse_edges_from[k]
        prev_mask = reuse_edge_masks[k]
        if reuse is not None and prev_mask is not None \
                and np.array_equal(prev_mask, mask):
            ea, eb, ec = reuse.edges_a, reuse.edges_b, reuse.edges_c
            grid = reuse.grid
        else:
            ea, eb, ec = _pack_edges(index, lane, mask=mask)
            grid = _maybe_grid(ea, eb, int(mask.sum()), index.scene,
                               edge_grid)
            ea, eb, ec = jnp.asarray(ea), jnp.asarray(eb), jnp.asarray(ec)

        # full-grid mapper: owned cells -> local id, foreign cells -> 0
        mapper_k = np.where(region_shard[cell_region] == k,
                            region_local[cell_region], 0).astype(np.int32)
        if layout.quantized:
            quant = [_quantize_slab(a, layout) for a in slabs]
            residual = ResidualTable(
                [a[2] for a in slabs], lbucket, lrow, mapper_k,
                widths_k, index.nx, index.ny, float(index.cell_size))
            shards.append(BucketedIndex(
                hub_ids=tuple(jnp.asarray(q[0]) for q in quant),
                via_xy=(),
                via_d=tuple(jnp.asarray(q[1]) for q in quant),
                via_ids=tuple(jnp.asarray(q[2]) for q in quant),
                mapper=jnp.asarray(mapper_k),
                region_bucket=jnp.asarray(lbucket),
                region_row=jnp.asarray(lrow),
                edges_a=ea, edges_b=eb, edges_c=ec, grid=grid,
                nx=index.nx, ny=index.ny, cell_size=float(index.cell_size),
                width=float(index.scene.width),
                height=float(index.scene.height),
                widths=tuple(widths_k),
                vert_xy=_vert_table(index),
                hub_base=tuple(jnp.asarray(q[3]) for q in quant),
                vid_base=tuple(jnp.asarray(q[4]) for q in quant),
                qerr=jnp.float32(max((q[5] for q in quant), default=0.0)),
                layout=layout, residual=residual))
        else:
            shards.append(BucketedIndex(
                hub_ids=tuple(jnp.asarray(a[0]) for a in slabs),
                via_xy=tuple(jnp.asarray(a[1]) for a in slabs),
                via_d=tuple(jnp.asarray(a[2]) for a in slabs),
                via_ids=tuple(jnp.asarray(a[3]) for a in slabs),
                mapper=jnp.asarray(mapper_k),
                region_bucket=jnp.asarray(lbucket),
                region_row=jnp.asarray(lrow),
                edges_a=ea, edges_b=eb, edges_c=ec, grid=grid,
                nx=index.nx, ny=index.ny, cell_size=float(index.cell_size),
                width=float(index.scene.width),
                height=float(index.scene.height),
                widths=tuple(widths_k)))

    route = dict(
        region_shard=region_shard,
        region_local=region_local,
        cell_region=cell_region,
        cell_shard=region_shard[cell_region],
        cell_local=region_local[cell_region],
        cell_bucket=region_lbucket[cell_region],
        cell_row=region_lrow[cell_region],
        cell_width=region_width[cell_region],
        edge_mask=edge_masks,
        shard_rects=shard_rects)
    return shards, route


def dispatch_buckets(bx: BucketedIndex, s, t) -> np.ndarray:
    """[B] dispatch bucket per query: max of the two endpoint buckets."""
    s = jnp.asarray(s, jnp.float32)
    t = jnp.asarray(t, jnp.float32)
    bs = bx.region_bucket[locate_regions(bx, s)]
    bt = bx.region_bucket[locate_regions(bx, t)]
    return np.asarray(jnp.maximum(bs, bt))


def query_batch_bucketed(bx: BucketedIndex, s, t,
                         use_kernels: bool = False,
                         want_argmin: bool = False):
    """Route a batch through per-bucket dispatch and scatter results back.

    Host-side convenience wrapper (PathServer does the same routing with
    fixed batch shapes and per-bucket stats): group queries by dispatch
    bucket, answer each group at its own width, reassemble in input order.
    """
    s = np.asarray(s, np.float32)
    t = np.asarray(t, np.float32)
    n = len(s)
    buckets = dispatch_buckets(bx, s, t) if n else np.zeros(0, np.int32)
    outs = empty_results(n, want_argmin)
    for k in np.unique(buckets):
        m = buckets == k
        res = query_batch_at_bucket(bx, s[m], t[m], bucket=int(k),
                                    use_kernels=use_kernels,
                                    want_argmin=want_argmin)
        if want_argmin and bx.layout.quantized:
            # 6-tuple: rescue ambiguous-margin rows against the residual
            if bool(np.asarray(res[5]).any()):
                exact = rescue_exact(bx, s[m], t[m], bx.widths[int(k)],
                                     res[1], use_kernels=use_kernels)
                res = splice_rescue(res, exact)
            else:
                res = res[:5]
        for o, r in zip(outs, res if want_argmin else (res,)):
            o[m] = np.asarray(r)
    return tuple(outs) if want_argmin else outs[0]


def empty_results(n: int, want_argmin: bool) -> list:
    """Output buffers matching the engine dtypes: d [+ covis, label ids]."""
    if not want_argmin:
        return [np.empty(n, np.float32)]
    return [np.empty(n, np.float32), np.empty(n, bool),
            np.empty(n, np.int32), np.empty(n, np.int32),
            np.empty(n, np.int32)]
