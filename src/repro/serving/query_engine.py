"""Pluggable query backends behind one ``QueryEngine`` interface.

The serving layer (``PathServer``) is backend-agnostic: it routes batches,
keeps stats and scatters results; *how* a batch is answered is an engine
(DESIGN.md §6).  Three interchangeable backends:

* :class:`HostEngine`   — the scalar float64 oracle (``repro.core.query``);
  slow, exact, the reference everything else is validated against.
* :class:`JnpEngine`    — batched XLA engine over a packed layout, pure-jnp
  ops (the serving default).
* :class:`PallasEngine` — same engine routed through the Pallas TPU kernels
  (compiled on the TPU; interpreted on the CPU, where the tests run them).

The device engines accept either packed layout: the single-slab
``PackedIndex`` (one bucket) or the width-bucketed ``BucketedIndex``
(per-bucket jit entries, ``buckets_of`` exposes the routing key).  All three
share the distance/join core in ``repro.core.packed`` — the argmin (path
unwinding) variant is the same code path with a flag, not a fork.
"""

from __future__ import annotations

import abc
import contextlib

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.grid import EHLIndex
from repro.core.packed import (BucketedIndex, LAYOUT_F32, PackedIndex,
                               gather_masked_exact, join_masked,
                               pack_bucketed, query_stacked,
                               rescue_exact, splice_rescue, stack_endpoints,
                               unpack_argmin)
from repro.core.query import query as host_query


class QueryEngine(abc.ABC):
    """Answer batches of ESPP queries; optionally bucket-routable.

    ``bucket`` arguments index the engine's dispatch buckets; engines with a
    single bucket (host oracle, single-slab) ignore them.  ``batch`` returns
    [B] float32 distances; ``batch_argmin`` additionally returns the winning
    (covis, via_s, hub, via_t) ids for host-side path unwinding.
    """

    name: str = "abstract"
    static_shapes = False   # True: batches must be padded to a fixed size
    generation = 0          # bumped by hot-swapping engines (repro.indexing)

    @contextlib.contextmanager
    def pin(self):
        """Pin a consistent engine for a multi-call request.

        ``PathServer`` routes one request through several engine calls
        (``buckets_of`` + one ``batch`` per bucket group); under a
        hot-swapping engine (``repro.indexing.SwappableEngine``) those calls
        must all hit the *same* artifact — bucket ids are meaningless across
        generations.  Static engines just yield themselves; swappable
        engines yield the pinned generation's engine and keep its device
        buffers alive until every pin drains.
        """
        yield self

    def buckets_of(self, s, t) -> np.ndarray:
        """[B] dispatch bucket per query (0 for single-bucket engines)."""
        return np.zeros(len(s), dtype=np.int32)

    @abc.abstractmethod
    def batch(self, s, t, bucket: int = 0) -> np.ndarray:
        ...

    def batch_argmin(self, s, t, bucket: int = 0):
        raise NotImplementedError(f"{self.name} has no argmin path")

    # -------------------------------------------- split-phase (async) path
    def stage(self, s, t, bucket: int = 0):
        """Begin host->device staging for one padded batch; returns an
        opaque handle for :meth:`dispatch_staged`.

        The continuous batcher (``serving.batcher``) stages batch N+1 while
        batch N computes, so transfers (and, under sharding, cross-shard
        label gathers) overlap device compute.  Default: pass-through."""
        return (s, t)

    def dispatch_staged(self, staged, bucket: int = 0,
                        want_argmin: bool = False) -> tuple:
        """Dispatch a staged batch WITHOUT synchronizing.

        Returns a tuple of result arrays (1 without argmin, 5 with, or
        the engine's own packed form) that may still be computing on
        device — the caller owns ``block_until_ready`` and reads them with
        :meth:`fetch`, which is what lets the batcher overlap the next
        group's staging with this group's compute."""
        s, t = staged
        if want_argmin:
            return tuple(self.batch_argmin(s, t, bucket=bucket))
        return (self.batch(s, t, bucket=bucket),)

    def fetch(self, res, n: int) -> list:
        """Host columns, rows ``[:n]``, of a :meth:`dispatch_staged`
        result, in the :func:`~repro.core.packed.empty_results` dtypes:
        one copy per array."""
        # repolint: disable=hot-path-sync -- the retire's copy after the batcher's join, and batch_argmin's synchronous return
        return [np.asarray(r)[:n] for r in res]

    def warmup(self, batch_size: int, want_argmin: bool = False) -> None:
        pass

    def device_bytes(self) -> int:
        return 0


class HostEngine(QueryEngine):
    """Scalar float64 oracle looped over the batch — exact, no device state."""

    name = "host"

    def __init__(self, index: EHLIndex):
        self.index = index

    def batch(self, s, t, bucket: int = 0) -> np.ndarray:
        return np.array([host_query(self.index, si, ti, want_path=False)[0]
                         for si, ti in zip(s, t)], dtype=np.float32)

    def paths(self, s, t) -> list:
        return [host_query(self.index, si, ti, want_path=True)[1]
                for si, ti in zip(s, t)]


class DeviceEngine(QueryEngine):
    """Batched XLA engine over a packed layout (jnp ops or Pallas kernels)."""

    use_kernels = False
    static_shapes = True    # jitted: pad batches so shapes never recompile

    def __init__(self, index, layout=LAYOUT_F32):
        if isinstance(index, EHLIndex):
            index = pack_bucketed(index, layout=layout)
        if not isinstance(index, (PackedIndex, BucketedIndex)):
            raise TypeError(f"unsupported index artifact: {type(index)!r}")
        self.index = index
        self.quantized = index.layout.quantized
        self.bucketed = isinstance(index, BucketedIndex)
        if self.bucketed:
            # host-side routing table mirrors (see buckets_of): admission-
            # path routing must not pay a per-call eager-jnp dispatch chain
            self._np_mapper = np.asarray(index.mapper)
            self._np_bucket = np.asarray(index.region_bucket)

    @property
    def num_buckets(self) -> int:
        return self.index.num_buckets if self.bucketed else 1

    def bucket_width(self, bucket: int) -> int:
        return (self.index.widths[bucket] if self.bucketed
                else self.index.label_width)

    def _route(self, pts) -> np.ndarray:
        """Host-numpy mirror of ``locate_regions`` -> bucket (same float32
        floor-divide, so cell ids agree with the device gathers bit-for-bit
        — the ShardRouter routes with the identical construction).  Runs on
        the submit path of the continuous batcher, where the eager per-op
        dispatch of ``dispatch_buckets`` would dominate admission cost."""
        p = np.asarray(pts, np.float32)
        cs = np.float32(self.index.cell_size)
        ix = np.clip((p[:, 0] / cs).astype(np.int32), 0, self.index.nx - 1)
        iy = np.clip((p[:, 1] / cs).astype(np.int32), 0, self.index.ny - 1)
        return self._np_bucket[self._np_mapper[iy * self.index.nx + ix]]

    def buckets_of(self, s, t) -> np.ndarray:
        if not self.bucketed:
            return np.zeros(len(s), dtype=np.int32)
        return np.maximum(self._route(s), self._route(t)).astype(np.int32)

    def _run(self, pts, bucket: int, want_argmin: bool):
        """Launch a staged batch: one fold and one join dispatch."""
        return query_stacked(self.index, pts,
                             bucket=bucket if self.bucketed else None,
                             use_kernels=self.use_kernels,
                             want_argmin=want_argmin)

    def _argmin(self, pts, bucket: int) -> tuple:
        """Launch an argmin batch: the packed [5, B] join of f32 layouts,
        unsynchronized, or the quantized layouts' five host columns after
        their rescue."""
        res = self._run(pts, bucket, want_argmin=True)
        if not self.quantized:
            return (res,)
        # quantized: 6-tuple — rescue ambiguous-margin rows against the
        # exact residual so argmin winners match the f32 engine bitwise
        # repolint: disable=hot-path-sync -- documented rescue trigger: one flag word, the exactness contract pays this sync
        if bool(np.asarray(res[5]).any()):
            with obs.Stopwatch() as sw:
                # repolint: disable=hot-path-sync -- the rescue re-reads the batch's endpoints on the host (already synced above)
                s, t = np.asarray(pts)
                exact = rescue_exact(self.index, s, t,
                                     self.bucket_width(bucket), res[1],
                                     use_kernels=self.use_kernels)
                out = splice_rescue(res, exact)
            # argmin-rescue attribution (DESIGN.md §12): the rescue is
            # fused into the dispatch stage from the span's point of view,
            # so its cost is surfaced through these engine-side series
            obs.REGISTRY.counter("rescue_batches_total",
                                 engine=self.name).inc()
            obs.REGISTRY.histogram("rescue_ms", engine=self.name).record(
                sw.seconds * 1e3)
            return out
        # repolint: disable=hot-path-sync -- the amb check above synced the batch; quantized argmin results are host columns
        return tuple(np.asarray(r) for r in res[:5])

    def batch(self, s, t, bucket: int = 0) -> np.ndarray:
        return self._run(self.stage(s, t), bucket, want_argmin=False)

    def batch_argmin(self, s, t, bucket: int = 0):
        return tuple(self.fetch(self._argmin(self.stage(s, t), bucket),
                                len(s)))

    def stage(self, s, t, bucket: int = 0):
        """Stack both endpoint sides into one [2, B, 2] float32 array and
        start its one host->device copy (jax transfers are async; on
        accelerators the DMA overlaps the in-flight batch)."""
        return stack_endpoints(s, t)

    def dispatch_staged(self, staged, bucket: int = 0,
                        want_argmin: bool = False) -> tuple:
        """Fold and join dispatches over the staged array, unsynchronized
        (quantized argmin batches sync for their rescue).  An f32 argmin
        batch is one packed array (:meth:`fetch` unpacks it)."""
        if want_argmin:
            return self._argmin(staged, bucket)
        return (self._run(staged, bucket, want_argmin=False),)

    def fetch(self, res, n: int) -> list:
        """As :meth:`QueryEngine.fetch`; a packed [5, B] argmin join (the
        one array of rank 2) is copied once and unpacked."""
        if len(res) == 1 and res[0].ndim == 2:
            return unpack_argmin(res[0], n)
        return super().fetch(res, n)

    def warmup(self, batch_size: int, want_argmin: bool = False) -> None:
        """Trace every per-bucket jit entry once with the serving shape.

        Batches are staged as the serve loop stages them, so warm-up traces
        exactly the programs the loop runs.  ``want_argmin=True``
        additionally traces the argmin (path extraction) entries — they are
        separate jit cache entries, so without this the first
        ``query_paths`` batch pays XLA compile inside the timed serving
        loop.
        """
        z = np.zeros((batch_size, 2), np.float32)
        pts = self.stage(z, z)
        for b in range(self.num_buckets):
            self._run(pts, b, want_argmin=False).block_until_ready()
            if want_argmin:
                jax.block_until_ready(self._run(pts, b, want_argmin=True))
                if self.quantized:
                    # the rescue path's entries (exact gather + plain
                    # argmin join) are their own jit cache entries
                    W = self.bucket_width(b)
                    zj = jnp.asarray(z)
                    d0 = jnp.full((batch_size, W), jnp.inf, jnp.float32)
                    ms = gather_masked_exact(self.index, zj, d0, W,
                                             use_kernels=self.use_kernels)
                    jax.block_until_ready(join_masked(
                        ms, ms, zj, zj, jnp.zeros(batch_size, bool),
                        use_kernels=self.use_kernels, want_argmin=True))

    def device_bytes(self) -> int:
        return self.index.device_bytes()


class JnpEngine(DeviceEngine):
    name = "jnp"
    use_kernels = False


class PallasEngine(DeviceEngine):
    name = "pallas"
    use_kernels = True


def make_engine(index, backend: str = "jnp",
                layout=LAYOUT_F32) -> QueryEngine:
    """Engine factory.  ``index``: EHLIndex (host backend, or auto-packed
    bucketed for device backends), PackedIndex, or BucketedIndex.
    ``layout`` picks the slab dtypes when auto-packing (DESIGN.md §11)."""
    if backend == "host":
        if not isinstance(index, EHLIndex):
            raise TypeError("host backend needs the host-side EHLIndex")
        return HostEngine(index)
    if backend == "jnp":
        return JnpEngine(index, layout=layout)
    if backend == "pallas":
        return PallasEngine(index, layout=layout)
    raise ValueError(f"unknown backend {backend!r} "
                     "(expected host | jnp | pallas)")
