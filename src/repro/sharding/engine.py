"""Sharded serving behind the standard ``QueryEngine`` protocol.

:class:`ShardedQueryEngine` fronts a :class:`~repro.serving.shard_router.
ShardRouter` with the exact interface ``PathServer`` already speaks —
``buckets_of`` returns composite (shard_s, shard_t, width) routing keys
instead of bucket ids, and ``batch``/``batch_argmin`` decode them — so the
whole serving stack (fixed-shape batching, per-bucket stats, pinning,
``SwappableEngine`` hot-swap, the adaptive ``IndexManager``) runs unchanged
over a mesh-sharded index.

Atomic multi-shard swap falls out of the object model: the engine *is* the
full shard set, so ``SwappableEngine.swap(new ShardedQueryEngine)`` flips
every shard under one generation — a pinned request keeps the entire old
shard set alive until it drains; no mixed-generation batch is expressible.
"""

from __future__ import annotations

import time

import numpy as np

import jax

from repro import obs
from repro.core.grid import EHLIndex
from repro.core.packed import LAYOUT_F32, splice_rescue
from repro.serving.query_engine import QueryEngine
from repro.serving.shard_router import ShardRouter

from .planner import ShardedIndex, ShardPlanner


class ShardStats(obs.StatsView):
    """Per-shard serving + occupancy counters (surfaced via ``ServeStats``).

    Registry-backed view (DESIGN.md §12): traffic counters are labeled
    series keyed by engine instance + shard, so per-shard series appear
    in the Prometheus export and survive the view object itself."""

    _COUNTERS = {
        "batches": ("shard_batches_total", int),   # sub-batches joined here
        # query slots dispatched here (incl. padding)
        "slots": ("shard_slots_total", int),
        "seconds": ("shard_seconds_total", float),
        # label rows gathered here for another shard
        "gathers_out": ("shard_gathers_out_total", int),
        # covis verdicts computed here for another shard's join
        # (distributed s->t visibility over clipped edges, §10)
        "covis_assists": ("shard_covis_assists_total", int),
    }

    def __init__(self, shard: int, device: str, regions: int,
                 device_bytes: int, used_slots: int, total_slots: int,
                 registry=None, labels=None):
        self.shard = shard
        self.device = device
        self.regions = regions
        self.device_bytes = device_bytes
        self.used_slots = used_slots    # label slots holding real labels
        self.total_slots = total_slots  # label slots allocated (slab area)
        lbl = dict(labels or {})
        lbl.setdefault("shard", shard)
        self._bind(registry, lbl, row_prefix="sh")
        for name, v in (("shard_regions", regions),
                        ("shard_device_bytes", device_bytes),
                        ("shard_used_slots", used_slots),
                        ("shard_total_slots", total_slots)):
            self.registry.gauge(name, **self.labels).set(v)

    @property
    def occupancy(self) -> float:
        """Real labels / allocated slab slots (packing efficiency)."""
        return self.used_slots / max(1, self.total_slots)

    @property
    def us_per_slot(self) -> float:
        return 1e6 * self.seconds / max(1, self.slots)


def shard_imbalance(stats: list) -> float:
    """max/mean of per-shard device bytes across a ``ShardStats`` list."""
    b = np.array([s.device_bytes for s in stats], dtype=np.float64)
    return float(b.max() / max(1.0, b.mean()))


class ShardedQueryEngine(QueryEngine):
    """Region-sharded slabs over a device mesh, one ``QueryEngine``.

    ``index``: a planned :class:`ShardedIndex`, or a host ``EHLIndex`` that
    is planned + packed here (``num_shards`` required).  ``mesh``: a
    ``launch.mesh.make_serving_mesh`` mesh; ``None`` round-robins shards
    onto the available devices, stacking several per device only on the
    CPU backend (single-device test mode — identical code paths, the
    transfers just degenerate to same-device copies;
    ``launch.mesh.shard_devices``).
    """

    name = "sharded"
    static_shapes = True

    def __init__(self, index, num_shards: int | None = None, mesh=None,
                 use_kernels: bool = False, lane: int = 128,
                 tol: float = 1.15, reuse_edges_from=None,
                 layout=LAYOUT_F32):
        if isinstance(index, EHLIndex):
            if not num_shards or num_shards < 1:
                raise ValueError("building from a host index needs "
                                 "num_shards >= 1")
            planner = ShardPlanner(num_shards, lane=lane, tol=tol,
                                   layout=layout)
            index = planner.build(index, reuse_edges_from=reuse_edges_from)
        if not isinstance(index, ShardedIndex):
            raise TypeError(f"unsupported artifact: {type(index)!r}")
        self.index = index
        self.use_kernels = use_kernels
        self.router = ShardRouter(index, mesh=mesh, use_kernels=use_kernels)
        self._telemetry = None      # bound by PathServer / IndexManager
        eng_id = obs.next_instance_id("e")
        self._stats = [
            ShardStats(
                shard=k, device=str(dev), regions=bx.num_regions,
                device_bytes=bx.device_bytes(),
                used_slots=bx.label_slots()[0],
                total_slots=bx.label_slots()[1],
                labels={"eng": eng_id, "shard": k})
            for k, (bx, dev) in enumerate(zip(index.shards,
                                              self.router.devices))]

    def bind_telemetry(self, telemetry) -> None:
        """Attach an event sink (cross-shard covis-assist events); the
        metrics registry is process-wide, so per-shard series are already
        exported without binding."""
        self._telemetry = telemetry

    # ------------------------------------------------- QueryEngine protocol
    @property
    def num_buckets(self) -> int:
        """Size of the composite key space (routing keys index into it)."""
        s = self.index.num_shards
        return s * s * len(self.index.width_classes)

    def buckets_of(self, s, t) -> np.ndarray:
        return self.router.route_keys(s, t)

    def bucket_width(self, bucket: int) -> int:
        """Join width of a routing key — the W^2 a query at this key pays."""
        return self.router.key_width(bucket)

    def _note_dispatch(self, staged, n: int) -> None:
        """Traffic counters for one dispatched group (no blocking)."""
        st = self._stats[staged.i]
        st.batches += 1
        st.slots += n
        if staged.j != staged.i:
            self._stats[staged.j].gathers_out += n
        assists = [k for k in staged.parts if k != staged.i]
        for k in assists:
            self._stats[k].covis_assists += n
        if assists and self._telemetry is not None:
            self._telemetry.events.emit("covis_assist", home=staged.i,
                                        helpers=assists, n=n)

    def _finish_argmin(self, staged, res6) -> tuple:
        """Quantized argmin epilogue: rescue ambiguous-margin rows against
        the exact residual so winners match the f32 sharded engine bitwise.
        """
        # repolint: disable=hot-path-sync -- documented rescue trigger: one flag word, the exactness contract pays this sync
        if bool(np.asarray(res6[5]).any()):
            return splice_rescue(res6, self.router.rescue(staged))
        # repolint: disable=hot-path-sync -- argmin epilogue returns host arrays by contract
        return tuple(np.asarray(r) for r in res6[:5])

    def _run(self, s, t, key: int, want_argmin: bool):
        t0 = time.perf_counter()
        # repolint: disable=hot-path-sync -- _run backs the synchronous batch()/batch_argmin() API; the staged path bypasses it
        staged = self.router.stage(np.asarray(s, np.float32),
                                   np.asarray(t, np.float32), int(key))  # repolint: disable=hot-path-sync -- host-input normalization in the synchronous path
        res = self.router.join_staged(staged, want_argmin=want_argmin)
        jax.block_until_ready(res)  # repolint: disable=hot-path-sync -- terminal join of the synchronous path
        if want_argmin and self.router.quantized:
            res = self._finish_argmin(staged, res)
        self._stats[staged.i].seconds += time.perf_counter() - t0
        self._note_dispatch(staged, len(s))
        return res

    def batch(self, s, t, bucket: int = 0) -> np.ndarray:
        return self._run(s, t, bucket, want_argmin=False)

    def batch_argmin(self, s, t, bucket: int = 0):
        return self._run(s, t, bucket, want_argmin=True)

    # ------------------------------------------------ split-phase (async)
    def stage(self, s, t, bucket: int = 0):
        """Pre-join transfers for one routed group (cross-shard gathers,
        covis dispatch) — overlaps the in-flight group's join under the
        continuous batcher."""
        # repolint: disable=hot-path-sync -- normalizes host inputs before the H2D enqueue; nothing lives on device yet
        return self.router.stage(np.asarray(s, np.float32),
                                 np.asarray(t, np.float32), int(bucket))  # repolint: disable=hot-path-sync -- same host-input normalization as the line above

    def dispatch_staged(self, staged, bucket: int = 0,
                        want_argmin: bool = False) -> tuple:
        """Non-blocking join over a staged group; the batcher owns
        synchronization (per-shard seconds land via note_batch_seconds)."""
        res = self.router.join_staged(staged, want_argmin=want_argmin)
        if want_argmin and self.router.quantized:
            # The amb verdict must be inspected host-side before results can
            # be scattered, so quantized argmin groups synchronize here; the
            # distance-only path stays fully asynchronous.
            res = self._finish_argmin(staged, res)
        self._note_dispatch(staged, int(staged.s_dev.shape[0]))
        return tuple(res) if want_argmin else (res,)

    def note_batch_seconds(self, bucket: int, seconds: float) -> None:
        """Async-path latency attribution to the key's home shard."""
        i, _, _ = self.router.decode_key(int(bucket))
        self._stats[i].seconds += seconds

    def warmup(self, batch_size: int, want_argmin: bool = False) -> None:
        self.router.warmup(batch_size, want_argmin=want_argmin)

    def device_bytes(self) -> int:
        """Total across the mesh; ``per_shard_bytes`` has the HBM view."""
        return self.index.device_bytes()

    # --------------------------------------------------------- observability
    def per_shard_bytes(self) -> list:
        return self.index.per_shard_bytes()

    def shard_stats(self) -> list:
        return self._stats

    def reset_serve_counters(self) -> None:
        """Zero the traffic counters (occupancy/bytes stay — they describe
        the artifact).  The IndexManager calls this after probe validation
        so a freshly swapped-in engine reports only real serving traffic."""
        for st in self._stats:
            st.batches = 0
            st.slots = 0
            st.seconds = 0.0
            st.gathers_out = 0
            st.covis_assists = 0

    def imbalance(self) -> float:
        return shard_imbalance(self._stats)

    # ------------------------------------------------------------- serving
    def query(self, s, t, want_argmin: bool = False):
        """Route + dispatch + in-order merge for a whole batch (exact
        shapes, no padding) — validation/bench/test entry.  Same dispatch
        path as ``batch`` so per-shard stats record either way."""
        from repro.core.packed import empty_results

        s = np.asarray(s, np.float32)
        t = np.asarray(t, np.float32)
        n = len(s)
        outs = empty_results(n, want_argmin)
        keys = self.buckets_of(s, t) if n else np.zeros(0, np.int32)
        for key in np.unique(keys):
            m = keys == key
            res = self._run(s[m], t[m], int(key), want_argmin)
            for o, r in zip(outs, res if want_argmin else (res,)):
                o[m] = np.asarray(r)
        return tuple(outs) if want_argmin else outs[0]
