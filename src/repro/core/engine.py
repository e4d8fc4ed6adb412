"""SSSPDelEngine — the paper's runtime loop (paper §4.1) as a host
orchestrator over jitted device epochs.

Faithful behaviour (defaults):
  * runs of consecutive ADD events are ingested as one batch and drained by
    monotone relaxation (the paper's runtime likewise drains its topology
    buffer before algorithmic messages, and insertion mode is order-free);
  * every DEL event triggers the stop-the-world sequence: converge, apply the
    single deletion, invalidation + recomputation, converge;
  * QUERY markers enforce an epoch and snapshot (dist, parent).

Beyond-paper switches:
  * ``sources=(s0, s1, ...)`` — batched multi-source serving (DESIGN.md §8):
    the engine maintains stacked ``[S, N]`` dist/parent state, one tree per
    source, over ONE shared graph layout; every epoch runs vmapped over the
    source axis and is bit-identical per lane to S independent engines
    (``source`` is ignored when ``sources`` is set).
  * ``batch_deletions=True`` — coalesce a run of consecutive DELs into one
    invalidation+recompute epoch (union of affected subtrees; DESIGN.md §3).
  * ``use_doubling`` — pointer-doubling invalidation (default True; set False
    for the paper's wave-by-wave flood).
  * ``relax_backend`` — any registered ``RelaxBackend`` (core/backends/,
    DESIGN.md §7): "segment" (scatter-min over the COO pool), "ellpack"
    (dense gather + row-min over an incrementally maintained ELLPACK block;
    the Pallas kernel's layout — DESIGN.md §2.7), or "sliced" (hub-aware
    hybrid: per-slice-width ELL + overflow COO lane for power-law hubs —
    DESIGN.md §6).  The engine itself is backend-agnostic: the ingest path
    calls the protocol's ``apply_adds`` / ``apply_dels`` / ``relax`` /
    ``delete`` hooks and never branches on the backend name.

Host-sync rules (DESIGN.md §2.4): the ingest loop never blocks on device
values.  Round/message stats accumulate in device scalars (DEL rounds also
apart, ``rounds_by_kind``) and are only read back inside ``query()``;
deletion epochs run unconditionally (an all-false seed is a cheap device
no-op) instead of the old ``bool(jnp.any(seed))`` round-trip per deletion.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backends as bk_mod
from repro.core import buckets
from repro.core import delete as del_mod
from repro.core import events as ev
from repro.core import frontier as frontier_mod
from repro.core import ingest, relax
from repro.core.backends import RELAX_BACKENDS
from repro.core.state import EdgePool, GraphState, SSSPState
from repro.core.stream import QueryResult, StreamEngineBase
from repro.kernels.relax import config as kernel_config
from repro.obs import WatchdogConfig

__all__ = ["EngineConfig", "QueryResult", "SSSPDelEngine", "RELAX_BACKENDS"]


@dataclasses.dataclass
class EngineConfig:
    num_vertices: int
    edge_capacity: int
    source: int
    use_doubling: bool = True
    batch_deletions: bool = False
    on_duplicate: str = "ignore"
    validate_every: int = 0     # if >0, run oracle check every k queries (tests)
    relax_backend: str = "segment"
    ell_block_rows: int = 256   # relax-kernel row tile (rebuilds pad to this)
    ell_init_k: int = 8         # initial ELL width; doubles on overflow
    # Pallas ELL row-min kernel (kernels/relax/relax.py) instead of the XLA
    # wave; off by default on every platform (DESIGN.md §2.7)
    ell_use_kernel: bool = False
    # "sliced" backend knobs (DESIGN.md §6)
    sliced_slice_rows: int = 256  # rows per degree slice (per-slice K)
    sliced_hub_k: int = 32        # hub threshold: rows past it spill to COO
    sliced_init_k: int = 2        # initial per-slice width; doubles at rebuild
    sliced_fused: bool = False    # fused Pallas wave kernel (DESIGN.md §9.4)
    # bucketed delta-stepping schedule (DESIGN.md §9): "rounds" settles every
    # epoch to fixpoint; "buckets" defers convergence work into a pending
    # set and drains it bucket-by-bucket at query/checkpoint time
    wave_schedule: str = "rounds"
    # delta; inf = one bucket (plain converge); "auto" picks a pow2-quantized
    # percentile of the live pool weights at drain time (DESIGN.md §9.5)
    bucket_width: float | str = 1.0
    # frontier-compacted sparse epochs (DESIGN.md §12): "sparse" routes every
    # push epoch through the compacted worklist path (the capacity ladder's
    # dense fallback bounds the regression when occupancy blows up); "auto"
    # routes per epoch from host-known occupancy bounds
    frontier_mode: str = "dense"
    frontier_cap: int = 0           # top ladder rung; 0 = derive (~N/64)
    frontier_kernel: bool = False   # Pallas gathered-rows wave kernel
    # batched multi-source serving (DESIGN.md §8); None = single-source
    sources: tuple[int, ...] | None = None
    # observability (DESIGN.md §10): named spans are always on; this turns
    # on the opt-in record — device-side counter registry, span list and
    # exports, histograms, flight recorder.  Off by default; its cost is
    # measured on the chip (DESIGN.md §10.4)
    observability: bool = False
    obs_flight_capacity: int = 128
    # stall/divergence watchdog (§10.8): a WatchdogConfig arms it (only
    # meaningful with observability=True); None = off
    obs_watchdog: "WatchdogConfig | None" = None
    # control-plane implementation (DESIGN.md §11): "columnar" (numpy
    # open-addressing index; the paper-scale default) or "dict" (the Python
    # reference).  Bit-identical outputs either way.
    alloc_impl: str = "columnar"

    def __post_init__(self):
        # fail at construction with the valid set, not deep in layout init
        bk_mod.validate_backend_config(self)
        ingest.allocator_cls(self.alloc_impl)  # raises on unknown impl
        if self.obs_flight_capacity < 1:
            raise ValueError(f"obs_flight_capacity must be >= 1; got "
                             f"{self.obs_flight_capacity}")
        if self.sources is not None:
            self.sources = tuple(int(s) for s in self.sources)
            bad = [s for s in self.sources
                   if not 0 <= s < self.num_vertices]
            if not self.sources or bad:
                raise ValueError(
                    f"sources must be non-empty vertex ids in "
                    f"[0, {self.num_vertices}); got {self.sources}")


class SSSPDelEngine(StreamEngineBase):
    """Host orchestrator; all heavy lifting is jitted device code.

    Stream dispatch, lazy device-scalar stats, and the stability metric are
    shared with the sharded engine via ``StreamEngineBase`` (core/stream.py);
    everything layout-specific lives behind ``self.backend``
    (core/backends/, DESIGN.md §7).
    """

    def __init__(self, cfg: EngineConfig):
        super().__init__(sources=cfg.sources,
                         observability=cfg.observability,
                         flight_capacity=cfg.obs_flight_capacity,
                         watchdog=cfg.obs_watchdog)
        self.cfg = cfg
        self.alloc = ingest.make_allocator(cfg.edge_capacity,
                                           cfg.on_duplicate, cfg.alloc_impl)
        self.state = GraphState.init(cfg.num_vertices, cfg.edge_capacity, cfg.source)
        if self.sources is not None:
            # stacked [S, N] trees over the single shared edge pool
            self.state = dataclasses.replace(
                self.state, sssp=SSSPState.init_batched(
                    cfg.num_vertices, self.sources))
        use_kernel = bool(cfg.ell_use_kernel)
        self._use_kernel = use_kernel
        self._interpret = kernel_config.default_interpret()
        # "auto" starts on the dense ELL layout and falls back to sliced when
        # a rebuild reports hub blowup (backends/base.py ELL_BLOWUP_RATIO)
        self._auto = cfg.relax_backend == bk_mod.AUTO_BACKEND
        self.backend_name = "ellpack" if self._auto else cfg.relax_backend
        self.backend = bk_mod.make_backend(
            self.backend_name, cfg, use_kernel=use_kernel,
            interpret=self._interpret)
        self.bucketed = cfg.wave_schedule == "buckets"
        self._pend = buckets.empty_pending(
            cfg.num_vertices,
            None if self.sources is None else len(self.sources))
        # frontier-compacted sparse path (DESIGN.md §12): OUT-adjacency
        # sidecar + capacity ladder; maintained whenever the mode can route
        # sparse so the routing decision stays a pure host policy choice
        self._sparse = cfg.frontier_mode != "dense"
        if self._sparse:
            self._out = frontier_mod.OutAdjacency(cfg.num_vertices)
            self._caps = frontier_mod.capacity_ladder(cfg.num_vertices,
                                                      cfg.frontier_cap)
        # host-side upper bound on pending-push occupancy (the "auto" drain
        # signal; reset per drain, pinned to N when a deletion's affected
        # set is unknown host-side)
        self._pend_bound = 0
        # bucket_width="auto" resolution cache: (resolved width, live-edge
        # estimate at resolution) — re-resolved when the pool doubles/halves
        self._bw_cache: tuple[float, int] | None = None

    # -------------------------------------------------- sparse/width policy
    def _route_sparse(self, occupancy_bound: int) -> bool:
        """Host-only routing: "sparse" always takes the compacted path (the
        device-side ladder bounds blowup); "auto" takes it only when the
        host-known occupancy upper bound fits the top rung — no device
        readback either way (DESIGN.md §2.4/§12.3)."""
        if not self._sparse:
            return False
        if self.cfg.frontier_mode == "sparse":
            return True
        return occupancy_bound <= self._caps[-1]

    def _fold_occupancy(self, occ) -> None:
        if self.obs.enabled:
            self.obs.counters.add(
                "frontier_occupancy",
                occ if getattr(occ, "ndim", 0) == 0 else jnp.sum(occ))

    def _bucket_width(self) -> float:
        """Resolve ``bucket_width="auto"`` host-side: the pow2-quantized
        median of the live pool weights (delta ~ typical edge weight groups
        each improvement chain into a handful of buckets — the §9 follow-up).
        Quantization plus a doubling/halving re-resolve policy bounds the
        distinct static widths the jitted drains see."""
        if self.cfg.bucket_width != "auto":
            return self.cfg.bucket_width
        live_est = max(1, self.n_adds - self.n_dels)
        if self._bw_cache is not None:
            width, at = self._bw_cache
            if at / 2 <= live_est <= at * 2:
                return width
        w = self.alloc.active_coo()[2]
        if len(w) == 0:
            width = 1.0
        else:
            med = max(float(np.percentile(w, 50.0)), 1e-6)
            width = float(2.0 ** np.round(np.log2(med)))
        self._bw_cache = (width, live_est)
        return width

    # ------------------------------------------------------------------ adds
    def _ingest_adds(self, batch: ev.EventBatch) -> None:
        with self.obs.tracer.span("plan_adds"):
            plan = self.alloc.plan_adds(batch.src, batch.dst, batch.w)
        if len(plan.slots) == 0:
            return
        with self.obs.epoch("add_epoch", events=len(plan.slots)):
            slots_p, src_p, dst_p, w_p = ingest.pad_pow2(
                plan.slots, plan.src, plan.dst, plan.w)
            edges = ingest.apply_adds(self.state.edges, jnp.asarray(slots_p),
                                      jnp.asarray(src_p), jnp.asarray(dst_p),
                                      jnp.asarray(w_p))
            # Frontier = tails of the inserted edges (paper Listing 3: tail
            # offers its distance to the head).  Relaxing from the tails
            # delivers exactly those offers (plus no-op re-offers along
            # other out-edges).
            frontier = relax.frontier_from_vertices(
                jnp.asarray(plan.src), self.cfg.num_vertices)
            self.backend.apply_adds(plan, self.alloc)
            if self._sparse:
                # OUT-adjacency sidecar rides along with every layout patch
                # so the per-epoch routing stays a free policy choice
                self._out.apply_adds(plan, self.alloc)
            if self._auto and getattr(self.backend, "blowup", False):
                self._fallback_to_sliced()
            self.obs.note_layout(self.backend.layout_counters())
            if self.obs.enabled:
                # frontier = distinct inserted tails — the host plan already
                # knows the figure the device mask encodes, so counting here
                # costs no device dispatch in the hot ingest path (§10.4);
                # the device-counter path carries the drain-side figures
                # (drain_waves, pending occupancy) the epochs computed anyway
                nf = len(np.unique(plan.src))
                self.obs.counters.inc("frontier", nf)
                # one occupancy-histogram sample per ADD epoch (§10.6):
                # sum(hist_frontier_occupancy) == add_epochs
                self.obs.hist_host("hist_frontier_occupancy", nf)
                if self.obs.watchdog is not None:
                    self.obs.watchdog.observe(
                        "add_epoch", 0.0, {"frontier": nf})
            if self.bucketed:
                # deferred settle (DESIGN.md §9): record the push obligation
                # and return — the drain delivers the offers bucket-by-bucket
                self._pend = buckets.enqueue_push(self._pend, frontier,
                                                  self.state.sssp.dist)
                self._pend_bound += len(np.unique(plan.src))
                self.state = dataclasses.replace(self.state, edges=edges)
            elif self._route_sparse(len(np.unique(plan.src))):
                sp_fn = (frontier_mod.sparse_relax_until_converged
                         if self.sources is None
                         else frontier_mod.sparse_relax_batched)
                sssp, stats, occ = sp_fn(
                    self.state.sssp, edges, self._out.state, frontier,
                    num_vertices=self.cfg.num_vertices, caps=self._caps,
                    use_kernel=self.cfg.frontier_kernel,
                    interpret=self._interpret)
                self.state = dataclasses.replace(self.state, edges=edges,
                                                 sssp=sssp)
                self._accumulate_relax(stats)
                self._fold_occupancy(occ)
            else:
                relax_fn = (self.backend.relax if self.sources is None
                            else self.backend.relax_batched)
                sssp, stats = relax_fn(self.state.sssp, edges, frontier)
                self.state = dataclasses.replace(self.state, edges=edges,
                                                 sssp=sssp)
                self._accumulate_relax(stats)
            self.n_adds += len(plan.slots)
            self.n_epochs += 1

    def _fallback_to_sliced(self) -> None:
        """relax_backend="auto": the dense-ELL rebuild just reported hub
        blowup (K*N cells >> live edges) — swap to the sliced/hybrid layout,
        rebuilt from the pool mirror exactly as a restore would."""
        self._auto = False
        self.backend_name = "sliced"
        self.backend = bk_mod.make_backend(
            "sliced", self.cfg, use_kernel=self._use_kernel,
            interpret=self._interpret)
        self.backend.restore(self.alloc)

    # ------------------------------------------------------------------ dels
    def _ingest_dels(self, batch: ev.EventBatch) -> None:
        for gsrc, gdst in self._deletion_groups(batch):
            with self.obs.tracer.span("plan_dels"):
                slots, psrc, pdst = self.alloc.plan_dels(gsrc, gdst)
            if len(slots) == 0:
                continue
            with self.obs.epoch("del_epoch", events=len(slots)):
                self._del_group(slots, psrc, pdst)

    def _del_group(self, slots: np.ndarray, psrc: np.ndarray,
                   pdst: np.ndarray) -> None:
        """One dispatched deletion epoch (one span, one flight record)."""
        slots_p, psrc_p, pdst_p = ingest.pad_pow2(slots, psrc, pdst)
        if self._sparse:
            self._out.apply_dels(psrc_p, pdst_p)
        if self.bucketed:
            # ONE fused dispatch: deactivate + seed + mark + invalidate,
            # recomputation deferred to the drain (DESIGN.md §9).  The
            # layout tombstones still stage as their own patch op.
            self.backend.apply_dels(pdst_p, psrc_p)
            # the affected subtree's size is device-only knowledge; pin the
            # pending bound to N so the "auto" drain routes dense
            self._pend_bound = self.cfg.num_vertices
            fn = (buckets.lazy_delete if self.sources is None
                  else buckets.lazy_delete_batched)
            sssp, edges, self._pend, dstats = fn(
                self.state.sssp, self.state.edges, self._pend,
                jnp.asarray(psrc_p), jnp.asarray(pdst_p),
                jnp.asarray(slots_p),
                num_vertices=self.cfg.num_vertices,
                use_doubling=self.cfg.use_doubling)
            self.state = dataclasses.replace(self.state, edges=edges,
                                             sssp=sssp)
            self._accumulate_delete(dstats)
            self.n_dels += len(slots)
            self.n_epochs += 1
            return
        # Epoch before the deletion is implicit: every prior batch ran to
        # convergence.  Seed from the *pre-deletion* tree, then
        # deactivate.  Batched lanes seed independently — whether a
        # deleted edge was a tree edge depends on each lane's forest.
        if self.sources is None:
            seed = del_mod.deletion_seed_for_edges(
                self.state.sssp, jnp.asarray(psrc_p),
                jnp.asarray(pdst_p), self.cfg.num_vertices)
            delete_fn = self.backend.delete
        else:
            seed = del_mod.deletion_seed_for_edges_batched(
                self.state.sssp, jnp.asarray(psrc_p),
                jnp.asarray(pdst_p), self.cfg.num_vertices)
            delete_fn = self.backend.delete_batched
        edges = ingest.apply_dels(self.state.edges, jnp.asarray(slots_p))
        self.backend.apply_dels(pdst_p, psrc_p)
        # Non-tree deletions (all-false seed) are a device no-op with
        # zeroed stats — cheaper than syncing on bool(jnp.any(seed)).
        # Sparse routing for DELs is mode="sparse" only: the affected
        # region's size is device-only knowledge, so "auto" stays dense.
        if self._sparse and self.cfg.frontier_mode == "sparse":
            sp_fn = (frontier_mod.sparse_invalidate_and_recompute
                     if self.sources is None
                     else frontier_mod.sparse_delete_batched)
            sssp, dstats, occ = sp_fn(
                self.state.sssp, edges, self._out.state, seed,
                num_vertices=self.cfg.num_vertices, caps=self._caps,
                use_doubling=self.cfg.use_doubling,
                use_kernel=self.cfg.frontier_kernel,
                interpret=self._interpret)
            self._fold_occupancy(occ)
        else:
            sssp, dstats = delete_fn(self.state.sssp, edges, seed)
        self.state = dataclasses.replace(self.state, edges=edges, sssp=sssp)
        self._accumulate_delete(dstats)
        self.n_dels += len(slots)
        self.n_epochs += 1

    # ----------------------------------------------------------------- query
    def drain(self) -> None:
        """Settle the bucketed schedule's pending work (no-op under the
        rounds schedule or with nothing pending — the drain's cond-gated
        pull and empty while loop cost one cheap dispatch, no host sync).
        Public so benches/tests can force a converged tree without the
        query()'s readback."""
        if not self.bucketed:
            return
        if self.obs.enabled:
            # bucket occupancy at drain entry (lazy device sums, §10.1);
            # [S] per-lane vectors on a batched engine
            occ_push, occ_pull = buckets.pending_occupancy(self._pend)
            occ_dim = None if self.sources is None else "lane"
            self.obs.counters.add("pending_push", occ_push, dim=occ_dim)
            self.obs.counters.add("pending_pull", occ_pull, dim=occ_dim)
        with self.obs.epoch("drain"):
            bw = self._bucket_width()
            if self._route_sparse(self._pend_bound):
                sp_fn = (frontier_mod.sparse_drain if self.sources is None
                         else frontier_mod.sparse_drain_batched)
                sssp, self._pend, stats, occ = sp_fn(
                    self.state.sssp, self.state.edges, self._out.state,
                    self._pend, num_vertices=self.cfg.num_vertices,
                    caps=self._caps, bucket_width=bw,
                    use_kernel=self.cfg.frontier_kernel,
                    interpret=self._interpret)
                self._fold_occupancy(occ)
            else:
                drain_fn = (self.backend.drain if self.sources is None
                            else self.backend.drain_batched)
                sssp, self._pend, stats = drain_fn(
                    self.state.sssp, self.state.edges, self._pend,
                    bucket_width=bw)
            self._pend_bound = 0
            self.state = dataclasses.replace(self.state, sssp=sssp)
            self._accumulate_relax(stats)
            if self.obs.enabled:
                # waves this drain spent (the §9 bucket pacing figure)
                self.obs.counters.add("drain_waves", stats.rounds)

    def _device_pair(self, lane: int | None) -> tuple[jax.Array, jax.Array]:
        """The pair the base query() settles and reads back; a routed lane
        query transfers only that source's [N] pair."""
        self.drain()
        s = self.state.sssp
        return (s.dist, s.parent) if lane is None else \
            (s.dist[lane], s.parent[lane])

    # ------------------------------------------------------------ checkpoint
    def checkpoint(self) -> dict[str, np.ndarray]:
        """O(N+E) snapshot for fault tolerance (see train/checkpoint.py for
        the sharded writer used at scale).  Backend layout state is NOT
        serialized — it is a derived view, rebuilt from the pool on
        restore (the protocol's checkpoint-participation rule)."""
        with self.obs.epoch("checkpoint"):
            self.drain()   # a checkpoint must capture a converged tree
            e, s = self.state.edges, self.state.sssp
            return {
                "src": np.asarray(e.src), "dst": np.asarray(e.dst),
                "w": np.asarray(e.w), "active": np.asarray(e.active),
                "dist": np.asarray(s.dist), "parent": np.asarray(s.parent),
                "source": np.asarray(s.source),
                "cursor": np.asarray(self.state.cursor),
            }

    def restore(self, ckpt: dict[str, np.ndarray]) -> None:
        self.state = GraphState(
            edges=EdgePool(jnp.asarray(ckpt["src"]), jnp.asarray(ckpt["dst"]),
                           jnp.asarray(ckpt["w"]), jnp.asarray(ckpt["active"])),
            sssp=SSSPState(jnp.asarray(ckpt["dist"]), jnp.asarray(ckpt["parent"]),
                           jnp.asarray(ckpt["source"])),
            cursor=jnp.asarray(ckpt["cursor"]),
        )
        # rebuild host planner state (slot map + mirror) from the pool
        self.alloc = ingest.allocator_cls(self.cfg.alloc_impl).from_pool(
            self.cfg.edge_capacity, self.cfg.on_duplicate,
            ckpt["src"], ckpt["dst"], ckpt["w"], ckpt["active"])
        self.backend.restore(self.alloc)
        if self._sparse:
            self._out.restore(self.alloc)
        # the restore's layout rebuild is a real rebuild event (§10)
        self.obs.note_layout(self.backend.layout_counters())
        # checkpoints are taken post-drain, so nothing was pending
        self._pend = buckets.empty_pending(
            self.cfg.num_vertices,
            None if self.sources is None else len(self.sources))
        self._pend_bound = 0
