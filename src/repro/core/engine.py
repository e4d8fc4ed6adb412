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

Frontier-compacted waves (DESIGN.md §12): on one source under the rounds
schedule with the segment backend, every push wave runs through the
capacity ladder (``core/frontier.py``), which the device steers per wave
from the frontier it sees, and an ADD epoch's first wave relaxes the
inserted edges alone.  ``frontier_mode="dense"`` keeps the dense reference;
``"sparse"`` forces the ladder on every engine shape.

Host-sync rules (DESIGN.md §2.4): the ingest loop never blocks on device
values.  Round/message stats accumulate in device scalars (DEL rounds also
apart, ``rounds_by_kind``; each round by how it ran, ``rounds_by_route``)
and are only read back inside ``query()``; deletion epochs run
unconditionally (an all-false seed is a cheap device no-op) instead of the
old ``bool(jnp.any(seed))`` round-trip per deletion.
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
from repro.core.backends.base import ladder_route
from repro.core.state import EdgePool, GraphState, SSSPState
from repro.core.stream import QueryResult, StreamEngineBase
from repro.kernels.relax import config as kernel_config
from repro.obs import WatchdogConfig

__all__ = ["EngineConfig", "QueryResult", "SSSPDelEngine", "RELAX_BACKENDS",
           "ROUTES"]

# how a round ran: the ADD epoch's wave over its inserted edges, a compacted
# ladder wave, a dense wave over the pool, a DEL epoch's invalidation round,
# or its bulk pull (a drain's pull too)
ROUTES = ("seed", "sparse", "dense", "invalidation", "pull")


def _route_counts(*counts) -> jax.Array:
    """Per-route counts (scalars, or [S] vectors) stacked on a last axis."""
    return jnp.stack(jnp.broadcast_arrays(
        *(jnp.asarray(c, jnp.int32) for c in counts)), axis=-1)


@jax.jit
def _fold_relax(rounds, messages, routes, stats, tally, pulled):
    """One dispatch folding an ADD epoch's or a drain's ``RelaxStats`` into
    the cumulative rounds, messages and route counts.  ``tally`` (a ladder
    epoch's ``WaveTally``) says how many waves were seeds or compacted, the
    rest ran dense; ``pulled`` is a drain's pending-pull mask, whose
    non-emptiness cost the drain one pull round."""
    seed = sparse = pull = 0
    if tally is not None:
        seed, sparse = tally.seed, tally.sparse
    if pulled is not None:
        pull = jnp.any(pulled, axis=-1).astype(jnp.int32)
    inc = _route_counts(seed, sparse, stats.rounds - seed - sparse - pull,
                        0, pull)
    return rounds + stats.rounds, messages + stats.messages, routes + inc


@jax.jit
def _fold_delete(rounds, del_rounds, messages, routes, dstats, tally):
    """One dispatch folding a deletion epoch's ``DeleteStats`` into the
    cumulative rounds, DEL rounds, messages and route counts; also returns
    the epoch's own rounds and messages (the histogram samples).  A
    recompute that ran began with its one bulk pull."""
    r = dstats.invalidation_rounds + dstats.recompute_rounds
    m = dstats.recompute_messages + dstats.affected
    pull = jnp.minimum(dstats.recompute_rounds, 1)
    sparse = 0 if tally is None else tally.sparse
    inc = _route_counts(0, sparse, dstats.recompute_rounds - pull - sparse,
                        dstats.invalidation_rounds, pull)
    return rounds + r, del_rounds + r, messages + m, routes + inc, r, m


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
    # frontier-compacted waves (DESIGN.md §12): "auto" runs every push wave
    # through the capacity ladder where that pays (one source, rounds
    # schedule, segment backend: ``backends.ladder_route``) and dense
    # elsewhere; "sparse" forces the ladder on every engine shape; "dense"
    # is the reference
    frontier_mode: str = "auto"
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
        # frontier-compacted waves (DESIGN.md §12): OUT-adjacency sidecar +
        # capacity ladder; the ADD epochs of one source under the rounds
        # schedule start from a seed wave over the inserted edges
        self._ladder = ladder_route(cfg)
        self._seeded = (self._ladder and self.sources is None
                        and not self.bucketed)
        if self._ladder:
            self._out = frontier_mod.OutAdjacency(cfg.num_vertices,
                                                  cfg.edge_capacity)
            self._caps = frontier_mod.capacity_ladder(cfg.num_vertices,
                                                      cfg.frontier_cap)
        # rounds by route (ROUTES), device-side like _dev_rounds
        self._dev_routes = jnp.zeros(
            (() if self.sources is None else (len(self.sources),))
            + (len(ROUTES),), jnp.int32)
        # bucket_width="auto" resolution cache: (resolved width, live-edge
        # estimate at resolution) — re-resolved when the pool doubles/halves
        self._bw_cache: tuple[float, int] | None = None

    # ----------------------------------------------------------- counters
    def _accumulate_relax(self, stats, tally=None, pulled=None) -> None:
        """Fold one ADD epoch's or drain's ``RelaxStats`` (and a ladder
        epoch's ``WaveTally``) into the device counters in one dispatch —
        no host sync.  Batched epochs carry ``[S]`` stat vectors.  With obs
        on, the same stats also record one sample each for the
        waves/messages-per-epoch histograms (§10.6), and a ladder epoch its
        ``frontier_occupancy``."""
        self._dev_rounds, self._dev_messages, self._dev_routes = _fold_relax(
            self._dev_rounds, self._dev_messages, self._dev_routes, stats,
            tally, pulled)
        if self.obs.enabled:
            self.obs.hist_device("hist_waves_per_epoch", stats.rounds)
            self.obs.hist_device("hist_messages_per_epoch", stats.messages)
            self._fold_occupancy(tally)

    def _accumulate_delete(self, dstats, tally=None) -> None:
        """Fold one deletion epoch's ``DeleteStats`` into the device
        counters, the DEL rounds among them, in one dispatch; ``affected``
        counts as messages (the SetToInfinity deliveries), matching the
        sharded epochs' accounting."""
        (self._dev_rounds, self._dev_del_rounds, self._dev_messages,
         self._dev_routes, rounds, messages) = _fold_delete(
            self._dev_rounds, self._dev_del_rounds, self._dev_messages,
            self._dev_routes, dstats, tally)
        if self.obs.enabled:
            self.obs.hist_device("hist_waves_per_epoch", rounds)
            self.obs.hist_device("hist_messages_per_epoch", messages)
            self._fold_occupancy(tally)

    def _fold_occupancy(self, tally) -> None:
        if tally is not None:
            occ = tally.occupancy
            self.obs.counters.add(
                "frontier_occupancy", occ if occ.ndim == 0 else jnp.sum(occ))

    @property
    def rounds_by_route(self) -> dict[str, int | np.ndarray]:
        """``n_rounds`` split by how each round ran (``ROUTES``), summing
        to it exactly: ``seed`` the ADD epochs' waves over their inserted
        edges, ``sparse`` the waves a ladder rung ran compacted, ``dense``
        every other wave over the pool, ``invalidation`` and ``pull`` the
        DEL epochs' marking rounds and bulk pulls (and the drains' pulls).
        Per source when batched."""
        got = np.asarray(jax.device_get(self._dev_routes))
        return {k: self._counter(got[..., i]) for i, k in enumerate(ROUTES)}

    def _stream_stats(self) -> dict:
        return {**super()._stream_stats(),
                "rounds_by_route": self.rounds_by_route}

    def metrics_snapshot(self) -> dict:
        return {**super().metrics_snapshot(),
                "rounds_by_route": self.rounds_by_route}

    # ---------------------------------------------------------- width policy

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
            src_d, dst_d, w_d = (jnp.asarray(a) for a in (src_p, dst_p, w_p))
            edges = ingest.apply_adds(self.state.edges, jnp.asarray(slots_p),
                                      src_d, dst_d, w_d)
            self.backend.apply_adds(plan, self.alloc)
            if self._ladder:
                # the OUT-adjacency sidecar rides along with every layout
                # patch
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
            if self._seeded:
                # the first wave relaxes the inserted edges alone, then the
                # ladder loop continues from what they improved
                sssp, stats, tally = frontier_mod.seeded_relax(
                    self.state.sssp, edges, self._out.state, src_d, dst_d,
                    w_d, num_vertices=self.cfg.num_vertices, caps=self._caps,
                    use_kernel=self.cfg.frontier_kernel,
                    interpret=self._interpret)
                self.state = dataclasses.replace(self.state, edges=edges,
                                                 sssp=sssp)
                self._accumulate_relax(stats, tally)
                self.n_adds += len(plan.slots)
                self.n_epochs += 1
                return
            # Frontier = tails of the inserted edges (paper Listing 3: tail
            # offers its distance to the head).  Relaxing from the tails
            # delivers exactly those offers (plus no-op re-offers along
            # other out-edges).
            frontier = relax.frontier_from_vertices(src_d,
                                                    self.cfg.num_vertices)
            if self.bucketed:
                # deferred settle (DESIGN.md §9): record the push obligation
                # and return — the drain delivers the offers bucket-by-bucket
                self._pend = buckets.enqueue_push(self._pend, frontier,
                                                  self.state.sssp.dist)
                self.state = dataclasses.replace(self.state, edges=edges)
            elif self._ladder:
                # batched lanes, forced through the ladder
                sssp, stats, tally = frontier_mod.sparse_relax_batched(
                    self.state.sssp, edges, self._out.state, frontier,
                    num_vertices=self.cfg.num_vertices, caps=self._caps,
                    use_kernel=self.cfg.frontier_kernel,
                    interpret=self._interpret)
                self.state = dataclasses.replace(self.state, edges=edges,
                                                 sssp=sssp)
                self._accumulate_relax(stats, tally)
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
        if self._ladder:
            self._out.apply_dels(slots, psrc)
        if self.bucketed:
            # ONE fused dispatch: deactivate + seed + mark + invalidate,
            # recomputation deferred to the drain (DESIGN.md §9).  The
            # layout tombstones still stage as their own patch op.
            self.backend.apply_dels(pdst_p, psrc_p)
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
        # zeroed stats — cheaper than syncing on bool(jnp.any(seed)).  On
        # the ladder route the recompute's push waves run compacted
        # wherever the device finds the affected region small enough.
        tally = None
        if self._ladder:
            sp_fn = (frontier_mod.sparse_invalidate_and_recompute
                     if self.sources is None
                     else frontier_mod.sparse_delete_batched)
            sssp, dstats, tally = sp_fn(
                self.state.sssp, edges, self._out.state, seed,
                num_vertices=self.cfg.num_vertices, caps=self._caps,
                use_doubling=self.cfg.use_doubling,
                use_kernel=self.cfg.frontier_kernel,
                interpret=self._interpret)
        else:
            sssp, dstats = delete_fn(self.state.sssp, edges, seed)
        self.state = dataclasses.replace(self.state, edges=edges, sssp=sssp)
        self._accumulate_delete(dstats, tally)
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
            pulled, tally = self._pend.pull, None
            if self._ladder:
                sp_fn = (frontier_mod.sparse_drain if self.sources is None
                         else frontier_mod.sparse_drain_batched)
                sssp, self._pend, stats, tally = sp_fn(
                    self.state.sssp, self.state.edges, self._out.state,
                    self._pend, num_vertices=self.cfg.num_vertices,
                    caps=self._caps, bucket_width=bw,
                    use_kernel=self.cfg.frontier_kernel,
                    interpret=self._interpret)
            else:
                drain_fn = (self.backend.drain if self.sources is None
                            else self.backend.drain_batched)
                sssp, self._pend, stats = drain_fn(
                    self.state.sssp, self.state.edges, self._pend,
                    bucket_width=bw)
            self.state = dataclasses.replace(self.state, sssp=sssp)
            self._accumulate_relax(stats, tally, pulled)
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
        if self._ladder:
            self._out.restore(self.alloc)
        # the restore's layout rebuild is a real rebuild event (§10)
        self.obs.note_layout(self.backend.layout_counters())
        # checkpoints are taken post-drain, so nothing was pending
        self._pend = buckets.empty_pending(
            self.cfg.num_vertices,
            None if self.sources is None else len(self.sources))
