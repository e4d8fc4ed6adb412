"""ShardedSSSPDelEngine — the fully dynamic engine over the vertex-partitioned
device mesh (DESIGN.md §5, §7.2).

This is the convergence of the repo's two halves: ``core/engine.py`` ingests
ADD/DEL/QUERY streams on one device; ``core/distributed.py`` solves static
graphs over a shard_map mesh.  Here the *same* ``EventLog`` stream drives
per-partition edge pools living across the mesh:

  * **Ownership**: vertices are range-partitioned over the flattened mesh
    axes (``npp`` per shard); an edge lives with the owner of its **dst** so
    the per-round scatter-min is shard-local (paper §3's shared-nothing
    mapping, same as ``DistributedSSSP``).
  * **Control plane**: one host-side ``SlotAllocator`` per partition (the
    ingest.py mirror/planning machinery, keyed by dst-owner) plans where each
    topology event lands in its owner's fixed ``Epp``-slot pool.  Global slot
    ``p*Epp + local`` addresses the sharded device arrays directly.
  * **Relaxation backend** (DESIGN.md §7.2): ``relax_backend=`` selects any
    registered backend.  The coordinator (core/backends/) holds one
    shard-local planner per partition — dst-owner placement makes every
    shard's in-edges local, so per-shard layout rows are exactly the owned
    vertex window — plus the per-shard layout blocks concatenated into
    globally sharded device arrays.  ADD patches run as separate jitted
    scatters before the fused epoch (amortized over the batch); DEL
    tombstones run INSIDE the fused deletion epoch (per-event hot path);
    the backend's wave replaces the hardwired segment-min inside the
    shard_map epochs' relaxation body.
  * **Data plane**: one jitted shard_map epoch per batch patches the pools in
    place (masked writes routed through a sacrificial slot so foreign batch
    entries never collide with real ones) and immediately runs the
    relaxation / deletion epoch seeded from the batch — frontier = tails of
    inserted edges; seeds = heads of deleted tree edges — reusing
    ``DistributedSSSP``'s allgather/delta exchange rounds.
  * **Host-sync rules** (DESIGN.md §2.4): the ingest loop never blocks on a
    device value.  Round/message counters thread through the epochs as
    replicated device scalars and are read back only in ``query()``;
    deletion epochs dispatch unconditionally (all-false seed = cheap no-op).
  * **Batched multi-source serving** (DESIGN.md §8): ``sources=(s0, ...)``
    stacks S trees as [S, N] dist/parent arrays sharded along the vertex
    axis; the ``_build_epochs_ms`` builder patches the shared pool/layout
    once per batch and runs the ``*_ms`` relaxation bodies
    (core/distributed.py) with the backend's wave vmapped over the source
    axis — bit-identical per lane to S single-source engines, same
    host-sync rules.

Equivalence contract: with ``exchange="allgather"`` the engine is
**bit-identical** in ``(dist, parent)`` — and equal in rounds/messages — to
``SSSPDelEngine`` *with the same relax_backend* on any event stream, for any
partition count (frontier evolution, candidate sets and smallest-src-id
tie-breaks are the same wave for wave; float min is exact) — and all
backends are bit-identical to each other (test_backend_equiv.py), so the
contract holds across the full backend x partition-count grid.  The
``"delta"`` exchange reaches the same ``(dist, parent)`` fixpoint with
compressed traffic (overflow rounds fall back to dense gathers — still
exact, see tests/test_sssp_distributed.py).

Optional **edge-balanced placement**: pass the ``(perm, inv, npp)`` triple
from ``graphs.partition.edge_balanced_relabeling`` (built for this mesh's
partition count) as ``relabel`` — events are permuted on ingest and results
un-permuted at query, so shards own ~equal in-edge mass instead of ~equal
vertex counts.  Distances are unchanged (same paths, same float sums);
parent ties may resolve differently (smallest *relabeled* id).

Checkpoint/restore reuses the single-device schema (pool snapshot +
dist/parent windows); backend layout state is a derived view and is rebuilt
from the per-partition mirrors on restore, never serialized.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import backends as bk_mod
from repro.core import events as ev
from repro.core import frontier as frontier_mod
from repro.core import ingest
from repro.core.backends.base import SHARDED_BACKENDS
from repro.core.distributed import (DistConfig, DistributedSSSP,
                                    inactive_dst_layout,
                                    per_partition_occupancy)
from repro.core.state import INF, NO_PARENT
from repro.core.stream import StreamEngineBase
from repro.launch import mesh as mesh_mod
from repro.obs import WatchdogConfig


EXCHANGES = ("allgather", "delta")

# Jitted epoch builders keyed by everything their traces depend on — the
# mesh/exchange config plus the backend's static geometry key — shared
# across engine instances: the closures are per-instance, so without this a
# fresh engine (benchmark warm/timed pairs, test sweeps) would re-trace and
# re-lower every batch shape it has already seen.  Layout arrays flow
# through epoch *arguments* (their shapes re-trace automatically); only
# truly static geometry (e.g. the sliced widths tuple) lives in the key.
_EPOCH_CACHE: dict[tuple, tuple] = {}


@dataclasses.dataclass
class ShardedEngineConfig:
    num_vertices: int        # logical |V| (pre-padding, pre-relabel)
    edges_per_part: int      # static per-partition edge-pool capacity (Epp)
    source: int
    exchange: str = "allgather"   # or "delta" (DESIGN.md §5.3)
    delta_cap: int = 4096    # per-part (idx,val) slots for "delta" exchange
    use_doubling: bool = True     # False = paper's wave-by-wave flood
    batch_deletions: bool = False
    on_duplicate: str = "ignore"  # or "min" (weight decreases)
    # Relaxation backend (DESIGN.md §7.2) + its knobs — same fields and
    # defaults as EngineConfig so the two validate identically.
    relax_backend: str = "segment"
    ell_block_rows: int = 256
    ell_init_k: int = 8
    ell_use_kernel: bool = False  # Pallas ELL row-min kernel (DESIGN.md §2.7)
    sliced_slice_rows: int = 256
    sliced_hub_k: int = 32
    sliced_init_k: int = 2
    # wave schedule (DESIGN.md §9): "rounds" settles every epoch to
    # fixpoint; "buckets" defers settling into delta-stepping drains run at
    # query/checkpoint — the bucket threshold is a replicated scalar, so the
    # sharded drain reuses the existing allgather/delta exchanges unchanged
    wave_schedule: str = "rounds"
    # delta; inf = one bucket; "auto" = pow2-quantized live-weight median
    # resolved host-side from the per-partition mirrors (DESIGN.md §9.5)
    bucket_width: float | str = 1.0
    # frontier-compacted sparse waves (DESIGN.md §12.4): "sparse" compacts
    # each partition's live-offer edges into a bounded worklist inside the
    # wave body (the backend's own dense wave is the in-cond fallback);
    # "auto" routes dense here — per-partition occupancy is device-only
    # knowledge, and the single-rung cond already bounds the regression
    frontier_mode: str = "dense"
    frontier_cap: int = 0    # per-partition edge-worklist cap; 0 = Epp/64
    # batched multi-source serving (DESIGN.md §8); None = single-source
    sources: tuple[int, ...] | None = None
    # observability (DESIGN.md §10) — same contract as EngineConfig; the
    # sharded registry folds per-partition [P] vectors, no new collectives
    observability: bool = False
    obs_flight_capacity: int = 128
    # stall/divergence watchdog (§10.8); None = off
    obs_watchdog: "WatchdogConfig | None" = None
    # control-plane implementation (DESIGN.md §11); same knob as
    # EngineConfig.alloc_impl, applied to every per-partition planner
    alloc_impl: str = "columnar"

    def __post_init__(self):
        bk_mod.validate_backend_config(self)
        ingest.allocator_cls(self.alloc_impl)  # raises on unknown impl
        if self.exchange not in EXCHANGES:
            raise ValueError(f"unknown exchange {self.exchange!r}; valid: "
                             f"{EXCHANGES}")
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


class ShardedSSSPDelEngine(StreamEngineBase):
    """Host orchestrator over shard_map ingest+epoch device code.

    ``mesh=None`` flattens every local device onto one "graph" axis; any
    explicit mesh works — all its axes are flattened into the vertex
    partition (launch/mesh.graph_axes), exactly like ``DistributedSSSP``.
    """

    def __init__(self, cfg: ShardedEngineConfig, mesh: Mesh | None = None,
                 relabel: tuple[np.ndarray, np.ndarray, int] | None = None):
        super().__init__(sources=cfg.sources,
                         observability=cfg.observability,
                         flight_capacity=cfg.obs_flight_capacity,
                         watchdog=cfg.obs_watchdog)
        self.cfg = cfg
        if mesh is None:
            mesh = mesh_mod._mk((len(jax.devices()),), ("graph",))
        axes = tuple(mesh.axis_names)
        P_ = 1
        for a in axes:
            P_ *= mesh.shape[a]
        if relabel is not None:
            perm, inv, npp_r = relabel
            self.perm = np.asarray(perm, np.int32)
            self.inv = np.asarray(inv, np.int32)
            assert len(self.perm) == cfg.num_vertices, "perm must cover |V|"
            assert npp_r * P_ == len(self.inv), (
                f"relabeling was built for {len(self.inv) // max(npp_r, 1)} "
                f"partitions (npp={npp_r}); this mesh flattens to P={P_} — "
                "rebuild with edge_balanced_relabeling(n, dst, P)")
            n_pad = len(self.inv)
        else:
            self.perm = self.inv = None
            n_pad = P_ * (-(-cfg.num_vertices // P_))
        self.ds = DistributedSSSP(mesh, DistConfig(
            num_vertices=n_pad, edges_per_part=cfg.edges_per_part,
            mesh_axes=axes, exchange=cfg.exchange, delta_cap=cfg.delta_cap))
        self.P, self.npp, self.epp = self.ds.P, self.ds.npp, cfg.edges_per_part
        # single-source: one padded/relabeled source id; batched serving: a
        # static tuple of them (the epoch-cache key and the epochs' "never
        # invalidate the source" mask are per lane)
        if self.sources is None:
            self._source_pad = int(cfg.source if self.perm is None
                                   else self.perm[cfg.source])
        else:
            self._source_pad = tuple(
                int(s if self.perm is None else self.perm[s])
                for s in self.sources)
        # control plane: one planner per partition, local Epp-slot pools
        self.allocs = [ingest.make_allocator(cfg.edges_per_part,
                                             cfg.on_duplicate,
                                             cfg.alloc_impl)
                       for _ in range(self.P)]
        # relaxation backend: per-shard planners + sharded layout arrays
        self.bk = bk_mod.make_sharded_backend(
            cfg.relax_backend, cfg, self.ds, self.allocs)
        # data plane: sharded vertex + edge-pool arrays ([S, N] stacked
        # trees over the one sharded pool in batched serving mode)
        if self.sources is None:
            self.dist, self.parent = self.ds.init_vertex_arrays(
                self._source_pad)
        else:
            self.dist, self.parent = self.ds.init_vertex_arrays_ms(
                self._source_pad)
        self.esrc, self.edst, self.ew, self.eact = self.ds.put_edges(
            np.zeros(self.P * self.epp, np.int32),
            inactive_dst_layout(self.P, self.npp, self.epp),
            np.zeros(self.P * self.epp, np.float32),
            np.zeros(self.P * self.epp, np.bool_))
        # frontier-compacted sparse waves (DESIGN.md §12.4): "sparse"
        # compacts inside every wave body (single rung + in-cond dense
        # fallback); "auto" routes dense — the occupancy signal is
        # device-only here and must not be synced per epoch (§2.4)
        self._fcap = 0
        if cfg.frontier_mode == "sparse":
            self._fcap = frontier_mod.capacity_ladder(
                cfg.edges_per_part, cfg.frontier_cap)[-1]
        # bucket_width="auto" resolution cache (same policy as the
        # single-device engine: pow2-quantized live-weight median,
        # re-resolved when the live-edge estimate doubles/halves)
        self._bw_cache: tuple[float, int] | None = None
        self._base_key = (mesh, n_pad, cfg.edges_per_part, cfg.exchange,
                          cfg.delta_cap, cfg.use_doubling, self._source_pad,
                          cfg.wave_schedule, self._fcap)
        # bucketed schedule: sharded pending masks (bool per owned vertex,
        # [S, N] stacked in serving mode), reset to the cached zeros after
        # every drain
        self.bucketed = cfg.wave_schedule == "buckets"
        if self.bucketed:
            shape = ((self.P * self.npp,) if self.sources is None
                     else (len(self.sources), self.P * self.npp))
            sh = (self.ds.vertex_sharding() if self.sources is None
                  else self.ds.vertex_sharding_ms())
            self._zero_pend = jax.device_put(np.zeros(shape, np.bool_), sh)
            self._push = self._pull = self._zero_pend
        # touched-vertex attribution baseline (§10.5): dist as of the last
        # metrics readout; compared once per snapshot, never per epoch
        self._obs_dist_mark = self.dist if self.obs.enabled else None

    def _epoch_pair(self):
        """The (add_epoch, del_epoch, drain_epoch) triple for the CURRENT
        backend geometry — looked up per batch because a coupled rebuild may
        change the backend's static key (e.g. the sliced widths tuple).
        ``drain_epoch`` is None under the rounds schedule."""
        bw = self._bucket_width()
        key = self._base_key + (bw,) + self.bk.static_key()
        if key not in _EPOCH_CACHE:
            build = (_build_epochs if self.sources is None
                     else _build_epochs_ms)
            _EPOCH_CACHE[key] = build(
                self.ds, self.epp, self.cfg.use_doubling, self._source_pad,
                self.cfg.relax_backend, self.bk.static_key(),
                self.cfg.wave_schedule, bw, self._fcap)
        return _EPOCH_CACHE[key]

    def _bucket_width(self) -> float:
        """Resolve ``bucket_width="auto"`` host-side from the concatenated
        per-partition mirror weights — same quantize/re-resolve policy as
        ``SSSPDelEngine._bucket_width`` so the two engines pick the same
        width on the same stream (no device sync; mirrors are host state)."""
        if self.cfg.bucket_width != "auto":
            return self.cfg.bucket_width
        live_est = max(1, self.n_adds - self.n_dels)
        if self._bw_cache is not None:
            width, at = self._bw_cache
            if at / 2 <= live_est <= at * 2:
                return width
        w = np.concatenate([a.active_coo()[2] for a in self.allocs]) \
            if self.allocs else np.empty(0, np.float32)
        if len(w) == 0:
            width = 1.0
        else:
            med = max(float(np.percentile(w, 50.0)), 1e-6)
            width = float(2.0 ** np.round(np.log2(med)))
        self._bw_cache = (width, live_est)
        return width

    # ------------------------------------------------------- per-epoch obs
    def _fold_epoch_obs(self) -> None:
        """Post-epoch §10.6 recording, ZERO device dispatches: the epochs
        return updated CUMULATIVE round/message counters, so appending the
        returned array references is enough — consecutive diffs (the same
        deltas ``drain_waves`` uses) become the per-epoch histogram
        samples in one stacked fold at snapshot flush."""
        self.obs.hist_cumulative("hist_waves_per_epoch", self._dev_rounds)
        self.obs.hist_cumulative("hist_messages_per_epoch",
                                 self._dev_messages)

    def _obs_pre_snapshot(self) -> None:
        """Per-partition touched-vertex attribution (§10.5): vertices whose
        dist changed since the LAST metrics readout, reduced shard-locally
        to a [P] vector ([S] per-lane batched).  One compare per READOUT —
        per-epoch diffing would dominate the tiny sharded epochs and break
        the §10.4 overhead contract."""
        mark = self._obs_dist_mark
        if mark is not None and mark.shape == self.dist.shape:
            upd = per_partition_occupancy(self.dist != mark, self.P,
                                          self.npp)
            if self.sources is None:
                self.obs.counters.add("updates_per_part", upd,
                                      dim="partition")
            else:
                self.obs.counters.add("updates_per_lane", upd, dim="lane")
        self._obs_dist_mark = self.dist

    # ------------------------------------------------------------------ adds
    def _ingest_adds(self, batch: ev.EventBatch) -> None:
        with self.obs.tracer.span("plan_adds"):
            src, dst, w = batch.src, batch.dst, batch.w
            if self.perm is not None:
                src, dst = self.perm[src], self.perm[dst]
            owner = np.asarray(dst, np.int64) // self.npp
            parts, plans = [], []
            for p in np.unique(owner):
                sel = owner == p
                plan = self.allocs[p].plan_adds(src[sel], dst[sel], w[sel])
                if len(plan.slots):
                    plans.append((int(p), plan))
                    parts.append((int(p) * self.epp
                                  + plan.slots.astype(np.int64),
                                  plan.src, plan.dst, plan.w))
            if not parts:
                return
            gslot, bsrc, bdst, bw = (np.concatenate(x) for x in zip(*parts))
        n_acc = len(gslot)
        with self.obs.epoch("add_epoch", events=n_acc):
            self.bk.stage_adds(plans)  # layout patches (or coupled rebuild)
            self.obs.note_layout(self.bk.layout_counters())
            if self.obs.enabled:
                # host-planned figures (§10.1): frontier = distinct inserted
                # tails; adds_per_part = a [P] numpy tally — no device work
                tails = np.unique(bsrc)
                nf = len(tails)
                self.obs.counters.inc("frontier", nf)
                # occupancy histogram sample + per-partition frontier
                # attribution (owners of the tail vertices) — §10.5/§10.6;
                # owners partition the tails, so sum(frontier_per_part)
                # stays == the flat "frontier" counter
                self.obs.hist_host("hist_frontier_occupancy", nf)
                self.obs.counters.inc(
                    "frontier_per_part",
                    np.bincount(tails.astype(np.int64) // self.npp,
                                minlength=self.P).astype(np.int64),
                    dim="partition")
                per_part = np.zeros(self.P, np.int64)
                for p, plan in plans:
                    per_part[p] = len(plan.slots)
                self.obs.counters.inc("adds_per_part", per_part,
                                      dim="partition")
                if self.obs.watchdog is not None:
                    self.obs.watchdog.observe(
                        "add_epoch", 0.0, {"frontier": nf})
            gslot, bsrc, bdst, bw = ingest.pad_pow2(
                gslot.astype(np.int32), bsrc, bdst, bw)
            add_epoch, _, _ = self._epoch_pair()
            if self.bucketed:
                # deferred settle (DESIGN.md §9): patch the pools, enqueue
                # the inserted tails as push obligations, no relaxation —
                # and so no waves/messages histogram sample (the drain's
                # delta carries those figures)
                (self.esrc, self.edst, self.ew, self.eact,
                 self._push) = add_epoch(
                    self.dist, self.esrc, self.edst, self.ew, self.eact,
                    self._push, jnp.asarray(gslot), jnp.asarray(bsrc),
                    jnp.asarray(bdst), jnp.asarray(bw))
            else:
                (self.dist, self.parent, self.esrc, self.edst, self.ew,
                 self.eact, self._dev_rounds, self._dev_messages) = add_epoch(
                    self.dist, self.parent, self.esrc, self.edst, self.ew,
                    self.eact, *self.bk.arrays(),
                    jnp.asarray(gslot), jnp.asarray(bsrc), jnp.asarray(bdst),
                    jnp.asarray(bw), self._dev_rounds, self._dev_messages)
                if self.obs.enabled:
                    self._fold_epoch_obs()
            self.n_adds += n_acc
            self.n_epochs += 1

    # ------------------------------------------------------------------ dels
    def _ingest_dels(self, batch: ev.EventBatch) -> None:
        for gsrc, gdst in self._deletion_groups(batch):
            with self.obs.tracer.span("plan_dels"):
                if self.perm is not None:
                    gsrc, gdst = self.perm[gsrc], self.perm[gdst]
                owner = np.asarray(gdst, np.int64) // self.npp
                parts = []
                for p in np.unique(owner):
                    sel = owner == p
                    slots, psrc, pdst = self.allocs[p].plan_dels(
                        gsrc[sel], gdst[sel])
                    if len(slots):
                        parts.append((int(p) * self.epp
                                      + slots.astype(np.int64), psrc, pdst))
                if parts:
                    gslot, psrc, pdst = (np.concatenate(x)
                                         for x in zip(*parts))
            if not parts:
                continue
            n_del = len(gslot)
            with self.obs.epoch("del_epoch", events=n_del):
                if self.obs.enabled:
                    per_part = np.zeros(self.P, np.int64)
                    for g, _, _ in parts:
                        per_part[int(g[0] // self.epp)] = len(g)
                    self.obs.counters.inc("dels_per_part", per_part,
                                          dim="partition")
                gslot, psrc, pdst = ingest.pad_pow2(
                    gslot.astype(np.int32), psrc, pdst)
                _, del_epoch, _ = self._epoch_pair()
                # the layout tombstone runs INSIDE the fused epoch (before
                # the recompute wave; the seed reads only the parent forest)
                # — a staged patch would cost one extra dispatch per
                # deletion, and deletions are per-event in the
                # paper-faithful mode
                n_mut = len(type(self.bk).del_mutated)
                r0 = self._dev_rounds
                if self.bucketed:
                    # invalidation-only epoch: seed + mark + SetToInfinity +
                    # tombstone; the recompute pull and push waves are
                    # deferred into the pending masks (DESIGN.md §9)
                    out = del_epoch(
                        self.dist, self.parent, self.eact, *self.bk.arrays(),
                        self._push, self._pull, jnp.asarray(gslot),
                        jnp.asarray(psrc), jnp.asarray(pdst),
                        self._dev_rounds, self._dev_messages)
                    self.dist, self.parent, self.eact = out[:3]
                    if n_mut:
                        self.bk.update_del_arrays(out[3:3 + n_mut])
                    (self._push, self._pull, self._dev_rounds,
                     self._dev_messages) = out[3 + n_mut:]
                else:
                    out = del_epoch(
                        self.dist, self.parent, self.esrc, self.edst,
                        self.ew, self.eact, *self.bk.arrays(),
                        jnp.asarray(gslot), jnp.asarray(psrc),
                        jnp.asarray(pdst), self._dev_rounds,
                        self._dev_messages)
                    self.dist, self.parent, self.eact = out[:3]
                    if n_mut:
                        self.bk.update_del_arrays(out[3:3 + n_mut])
                    self._dev_rounds, self._dev_messages = out[3 + n_mut:]
                # the epoch returned the cumulative counter: its share
                # is this epoch's DEL rounds (one dispatch)
                self._dev_del_rounds = _add_delta(
                    self._dev_del_rounds, self._dev_rounds, r0)
                if self.obs.enabled:
                    self._fold_epoch_obs()
                self.n_dels += n_del
                self.n_epochs += 1

    # ----------------------------------------------------------------- query
    def drain(self) -> None:
        """Settle the bucketed schedule's pending work (no-op under the
        rounds schedule; with nothing pending the epoch is one cheap
        dispatch — the drain loop exits immediately, no host sync).  Same
        contract as the single-device ``SSSPDelEngine.drain``."""
        if not self.bucketed:
            return
        if self.obs.enabled:
            # bucket occupancy at drain entry (lazy shard-local sums, §10.1):
            # [P] per-partition row counts, or [S] per-lane totals batched —
            # accumulated on device, drained with the registry snapshot
            occ_dim = "partition" if self.sources is None else "lane"
            self.obs.counters.add("pending_push", per_partition_occupancy(
                self._push, self.P, self.npp), dim=occ_dim)
            self.obs.counters.add("pending_pull", per_partition_occupancy(
                self._pull, self.P, self.npp), dim=occ_dim)
        with self.obs.epoch("drain"):
            _, _, drain_epoch = self._epoch_pair()
            r0 = self._dev_rounds
            (self.dist, self.parent, self._dev_rounds,
             self._dev_messages) = drain_epoch(
                self.dist, self.parent, self.esrc, self.edst, self.ew,
                self.eact, *self.bk.arrays(), self._push, self._pull,
                self._dev_rounds, self._dev_messages)
            self._push = self._pull = self._zero_pend
            if self.obs.enabled:
                # waves this drain spent — a lazy device delta of the same
                # counter n_rounds reads (bit-consistent by construction)
                self.obs.counters.add("drain_waves", self._dev_rounds - r0)
                self._fold_epoch_obs()

    def _device_pair(self, lane: int | None) -> tuple[jax.Array, jax.Array]:
        """The sharded pair the base query() settles and reads back; a
        routed lane query transfers only that source's padded [N] pair."""
        self.drain()
        return (self.dist, self.parent) if lane is None else \
            (self.dist[lane], self.parent[lane])

    def _host_view(self, dist: np.ndarray, parent: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """The read-back pair in the caller's vertex ids: the inverse
        relabeling, if any, and the padding rows cut off."""
        n = self.cfg.num_vertices
        if self.perm is not None:
            dist = dist[..., self.perm]
            pp = parent[..., self.perm]
            parent = np.where(pp >= 0, self.inv[np.clip(pp, 0, None)],
                              NO_PARENT).astype(np.int32)
        else:
            dist, parent = dist[..., :n], parent[..., :n]
        return dist, parent

    # ------------------------------------------------------------ checkpoint
    def checkpoint(self) -> dict[str, np.ndarray]:
        """Single-device-schema snapshot (engine.SSSPDelEngine.checkpoint):
        pool arrays in partition-major global-slot order (from the host
        mirrors — no device readback for the pool) plus the padded
        dist/parent windows.  Backend layout state is rebuilt on restore,
        never serialized."""
        with self.obs.epoch("checkpoint"):
            self.drain()   # a checkpoint must capture a converged tree
            return {
                "src": np.concatenate([a.msrc for a in self.allocs]),
                "dst": np.concatenate([a.mdst for a in self.allocs]),
                "w": np.concatenate([a.mw for a in self.allocs]),
                "active": np.concatenate([a.mactive for a in self.allocs]),
                "dist": np.asarray(jax.device_get(self.dist)),
                "parent": np.asarray(jax.device_get(self.parent)),
                "source": np.asarray(self._source_pad),
                "cursor": np.asarray(0),
            }

    def restore(self, ckpt: dict[str, np.ndarray]) -> None:
        """Crash-restart from a ``checkpoint()`` snapshot taken by an engine
        with the same config/mesh/relabel.  Rebuilds the per-partition
        planners from the pool slices, re-shards the device arrays, and
        rebuilds the backend layout from the mirrors."""
        src_ck = np.atleast_1d(np.asarray(ckpt["source"])).tolist()
        src_now = np.atleast_1d(np.asarray(self._source_pad)).tolist()
        assert src_ck == src_now, "source mismatch"
        assert ckpt["dist"].shape[-1] == self.P * self.npp, (
            f"checkpoint has {ckpt['dist'].shape[-1]} vertex rows; this "
            f"engine pads to {self.P * self.npp} — same P/mesh required")
        assert len(ckpt["src"]) == self.P * self.epp, (
            f"checkpoint has {len(ckpt['src'])} pool slots; this engine "
            f"expects {self.P * self.epp} — same edges_per_part required")
        epp = self.epp
        alloc_cls = ingest.allocator_cls(self.cfg.alloc_impl)
        self.allocs = [
            alloc_cls.from_pool(
                epp, self.cfg.on_duplicate,
                ckpt["src"][p * epp:(p + 1) * epp],
                ckpt["dst"][p * epp:(p + 1) * epp],
                ckpt["w"][p * epp:(p + 1) * epp],
                ckpt["active"][p * epp:(p + 1) * epp])
            for p in range(self.P)]
        # inactive slots must keep the padding-row invariant for the
        # shard-local segment ids (see inactive_dst_layout)
        dst = np.where(ckpt["active"], ckpt["dst"],
                       inactive_dst_layout(self.P, self.npp, epp))
        self.esrc, self.edst, self.ew, self.eact = self.ds.put_edges(
            np.asarray(ckpt["src"], np.int32), dst.astype(np.int32),
            np.asarray(ckpt["w"], np.float32),
            np.asarray(ckpt["active"], np.bool_))
        sh = (self.ds.vertex_sharding() if self.sources is None
              else self.ds.vertex_sharding_ms())
        self.dist = jax.device_put(
            np.asarray(ckpt["dist"], np.float32), sh)
        self.parent = jax.device_put(
            np.asarray(ckpt["parent"], np.int32), sh)
        self.bk.allocs = self.allocs
        self.bk.restore()
        # the restore's layout rebuild is a real rebuild event (§10)
        self.obs.note_layout(self.bk.layout_counters())
        # checkpoints are taken post-drain, so nothing was pending
        if self.bucketed:
            self._push = self._pull = self._zero_pend

    # ------------------------------------------------------------ diagnostics
    def partition_fill(self) -> np.ndarray:
        """Live edges per partition, from the host mirrors (no device sync)."""
        return np.array([int(a.mactive.sum()) for a in self.allocs])


@jax.jit
def _add_delta(acc, new, old):
    """``acc + (new - old)`` in one dispatch."""
    return acc + (new - old)


def _build_epochs(ds: DistributedSSSP, epp: int, use_doubling: bool,
                  source_pad: int, backend: str, backend_static: tuple,
                  wave_schedule: str = "rounds", bucket_width: float = 1.0,
                  frontier_cap: int = 0):
    """Build the (add_epoch, del_epoch, drain_epoch) jitted shard_map triple
    for one backend geometry.  Under the rounds schedule the epochs settle
    in place and ``drain_epoch`` is None; under the bucketed schedule the
    add/del epochs are the lazy (invalidation-only) variants and the drain
    epoch settles the pending masks (DESIGN.md §9).

    Module-level on purpose: the closures capture only ``ds`` (mesh + config
    + specs, no device buffers), scalars, and the backend's *static* wave
    factory — layout arrays arrive as epoch arguments — so ``_EPOCH_CACHE``
    entries never pin an engine's device state or host mirrors.
    """
    npp = ds.npp
    ax = ds.cfg.mesh_axes
    exchange = ds.cfg.exchange
    v, e, r = ds.vspec, ds.espec, ds.rspec
    bk_cls = SHARDED_BACKENDS[backend]
    n_extra = bk_cls.n_extra
    make_wave = bk_cls.shard_wave_factory(backend_static, npp)
    if frontier_cap:
        # frontier-compacted sparse waves (DESIGN.md §12.4): compact this
        # partition's live-offer edges inside the wave body; the backend's
        # own dense wave is the in-cond fallback, so every epoch below is
        # unchanged — delta exchange already ships sparse offers
        make_wave = frontier_mod.wrap_shard_wave(make_wave, npp, frontier_cap)
    del_patch = bk_cls.shard_del_patch(backend_static, npp)
    del_mutated = bk_cls.del_mutated
    extra_specs = (v,) * n_extra

    def masked_write(arr, loc, val):
        """Scatter batch values into this shard's pool slice.  Foreign batch
        entries are routed to a sacrificial extra slot (index epp) instead of
        a masked in-range index — a masked write at a real index would race
        with a genuine write to the same slot."""
        pad = jnp.zeros((1,), arr.dtype)
        return jnp.concatenate([arr, pad]).at[loc].set(
            val.astype(arr.dtype))[:epp]

    def local_slots(gslot, my_p):
        mine = (gslot // epp) == my_p
        return jnp.where(mine, gslot - my_p * epp, epp)

    @jax.jit
    @partial(jax.shard_map, mesh=ds.mesh,
             in_specs=(v, v, e, e, e, e) + extra_specs + (r, r, r, r, r, r),
             out_specs=(v, v, e, e, e, e, r, r),
             check_vma=False)
    def add_epoch(dist, parent, esrc, edst, ew, eact, *rest):
        """patch pools + relax from the inserted tails, one fused epoch.
        Layout extras arrive already patched (staged before the epoch)."""
        extras = rest[:n_extra]
        gslot, bsrc, bdst, bw, racc, macc = rest[n_extra:]
        my_p = jnp.int32(ds._flat_index())
        row0 = my_p * npp
        loc = local_slots(gslot, my_p)
        esrc = masked_write(esrc, loc, bsrc)
        edst = masked_write(edst, loc, bdst)
        ew = masked_write(ew, loc, bw)
        eact = masked_write(eact, loc, jnp.ones_like(gslot, jnp.bool_))
        # Frontier = tails of the inserted edges (paper Listing 3); each
        # shard keeps its own window of the global bool frontier.
        in_r = (bsrc >= row0) & (bsrc < row0 + npp)
        fr = jnp.zeros((npp,), jnp.bool_).at[
            jnp.clip(bsrc - row0, 0, npp - 1)].max(in_r)
        wave = make_wave(esrc, edst, ew, eact, extras, my_p)
        dist, parent, rounds, msgs = ds._relax_body(dist, parent, fr, wave)
        return (dist, parent, esrc, edst, ew, eact,
                racc + rounds, macc + msgs)

    @jax.jit
    @partial(jax.shard_map, mesh=ds.mesh,
             in_specs=(v, v, e, e, e, e) + extra_specs + (r, r, r, r, r),
             out_specs=(v, v, e) + (v,) * len(del_mutated) + (r, r),
             check_vma=False)
    def del_epoch(dist, parent, esrc, edst, ew, eact, *rest):
        """seed from pre-deletion tree + deactivate + tombstone layout +
        invalidate + recompute, one fused epoch.  Stats mirror
        core/delete.DeleteStats exactly.  The backend's layout tombstone
        (``shard_del_patch``) runs in-epoch; the mutated layout arrays are
        returned after (dist, parent, eact)."""
        extras = list(rest[:n_extra])
        gslot, psrc, pdst, racc, macc = rest[n_extra:]
        my_p = jnp.int32(ds._flat_index())
        row0 = my_p * npp
        # Listing 4: only deletions of tree edges (parent[head]==tail)
        # seed invalidation — judged against the PRE-deletion tree.
        in_r = (pdst >= row0) & (pdst < row0 + npp)
        lds = jnp.clip(pdst - row0, 0, npp - 1)
        seed = jnp.zeros((npp,), jnp.bool_).at[lds].max(
            in_r & (parent[lds] == psrc))
        any_seed = jax.lax.psum(jnp.sum(seed.astype(jnp.int32)), ax) > 0
        # deactivate the deleted slots (dst stays in-range)
        loc = local_slots(gslot, my_p)
        eact = masked_write(eact, loc, jnp.zeros_like(gslot, jnp.bool_))
        # tombstone the backend layout (the recompute must not see the
        # deleted edges; the seed above reads only the parent forest)
        if del_patch is not None:
            new_vals = del_patch(tuple(extras), psrc, pdst, my_p)
            for i, val in zip(del_mutated, new_vals):
                extras[i] = val
        # --- invalidation over the parent forest
        if use_doubling:
            aff, inv_rounds = ds._invalidate_doubling(parent, seed)
        elif exchange == "delta":
            aff, inv_rounds = ds._invalidate_delta(parent, seed, row0)
        else:
            aff, inv_rounds = ds._invalidate_flood_dense(parent, seed)
        # never invalidate the source (parity with single-device engine)
        local_ids = row0 + jnp.arange(npp, dtype=jnp.int32)
        aff = aff & (local_ids != source_pad)
        affected = jax.lax.psum(jnp.sum(aff.astype(jnp.int32)), ax)
        dist = jnp.where(aff, INF, dist)
        parent = jnp.where(aff, NO_PARENT, parent)
        # --- recomputation (shared with the static delete epoch; the
        # distributed rendering of delete.invalidate_and_recompute), with
        # the backend's wave in place of the hardwired segment-min
        wave = make_wave(esrc, edst, ew, eact, tuple(extras), my_p)
        if exchange == "delta":
            dist, parent, rec_rounds, rec_msgs = ds._recompute_delta(
                dist, parent, aff, esrc, edst, eact, wave, row0)
        else:
            dist, parent, rec_rounds, rec_msgs = ds._recompute_pull_push(
                dist, parent, aff, wave)
        zero = jnp.int32(0)
        d_rounds = jnp.where(any_seed, inv_rounds + rec_rounds, zero)
        d_msgs = jnp.where(any_seed, rec_msgs, zero) + affected
        return (dist, parent, eact, *(extras[i] for i in del_mutated),
                racc + d_rounds, macc + d_msgs)

    if wave_schedule == "rounds":
        return add_epoch, del_epoch, None

    # ---------------------------------------- bucketed (lazy) epoch variants
    @jax.jit
    @partial(jax.shard_map, mesh=ds.mesh,
             in_specs=(v, e, e, e, e, v, r, r, r, r),
             out_specs=(e, e, e, e, v),
             check_vma=False)
    def add_epoch_lazy(dist, esrc, edst, ew, eact, push,
                       gslot, bsrc, bdst, bw):
        """Bucketed ADD: patch the pools + enqueue the inserted tails as
        push obligations (pruned to currently-reachable tails, the sharded
        ``buckets.enqueue_push``) — no relaxation until the drain."""
        my_p = jnp.int32(ds._flat_index())
        row0 = my_p * npp
        loc = local_slots(gslot, my_p)
        esrc = masked_write(esrc, loc, bsrc)
        edst = masked_write(edst, loc, bdst)
        ew = masked_write(ew, loc, bw)
        eact = masked_write(eact, loc, jnp.ones_like(gslot, jnp.bool_))
        in_r = (bsrc >= row0) & (bsrc < row0 + npp)
        fr = jnp.zeros((npp,), jnp.bool_).at[
            jnp.clip(bsrc - row0, 0, npp - 1)].max(in_r)
        push = push | (fr & jnp.isfinite(dist))
        return esrc, edst, ew, eact, push

    @jax.jit
    @partial(jax.shard_map, mesh=ds.mesh,
             in_specs=(v, v, e) + extra_specs + (v, v, r, r, r, r, r),
             out_specs=(v, v, e) + (v,) * len(del_mutated) + (v, v, r, r),
             check_vma=False)
    def del_epoch_lazy(dist, parent, eact, *rest):
        """Bucketed DEL: seed + deactivate + tombstone + invalidate — the
        immediate work the witness-invariant argument requires — with the
        recompute deferred into (push, pull).  The sharded rendering of
        ``buckets.lazy_delete``; stats mirror its DeleteStats exactly."""
        extras = list(rest[:n_extra])
        push, pull, gslot, psrc, pdst, racc, macc = rest[n_extra:]
        my_p = jnp.int32(ds._flat_index())
        row0 = my_p * npp
        in_r = (pdst >= row0) & (pdst < row0 + npp)
        lds = jnp.clip(pdst - row0, 0, npp - 1)
        seed = jnp.zeros((npp,), jnp.bool_).at[lds].max(
            in_r & (parent[lds] == psrc))
        any_seed = jax.lax.psum(jnp.sum(seed.astype(jnp.int32)), ax) > 0
        loc = local_slots(gslot, my_p)
        eact = masked_write(eact, loc, jnp.zeros_like(gslot, jnp.bool_))
        if del_patch is not None:
            new_vals = del_patch(tuple(extras), psrc, pdst, my_p)
            for i, val in zip(del_mutated, new_vals):
                extras[i] = val
        if use_doubling:
            aff, inv_rounds = ds._invalidate_doubling(parent, seed,
                                                      gate=any_seed)
        elif exchange == "delta":
            aff, inv_rounds = ds._invalidate_delta(parent, seed, row0,
                                                   gate=any_seed)
        else:
            aff, inv_rounds = ds._invalidate_flood_dense(parent, seed,
                                                         gate=any_seed)
        local_ids = row0 + jnp.arange(npp, dtype=jnp.int32)
        aff = aff & (local_ids != source_pad)
        affected = jax.lax.psum(jnp.sum(aff.astype(jnp.int32)), ax)
        dist = jnp.where(aff, INF, dist)
        parent = jnp.where(aff, NO_PARENT, parent)
        # invalidated vertices stop offering; they re-enter via the drain
        push = push & jnp.isfinite(dist)
        pull = pull | aff
        d_rounds = jnp.where(any_seed, inv_rounds, jnp.int32(0))
        return (dist, parent, eact, *(extras[i] for i in del_mutated),
                push, pull, racc + d_rounds, macc + affected)

    @jax.jit
    @partial(jax.shard_map, mesh=ds.mesh,
             in_specs=(v, v, e, e, e, e) + extra_specs + (v, v, r, r),
             out_specs=(v, v, r, r),
             check_vma=False)
    def drain_epoch(dist, parent, esrc, edst, ew, eact, *rest):
        """Settle the pending masks bucket-by-bucket with the backend's
        wave; the caller resets (push, pull) to zeros afterwards."""
        extras = rest[:n_extra]
        push, pull, racc, macc = rest[n_extra:]
        my_p = jnp.int32(ds._flat_index())
        row0 = my_p * npp
        wave = make_wave(esrc, edst, ew, eact, extras, my_p)
        dist, parent, rounds, msgs = ds._drain_body(
            dist, parent, push, pull, wave, row0, bucket_width)
        return dist, parent, racc + rounds, macc + msgs

    return add_epoch_lazy, del_epoch_lazy, drain_epoch


def _build_epochs_ms(ds: DistributedSSSP, epp: int, use_doubling: bool,
                     sources_pad: tuple[int, ...], backend: str,
                     backend_static: tuple,
                     wave_schedule: str = "rounds", bucket_width: float = 1.0,
                     frontier_cap: int = 0):
    """Batched multi-source rendering of ``_build_epochs`` (DESIGN.md §8):
    the (add_epoch, del_epoch, drain_epoch) triple for S stacked trees over
    one shared sharded pool + layout.

    Same contract as the single-source builder — module-level, closures
    capture only static config — plus the serving-mode shape rules: vertex
    state is [S, npp] per shard (``ds.vspec_ms``), per-source stat counters
    are replicated [S] vectors, the pool/layout patches run ONCE (shared
    graph), and each lane's relax/invalidate/recompute is the ``*_ms`` body
    with the backend's pure shard-local wave vmapped over the source axis.
    Per lane the results are bit-identical to the single-source epochs for
    that lane's source (tests/test_serving.py).
    """
    npp = ds.npp
    ax = ds.cfg.mesh_axes
    exchange = ds.cfg.exchange
    S = len(sources_pad)
    v, vb, e, r = ds.vspec, ds.vspec_ms, ds.espec, ds.rspec
    bk_cls = SHARDED_BACKENDS[backend]
    n_extra = bk_cls.n_extra
    make_wave = bk_cls.shard_wave_factory(backend_static, npp)
    if frontier_cap:
        # per-lane sparse waves under vmap lower the cond to select (both
        # branches execute) — correctness-grade, same §12.3 batched caveat
        make_wave = frontier_mod.wrap_shard_wave(make_wave, npp, frontier_cap)
    del_patch = bk_cls.shard_del_patch(backend_static, npp)
    del_mutated = bk_cls.del_mutated
    extra_specs = (v,) * n_extra

    def masked_write(arr, loc, val):
        pad = jnp.zeros((1,), arr.dtype)
        return jnp.concatenate([arr, pad]).at[loc].set(
            val.astype(arr.dtype))[:epp]

    def local_slots(gslot, my_p):
        mine = (gslot // epp) == my_p
        return jnp.where(mine, gslot - my_p * epp, epp)

    @jax.jit
    @partial(jax.shard_map, mesh=ds.mesh,
             in_specs=(vb, vb, e, e, e, e) + extra_specs + (r, r, r, r, r, r),
             out_specs=(vb, vb, e, e, e, e, r, r),
             check_vma=False)
    def add_epoch(dist, parent, esrc, edst, ew, eact, *rest):
        """One shared pool patch + the SAME insertion frontier broadcast to
        every lane (ADD tails are source-independent), then the batched
        relax body to per-lane fixpoints."""
        extras = rest[:n_extra]
        gslot, bsrc, bdst, bw, racc, macc = rest[n_extra:]
        my_p = jnp.int32(ds._flat_index())
        row0 = my_p * npp
        loc = local_slots(gslot, my_p)
        esrc = masked_write(esrc, loc, bsrc)
        edst = masked_write(edst, loc, bdst)
        ew = masked_write(ew, loc, bw)
        eact = masked_write(eact, loc, jnp.ones_like(gslot, jnp.bool_))
        in_r = (bsrc >= row0) & (bsrc < row0 + npp)
        fr = jnp.zeros((npp,), jnp.bool_).at[
            jnp.clip(bsrc - row0, 0, npp - 1)].max(in_r)
        fr_b = jnp.broadcast_to(fr, (S, npp))
        wave = make_wave(esrc, edst, ew, eact, extras, my_p)
        dist, parent, rounds, msgs = ds._relax_body_ms(
            dist, parent, fr_b, jax.vmap(wave))
        return (dist, parent, esrc, edst, ew, eact,
                racc + rounds, macc + msgs)

    @jax.jit
    @partial(jax.shard_map, mesh=ds.mesh,
             in_specs=(vb, vb, e, e, e, e) + extra_specs + (r, r, r, r, r),
             out_specs=(vb, vb, e) + (v,) * len(del_mutated) + (r, r),
             check_vma=False)
    def del_epoch(dist, parent, esrc, edst, ew, eact, *rest):
        """Per-lane seeds (a deletion is a tree edge per lane or not) +
        ONE shared deactivate/tombstone + per-lane invalidate/recompute.
        Stats mirror the single-source del epoch per lane, gated on each
        lane's own any_seed."""
        extras = list(rest[:n_extra])
        gslot, psrc, pdst, racc, macc = rest[n_extra:]
        my_p = jnp.int32(ds._flat_index())
        row0 = my_p * npp
        in_r = (pdst >= row0) & (pdst < row0 + npp)
        lds = jnp.clip(pdst - row0, 0, npp - 1)
        seed = jax.vmap(
            lambda par: jnp.zeros((npp,), jnp.bool_).at[lds].max(
                in_r & (par[lds] == psrc)))(parent)
        any_seed = jax.lax.psum(
            jnp.sum(seed.astype(jnp.int32), axis=1), ax) > 0        # [S]
        loc = local_slots(gslot, my_p)
        eact = masked_write(eact, loc, jnp.zeros_like(gslot, jnp.bool_))
        if del_patch is not None:
            new_vals = del_patch(tuple(extras), psrc, pdst, my_p)
            for i, val in zip(del_mutated, new_vals):
                extras[i] = val
        if use_doubling:
            aff, inv_rounds = ds._invalidate_doubling_ms(parent, seed)
        elif exchange == "delta":
            aff, inv_rounds = ds._invalidate_delta_ms(parent, seed, row0)
        else:
            aff, inv_rounds = ds._invalidate_flood_dense_ms(parent, seed)
        # never invalidate each lane's own source
        local_ids = row0 + jnp.arange(npp, dtype=jnp.int32)
        src_arr = jnp.asarray(sources_pad, jnp.int32)
        aff = aff & (local_ids[None, :] != src_arr[:, None])
        affected = jax.lax.psum(jnp.sum(aff.astype(jnp.int32), axis=1), ax)
        dist = jnp.where(aff, INF, dist)
        parent = jnp.where(aff, NO_PARENT, parent)
        wave = make_wave(esrc, edst, ew, eact, tuple(extras), my_p)
        wave_b = jax.vmap(wave)
        if exchange == "delta":
            dist, parent, rec_rounds, rec_msgs = ds._recompute_delta_ms(
                dist, parent, aff, esrc, edst, eact, wave_b, row0)
        else:
            dist, parent, rec_rounds, rec_msgs = ds._recompute_pull_push_ms(
                dist, parent, aff, wave_b)
        zero = jnp.zeros((S,), jnp.int32)
        d_rounds = jnp.where(any_seed, inv_rounds + rec_rounds, zero)
        d_msgs = jnp.where(any_seed, rec_msgs, zero) + affected
        return (dist, parent, eact, *(extras[i] for i in del_mutated),
                racc + d_rounds, macc + d_msgs)

    if wave_schedule == "rounds":
        return add_epoch, del_epoch, None

    # ---------------------------------------- bucketed (lazy) epoch variants
    @jax.jit
    @partial(jax.shard_map, mesh=ds.mesh,
             in_specs=(vb, e, e, e, e, vb, r, r, r, r),
             out_specs=(e, e, e, e, vb),
             check_vma=False)
    def add_epoch_lazy(dist, esrc, edst, ew, eact, push,
                       gslot, bsrc, bdst, bw):
        """Bucketed ADD: one shared pool patch + the shared tail frontier
        enqueued per lane, pruned to each lane's reachable tails."""
        my_p = jnp.int32(ds._flat_index())
        row0 = my_p * npp
        loc = local_slots(gslot, my_p)
        esrc = masked_write(esrc, loc, bsrc)
        edst = masked_write(edst, loc, bdst)
        ew = masked_write(ew, loc, bw)
        eact = masked_write(eact, loc, jnp.ones_like(gslot, jnp.bool_))
        in_r = (bsrc >= row0) & (bsrc < row0 + npp)
        fr = jnp.zeros((npp,), jnp.bool_).at[
            jnp.clip(bsrc - row0, 0, npp - 1)].max(in_r)
        push = push | (fr[None, :] & jnp.isfinite(dist))
        return esrc, edst, ew, eact, push

    @jax.jit
    @partial(jax.shard_map, mesh=ds.mesh,
             in_specs=(vb, vb, e) + extra_specs + (vb, vb, r, r, r, r, r),
             out_specs=(vb, vb, e) + (v,) * len(del_mutated) + (vb, vb, r, r),
             check_vma=False)
    def del_epoch_lazy(dist, parent, eact, *rest):
        """Bucketed DEL: per-lane seeds + ONE shared deactivate/tombstone +
        per-lane gated invalidation; recompute deferred into (push, pull)."""
        extras = list(rest[:n_extra])
        push, pull, gslot, psrc, pdst, racc, macc = rest[n_extra:]
        my_p = jnp.int32(ds._flat_index())
        row0 = my_p * npp
        in_r = (pdst >= row0) & (pdst < row0 + npp)
        lds = jnp.clip(pdst - row0, 0, npp - 1)
        seed = jax.vmap(
            lambda par: jnp.zeros((npp,), jnp.bool_).at[lds].max(
                in_r & (par[lds] == psrc)))(parent)
        any_seed = jax.lax.psum(
            jnp.sum(seed.astype(jnp.int32), axis=1), ax) > 0        # [S]
        loc = local_slots(gslot, my_p)
        eact = masked_write(eact, loc, jnp.zeros_like(gslot, jnp.bool_))
        if del_patch is not None:
            new_vals = del_patch(tuple(extras), psrc, pdst, my_p)
            for i, val in zip(del_mutated, new_vals):
                extras[i] = val
        if use_doubling:
            aff, inv_rounds = ds._invalidate_doubling_ms(parent, seed,
                                                         gate=any_seed)
        elif exchange == "delta":
            aff, inv_rounds = ds._invalidate_delta_ms(parent, seed, row0,
                                                      gate=any_seed)
        else:
            aff, inv_rounds = ds._invalidate_flood_dense_ms(parent, seed,
                                                            gate=any_seed)
        local_ids = row0 + jnp.arange(npp, dtype=jnp.int32)
        src_arr = jnp.asarray(sources_pad, jnp.int32)
        aff = aff & (local_ids[None, :] != src_arr[:, None])
        affected = jax.lax.psum(jnp.sum(aff.astype(jnp.int32), axis=1), ax)
        dist = jnp.where(aff, INF, dist)
        parent = jnp.where(aff, NO_PARENT, parent)
        push = push & jnp.isfinite(dist)
        pull = pull | aff
        zero = jnp.zeros((S,), jnp.int32)
        d_rounds = jnp.where(any_seed, inv_rounds, zero)
        return (dist, parent, eact, *(extras[i] for i in del_mutated),
                push, pull, racc + d_rounds, macc + affected)

    @jax.jit
    @partial(jax.shard_map, mesh=ds.mesh,
             in_specs=(vb, vb, e, e, e, e) + extra_specs + (vb, vb, r, r),
             out_specs=(vb, vb, r, r),
             check_vma=False)
    def drain_epoch(dist, parent, esrc, edst, ew, eact, *rest):
        """Batched drain: per-lane bucket pacing with the vmapped wave."""
        extras = rest[:n_extra]
        push, pull, racc, macc = rest[n_extra:]
        my_p = jnp.int32(ds._flat_index())
        row0 = my_p * npp
        wave = make_wave(esrc, edst, ew, eact, extras, my_p)
        dist, parent, rounds, msgs = ds._drain_body_ms(
            dist, parent, push, pull, jax.vmap(wave), row0, bucket_width)
        return dist, parent, racc + rounds, macc + msgs

    return add_epoch_lazy, del_epoch_lazy, drain_epoch
