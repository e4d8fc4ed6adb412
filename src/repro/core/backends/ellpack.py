"""Dense ELLPACK backend: incrementally maintained by-destination ELL block
(DESIGN.md §2), behind the RelaxBackend protocol (§7).

The segment backend scatter-reduces over the flat COO edge pool; this module
keeps a second, TPU-native view of the same graph and maintains it
*incrementally* under ADD/DEL batches:

  * ADD  — the host planner assigns each new edge a (row, k) cell past the
    row's fill high-water mark; the device patch is one idempotent scatter.
  * DEL  — resolved entirely on device: each deleted edge's cell is found by
    matching the source id in its destination row and tombstoned (w := +inf).
    No host map of ELL positions exists at all.
  * weight-decrease (``on_duplicate="min"``) — device-side match + min-scatter.
  * overflow — when a row's fill would exceed K, the planner rebuilds the
    whole block from the host COO mirror with K doubled (next pow2 of twice
    the max in-degree) and tombstones compacted away.  O(E) numpy + one
    transfer, amortized over the doublings.

All patch ops are jitted, tolerate pad_pow2-repeated rows (their scatters are
idempotent or min/max-combined), and never read device memory back.

Epoch functions mirror core/relax.py and core/delete.py exactly — same
frontier evolution, same smallest-src-id tie-break — so (dist, parent) are
bit-identical between the backends (test_backend_equiv.py).

Sharded participation (§7.2): ``ShardedEllpack`` holds one shard-local
planner per partition (each planning rows for its owned vertex window via
the planner's ``row0``) and the per-shard ELL blocks concatenated
partition-major into globally sharded device arrays; K is synchronized
across shards at rebuild time so the shard_map epochs see one static block
shape.

Batched multi-source serving (§8): the ELL block is source-independent —
one layout serves every lane.  The epochs vmap over the stacked [S, N]
dist/parent (base protocol ``relax_batched``/``delete_batched``; the
sharded engine vmaps the wave), with the block arrays captured unbatched.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import buckets
from repro.core import delete as del_mod
from repro.core import ingest
from repro.core.backends.base import (ELL_BLOWUP_RATIO, RelaxBackend,
                                      ShardedBackend, register,
                                      register_sharded, rank_within_rows)
from repro.core.relax import RelaxStats
from repro.core.state import INF, NO_PARENT, SSSPState
from repro.graphs import csr as csr_mod
from repro.kernels.relax import config as kernel_config
from repro.kernels.relax.ops import relax_wave

_NEG_INF = jnp.float32(-jnp.inf)
_next_pow2 = csr_mod.next_pow2


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EllState:
    """Device-resident dense-ELL view of the active edge set (one global K;
    the hub-aware sliced/hybrid variant lives in backends/sliced.py).

    ``fill`` is each row's occupancy high-water mark: cells at k >= fill[r]
    have never been written; cells below it are live edges or tombstones
    (w == +inf).  Rows n..R-1 are kernel block padding and stay empty.
    """

    nbr_idx: jax.Array  # i32[R, K] in-neighbor ids (0 where empty/tombstone)
    nbr_w: jax.Array    # f32[R, K] weights (+inf where empty/tombstone)
    fill: jax.Array     # i32[R]

    @property
    def k(self) -> int:
        return self.nbr_w.shape[1]

    @property
    def rows(self) -> int:
        return self.nbr_w.shape[0]


# --------------------------------------------------------------- patch ops --
@jax.jit
def ell_append(ell: EllState, rows: jax.Array, kpos: jax.Array,
               src: jax.Array, w: jax.Array) -> EllState:
    """Write fresh edges into planner-assigned cells (idempotent scatter —
    pad_pow2 repeats of the same (row, kpos, src, w) are no-ops)."""
    return EllState(
        nbr_idx=ell.nbr_idx.at[rows, kpos].set(src),
        nbr_w=ell.nbr_w.at[rows, kpos].set(w),
        fill=ell.fill.at[rows].max(kpos + 1),
    )


def _match_cell(ell: EllState, rows: jax.Array, src: jax.Array):
    """Locate each (src -> rows) edge's live cell: (kpos, found).

    Live edges are unique per (row, src) — the slot allocator dedups — so at
    most one finite-weight cell matches.
    """
    row_idx = ell.nbr_idx[rows]                      # (m, K)
    row_w = ell.nbr_w[rows]                          # (m, K)
    hit = (row_idx == src[:, None]) & jnp.isfinite(row_w)
    return jnp.argmax(hit, axis=1), jnp.any(hit, axis=1)


@jax.jit
def ell_delete(ell: EllState, rows: jax.Array, src: jax.Array) -> EllState:
    """Tombstone deleted edges (w := +inf), located on device by source-id
    match.  Duplicate (row, src) pairs from batch padding collapse to the
    same cell; the max-combine makes the scatter order-free."""
    kpos, found = _match_cell(ell, rows, src)
    val = jnp.where(found, INF, _NEG_INF)            # -inf = no-op under max
    return dataclasses.replace(
        ell, nbr_w=ell.nbr_w.at[rows, kpos].max(val))


@jax.jit
def ell_update_min(ell: EllState, rows: jax.Array, src: jax.Array,
                   w: jax.Array) -> EllState:
    """Weight-decrease of existing edges (on_duplicate="min"): device-side
    match + min-scatter (+inf = no-op for unmatched/padded entries)."""
    kpos, found = _match_cell(ell, rows, src)
    val = jnp.where(found, w, INF)
    return dataclasses.replace(
        ell, nbr_w=ell.nbr_w.at[rows, kpos].min(val))


@jax.jit
def ell_invariants(ell: EllState) -> dict[str, jax.Array]:
    """Occupancy invariants over the device fill marks (diagnostics/tests):
    every cell at or past a row's fill mark must be empty (+inf), and fill
    must stay within the block width.  Guards the device copy of the fill
    state against drifting from the host planner's."""
    k_iota = jax.lax.broadcasted_iota(jnp.int32, ell.nbr_w.shape, 1)
    beyond = k_iota >= ell.fill[:, None]
    return {
        "beyond_fill_empty": jnp.all(jnp.where(beyond, jnp.isinf(ell.nbr_w),
                                               True)),
        "fill_in_range": jnp.all((ell.fill >= 0)
                                 & (ell.fill <= ell.nbr_w.shape[1])),
    }


# ------------------------------------------------------------ host planner --
class EllPlanner:
    """Host control plane for the ELL block: assigns append cells, detects
    overflow, and rebuilds (with capacity doubling) from the host COO mirror.

    Keeps only dense per-row fill counts — deletions and weight updates are
    resolved on device, so there is no host map of ELL cell positions.

    ``row0`` makes the planner window-local (DESIGN.md §7.2): it plans rows
    for the vertex window ``[row0, row0 + num_vertices)`` and accepts
    *global* destination ids everywhere — the sharded engine runs one
    planner per partition over its owned window.
    """

    def __init__(self, num_vertices: int, *, block_rows: int = 256,
                 init_k: int = 8, row0: int = 0):
        self.n = num_vertices
        self.row0 = row0
        bm = min(block_rows, _next_pow2(max(num_vertices, 1)))
        self.rows = -(-num_vertices // bm) * bm      # ceil to block multiple
        self.k = max(1, init_k)
        self.fill = np.zeros(self.rows, np.int32)
        self.rebuilds = 0
        self._warned_blowup = False

    def empty_state(self) -> EllState:
        idx, ww, fill = self.empty_host()
        return EllState(nbr_idx=jnp.asarray(idx), nbr_w=jnp.asarray(ww),
                        fill=jnp.asarray(fill))

    def empty_host(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        return (np.zeros((self.rows, self.k), np.int32),
                np.full((self.rows, self.k), INF, np.float32),
                np.zeros(self.rows, np.int32))

    def plan_appends(self, rows: np.ndarray) -> np.ndarray | None:
        """Assign a distinct cell past the fill mark to each fresh edge
        (``rows``: global dst ids within this planner's window).

        Returns kpos i32[m] (and advances the fill marks), or None when any
        row would overflow K — the caller must rebuild instead.
        """
        m = len(rows)
        if m == 0:
            return np.empty(0, np.int32)
        rows = np.asarray(rows, np.int64) - self.row0
        counts = np.bincount(rows, minlength=self.n)
        if int((self.fill[:self.n] + counts[:self.n]).max(initial=0)) > self.k:
            return None
        kpos = self.fill[rows] + rank_within_rows(rows)
        np.maximum.at(self.fill, rows, kpos + 1)
        return kpos.astype(np.int32)

    def required_k(self, dst: np.ndarray) -> int:
        """The K this planner's doubling policy wants for a live edge set
        (global dst ids) — used by the sharded coordinator to synchronize K
        across partitions before a coupled rebuild."""
        deg = self._local_deg(dst)
        return max(self.k, _next_pow2(max(2 * int(deg.max(initial=0)), 1)))

    def _local_deg(self, dst: np.ndarray) -> np.ndarray:
        if not len(dst):
            return np.zeros(self.n, np.int64)
        return np.bincount(np.asarray(dst, np.int64) - self.row0,
                           minlength=self.n)

    def rebuild_host(self, src: np.ndarray, dst: np.ndarray, w: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Numpy half of ``rebuild`` — the sharded coordinator concatenates
        these blocks partition-major before one sharded transfer."""
        self.k = self.required_k(dst)
        cells, live = self.rows * self.k, len(dst)
        if (live and cells > ELL_BLOWUP_RATIO * live
                and not self._warned_blowup):
            # The power-law-hub pathology (DESIGN.md §6): a few hub rows set
            # the global K and the dense block is mostly +inf padding.
            warnings.warn(
                f"dense-ELL rebuild allocates {cells} cells (K={self.k} x "
                f"{self.rows} rows) for {live} live edges — more than "
                f"{ELL_BLOWUP_RATIO}x blowup; the hub-aware "
                f"relax_backend='sliced' layout (or relax_backend='auto') "
                f"avoids this", RuntimeWarning, stacklevel=3)
            self._warned_blowup = True
        idx, ww, fill = csr_mod.ell_from_coo(
            self.n, src, dst, w, k=self.k, n_rows=self.rows, row0=self.row0)
        self.fill = fill
        self.rebuilds += 1
        return idx, ww, fill

    def rebuild(self, src: np.ndarray, dst: np.ndarray, w: np.ndarray
                ) -> EllState:
        """Rebuild the device block from the live COO edge set (host mirror):
        compacts tombstones and doubles K to the next pow2 of 2x the max
        in-degree when the degree itself (not churn) caused the overflow."""
        idx, ww, fill = self.rebuild_host(src, dst, w)
        return EllState(nbr_idx=jnp.asarray(idx), nbr_w=jnp.asarray(ww),
                        fill=jnp.asarray(fill))


# ------------------------------------------------------------------ epochs --
@partial(jax.jit, static_argnames=("num_vertices", "max_rounds",
                                   "use_kernel", "interpret"))
def ell_relax_until_converged(
    sssp: SSSPState,
    nbr_idx: jax.Array,
    nbr_w: jax.Array,
    frontier: jax.Array,
    *,
    num_vertices: int,
    max_rounds: int = 0,
    use_kernel: bool = False,
    interpret: bool = True,
) -> tuple[SSSPState, RelaxStats]:
    """ELL rendering of relax.relax_until_converged: frontier-masked waves to
    fixpoint.  Same candidate sets, same tie-break => bit-identical results."""

    def cond(carry):
        _, _, frontier, rounds, _ = carry
        go = jnp.any(frontier)
        if max_rounds:
            go = go & (rounds < max_rounds)
        return go

    def body(carry):
        dist, parent, frontier, rounds, msgs = carry
        dist, parent, improved = relax_wave(
            dist, parent, nbr_idx, nbr_w, frontier=frontier,
            use_kernel=use_kernel, interpret=interpret)
        return (dist, parent, improved, rounds + 1,
                msgs + jnp.sum(improved.astype(jnp.int32)))

    dist, parent, _, rounds, msgs = jax.lax.while_loop(
        cond, body,
        (sssp.dist, sssp.parent, frontier, jnp.int32(0), jnp.int32(0)),
    )
    return (
        SSSPState(dist=dist, parent=parent, source=sssp.source),
        RelaxStats(rounds=rounds, messages=msgs),
    )


@partial(jax.jit, static_argnames=("num_vertices", "use_kernel",
                                   "interpret"))
def ell_relax_batched(sssp, nbr_idx, nbr_w, frontier, *, num_vertices: int,
                      use_kernel: bool = False, interpret: bool = True):
    """Batched multi-source rendering (DESIGN.md §8): jit(vmap(epoch)) over
    the [S, N] tree stack, the shared ELL block captured unbatched."""
    return jax.vmap(
        lambda s: ell_relax_until_converged(
            s, nbr_idx, nbr_w, frontier, num_vertices=num_vertices,
            use_kernel=use_kernel, interpret=interpret))(sssp)


@partial(jax.jit, static_argnames=("num_vertices", "use_doubling",
                                   "use_kernel", "interpret"))
def ell_delete_batched(sssp, nbr_idx, nbr_w, seed, *, num_vertices: int,
                       use_doubling: bool = True, use_kernel: bool = False,
                       interpret: bool = True):
    """Batched deletion epoch: per-lane [S, N] seeds over the shared block."""
    return jax.vmap(
        lambda s, sd: ell_invalidate_and_recompute(
            s, nbr_idx, nbr_w, sd, num_vertices=num_vertices,
            use_doubling=use_doubling, use_kernel=use_kernel,
            interpret=interpret))(sssp, seed)


@partial(jax.jit, static_argnames=("num_vertices", "use_doubling",
                                   "use_kernel", "interpret"))
def ell_invalidate_and_recompute(
    sssp: SSSPState,
    nbr_idx: jax.Array,
    nbr_w: jax.Array,
    seed: jax.Array,
    *,
    num_vertices: int,
    use_doubling: bool = True,
    use_kernel: bool = False,
    interpret: bool = True,
) -> tuple[SSSPState, del_mod.DeleteStats]:
    """Deletion epoch on the ELL block (paper Listings 4/8/9).

    Invalidation reuses the parent-forest marking from core/delete.py (it
    does not touch edges).  The bulk DistanceQuery pull is ONE ELL wave: every
    affected row gathers offers from all in-neighbors at once (+inf sources —
    other affected vertices — and tombstones offer nothing), then ordinary
    frontier-masked waves drain the epoch.

    Safe to call with an all-false seed (non-tree deletions): the state is
    returned unchanged and every stat is 0, which lets the engine skip the
    blocking ``bool(jnp.any(seed))`` host sync entirely (DESIGN.md §2.4).
    """
    any_seed = jnp.any(seed)
    mark = (del_mod.mark_subtree_doubling if use_doubling
            else del_mod.mark_subtree_flood)
    aff, inv_rounds = mark(sssp.parent, seed)
    aff = aff.at[sssp.source].set(False)

    dist = jnp.where(aff, INF, sssp.dist)
    parent = jnp.where(aff, NO_PARENT, sssp.parent)

    # Bulk pull: one unmasked wave, improvements applied to affected rows
    # only (matching the segment path's ``aff[dst]`` edge mask; unaffected
    # rows cannot improve anyway — the pre-deletion state was converged).
    dist_p, parent_p, improved = relax_wave(
        dist, parent, nbr_idx, nbr_w,
        use_kernel=use_kernel, interpret=interpret)
    improved = improved & aff
    dist = jnp.where(improved, dist_p, dist)
    parent = jnp.where(improved, parent_p, parent)

    state1 = SSSPState(dist=dist, parent=parent, source=sssp.source)
    state2, stats = ell_relax_until_converged(
        state1, nbr_idx, nbr_w, improved, num_vertices=num_vertices,
        use_kernel=use_kernel, interpret=interpret)
    zero = jnp.int32(0)
    return state2, del_mod.DeleteStats(
        invalidation_rounds=jnp.where(any_seed, inv_rounds, zero),
        affected=jnp.sum(aff.astype(jnp.int32)),
        recompute_rounds=jnp.where(any_seed, stats.rounds + 1, zero),
        recompute_messages=jnp.where(
            any_seed,
            stats.messages + jnp.sum(improved.astype(jnp.int32)), zero),
    )


@partial(jax.jit, static_argnames=("num_vertices", "bucket_width",
                                   "use_kernel", "interpret"))
def ell_drain(sssp, nbr_idx, nbr_w, pend, *, num_vertices: int,
              bucket_width: float, use_kernel: bool = False,
              interpret: bool = True):
    """Bucketed drain on the ELL block (DESIGN.md §9): the pull is the same
    one-unmasked-wave-then-``improved &= aff`` pattern as the deletion epoch,
    so the drain's improved sets — hence its wave sequence and stats — stay
    bit-identical to the segment drain's."""

    def wave(dist, parent, active):
        return relax_wave(dist, parent, nbr_idx, nbr_w, frontier=active,
                          use_kernel=use_kernel, interpret=interpret)

    def pull_wave(dist, parent, aff):
        dist_p, parent_p, improved = relax_wave(
            dist, parent, nbr_idx, nbr_w,
            use_kernel=use_kernel, interpret=interpret)
        improved = improved & aff
        return (jnp.where(improved, dist_p, dist),
                jnp.where(improved, parent_p, parent), improved)

    dist, parent, stats = buckets.run_drain(
        sssp.dist, sssp.parent, pend, bucket_width=bucket_width,
        wave=wave, pull_wave=pull_wave)
    return (SSSPState(dist=dist, parent=parent, source=sssp.source),
            buckets.empty_pending(num_vertices), stats)


@partial(jax.jit, static_argnames=("num_vertices", "bucket_width",
                                   "use_kernel", "interpret"))
def ell_drain_batched(sssp, nbr_idx, nbr_w, pend, *, num_vertices: int,
                      bucket_width: float, use_kernel: bool = False,
                      interpret: bool = True):
    return jax.vmap(
        lambda s, pd: ell_drain(
            s, nbr_idx, nbr_w, pd, num_vertices=num_vertices,
            bucket_width=bucket_width, use_kernel=use_kernel,
            interpret=interpret))(sssp, pend)


# ----------------------------------------------------------------- backend --
@register
class EllpackBackend(RelaxBackend):
    """RelaxBackend over the dense ELL block: EllPlanner host control plane,
    jitted patch ops, ELL epoch waves, doubling rebuilds from the mirror."""

    name = "ellpack"

    def __init__(self, cfg, num_vertices, *, use_kernel=False, interpret=True):
        super().__init__(cfg, num_vertices, use_kernel=use_kernel,
                         interpret=interpret)
        self.planner = EllPlanner(
            num_vertices, block_rows=cfg.ell_block_rows,
            init_k=cfg.ell_init_k)
        self.state = self.planner.empty_state()
        self.blowup = False   # set by rebuilds; read by the "auto" fallback

    def apply_adds(self, plan, alloc):
        """Incremental ELL maintenance for one ADD batch (DESIGN.md §2.3).

        Fresh edges get planner-assigned cells (one idempotent device
        scatter); weight-decreases resolve their cell on device.  Overflow of
        any row's fill mark triggers a full rebuild from the host COO mirror
        — which already contains this batch, so no patch follows.
        """
        fresh = plan.fresh
        rows = plan.dst[fresh].astype(np.int64)
        kpos = self.planner.plan_appends(rows)
        if kpos is None:
            src, dst, w = alloc.active_coo()
            self.state = self.planner.rebuild(src, dst, w)
            # host-visible blowup flag for relax_backend="auto" fallback
            self.blowup = (self.planner.rows * self.planner.k
                           > ELL_BLOWUP_RATIO * max(len(dst), 1))
            return
        if len(rows):
            rows_p, kpos_p, src_p, w_p = ingest.pad_pow2(
                rows.astype(np.int32), kpos, plan.src[fresh], plan.w[fresh])
            self.state = ell_append(
                self.state, jnp.asarray(rows_p), jnp.asarray(kpos_p),
                jnp.asarray(src_p), jnp.asarray(w_p))
        if not fresh.all():
            upd = ~fresh
            rows_p, src_p, w_p = ingest.pad_pow2(
                plan.dst[upd], plan.src[upd], plan.w[upd])
            self.state = ell_update_min(
                self.state, jnp.asarray(rows_p), jnp.asarray(src_p),
                jnp.asarray(w_p))

    def apply_dels(self, rows, src):
        self.state = ell_delete(self.state, jnp.asarray(rows),
                                jnp.asarray(src))

    def relax(self, sssp, edges, frontier):
        return ell_relax_until_converged(
            sssp, self.state.nbr_idx, self.state.nbr_w, frontier,
            num_vertices=self.n, use_kernel=self.use_kernel,
            interpret=self.interpret)

    def delete(self, sssp, edges, seed):
        return ell_invalidate_and_recompute(
            sssp, self.state.nbr_idx, self.state.nbr_w, seed,
            num_vertices=self.n, use_doubling=self.cfg.use_doubling,
            use_kernel=self.use_kernel, interpret=self.interpret)

    def relax_batched(self, sssp, edges, frontier):
        return ell_relax_batched(
            sssp, self.state.nbr_idx, self.state.nbr_w, frontier,
            num_vertices=self.n, use_kernel=self.use_kernel,
            interpret=self.interpret)

    def delete_batched(self, sssp, edges, seed):
        return ell_delete_batched(
            sssp, self.state.nbr_idx, self.state.nbr_w, seed,
            num_vertices=self.n, use_doubling=self.cfg.use_doubling,
            use_kernel=self.use_kernel, interpret=self.interpret)

    def drain(self, sssp, edges, pend, *, bucket_width):
        return ell_drain(
            sssp, self.state.nbr_idx, self.state.nbr_w, pend,
            num_vertices=self.n, bucket_width=bucket_width,
            use_kernel=self.use_kernel, interpret=self.interpret)

    def drain_batched(self, sssp, edges, pend, *, bucket_width):
        return ell_drain_batched(
            sssp, self.state.nbr_idx, self.state.nbr_w, pend,
            num_vertices=self.n, bucket_width=bucket_width,
            use_kernel=self.use_kernel, interpret=self.interpret)

    def restore(self, alloc):
        self.planner = EllPlanner(
            self.n, block_rows=self.cfg.ell_block_rows,
            init_k=self.cfg.ell_init_k)
        self.state = self.planner.rebuild(*alloc.active_coo())

    def invariants(self):
        return ell_invariants(self.state)


# ----------------------------------------------------------- sharded side --
@register_sharded
class ShardedEllpack(ShardedBackend):
    """One shard-local EllPlanner per partition + the per-shard ELL blocks
    concatenated partition-major into globally sharded device arrays.

    Global addressing: vertex ``v`` (owner ``p = v // npp``) lives in ELL
    row ``p * rows_pp + (v % npp)`` — ``rows_pp`` is each shard's
    block-padded row count, identical across shards.  K is synchronized at
    rebuild time (max of the per-shard doubling policies) so shard_map sees
    one static block shape; any shard's overflow triggers a coupled rebuild
    of all shards from the per-partition mirrors.
    """

    name = "ellpack"
    n_extra = 2   # (nbr_idx, nbr_w) — what the wave reads

    def __init__(self, cfg, ds, allocs):
        super().__init__(cfg, ds, allocs)
        self.P, self.npp = ds.P, ds.npp
        self.use_kernel = bool(cfg.ell_use_kernel)
        self.interpret = kernel_config.default_interpret()
        self.planners = [
            EllPlanner(self.npp, block_rows=cfg.ell_block_rows,
                       init_k=cfg.ell_init_k, row0=p * self.npp)
            for p in range(self.P)]
        self.rows_pp = self.planners[0].rows
        self._sh = ds.vertex_sharding()   # dim-0 sharding, any rank
        self._put_blocks([pl.empty_host() for pl in self.planners])

    # ---- assembly
    def _put_blocks(self, blocks) -> None:
        idx = np.concatenate([b[0] for b in blocks])
        ww = np.concatenate([b[1] for b in blocks])
        fill = np.concatenate([b[2] for b in blocks])
        self.state = EllState(
            nbr_idx=jax.device_put(idx, self._sh),
            nbr_w=jax.device_put(ww, self._sh),
            fill=jax.device_put(fill, self._sh))

    def _pin(self) -> None:
        """Re-pin the patched arrays to the partition sharding (device-to-
        device, async — the ingest loop stays host-sync free).  On a P=1
        mesh any layout is trivially correctly sharded, so the per-batch
        device_put dispatches would be pure overhead — skip them."""
        if self.P == 1:
            return
        self.state = EllState(
            nbr_idx=jax.device_put(self.state.nbr_idx, self._sh),
            nbr_w=jax.device_put(self.state.nbr_w, self._sh),
            fill=jax.device_put(self.state.fill, self._sh))

    def _ellrows(self, p: int, dst: np.ndarray) -> np.ndarray:
        return (p * self.rows_pp
                + (np.asarray(dst, np.int64) - p * self.npp)).astype(np.int32)

    def arrays(self):
        return (self.state.nbr_idx, self.state.nbr_w)

    def static_key(self):
        return (self.name, self.use_kernel, self.interpret)

    # ---- patch staging
    def stage_adds(self, plans) -> None:
        app, upd = [], []
        for p, plan in plans:
            fresh = plan.fresh
            rows_v = plan.dst[fresh].astype(np.int64)
            kpos = self.planners[p].plan_appends(rows_v)
            if kpos is None:
                self._rebuild_all()   # mirrors already contain this batch
                return
            if len(rows_v):
                app.append((self._ellrows(p, rows_v), kpos,
                            plan.src[fresh], plan.w[fresh]))
            if not fresh.all():
                u = ~fresh
                upd.append((self._ellrows(p, plan.dst[u]),
                            plan.src[u], plan.w[u]))
        if app:
            rows, kpos, src, w = (np.concatenate(x) for x in zip(*app))
            rows, kpos, src, w = ingest.pad_pow2(rows, kpos, src, w)
            self.state = ell_append(
                self.state, jnp.asarray(rows), jnp.asarray(kpos),
                jnp.asarray(src), jnp.asarray(w))
        if upd:
            rows, src, w = (np.concatenate(x) for x in zip(*upd))
            rows, src, w = ingest.pad_pow2(rows, src, w)
            self.state = ell_update_min(
                self.state, jnp.asarray(rows), jnp.asarray(src),
                jnp.asarray(w))
        if app or upd:
            self._pin()

    def update_del_arrays(self, new_vals) -> None:
        (nbr_w,) = new_vals
        self.state = dataclasses.replace(self.state, nbr_w=nbr_w)

    # ---- coupled rebuild / restore
    def _rebuild_all(self) -> None:
        k = max(pl.required_k(alloc.active_coo()[1])
                for pl, alloc in zip(self.planners, self.allocs))
        for pl in self.planners:
            pl.k = k
        self._put_blocks([pl.rebuild_host(*alloc.active_coo())
                          for pl, alloc in zip(self.planners, self.allocs)])

    def restore(self) -> None:
        self.planners = [
            EllPlanner(self.npp, block_rows=self.cfg.ell_block_rows,
                       init_k=self.cfg.ell_init_k, row0=p * self.npp)
            for p in range(self.P)]
        self._rebuild_all()

    # ---- wave / in-epoch DEL patch
    @classmethod
    def shard_wave_factory(cls, static, npp):
        _, use_kernel, interpret = static
        from repro.kernels.relax.ref import ellpack_relax_ref
        from repro.kernels.relax.relax import ellpack_relax

        def make_wave(esrc, edst, ew, eact, extras, my_p):
            nbr_idx, nbr_w = extras

            def wave(offers):
                if use_kernel:
                    best, arg = ellpack_relax(offers, nbr_idx, nbr_w,
                                              interpret=interpret)
                else:
                    best, arg = ellpack_relax_ref(offers, nbr_idx, nbr_w)
                return best[:npp], arg[:npp]

            return wave

        return make_wave

    del_mutated = (1,)   # nbr_w

    @classmethod
    def shard_del_patch(cls, static, npp):
        def patch(extras, psrc, pdst, my_p):
            """Tombstone deleted edges in this shard's ELL block: local
            src-id match (the in-epoch rendering of ``ell_delete``), with
            foreign/unmatched entries no-ops under the -inf/max combine."""
            nbr_idx, nbr_w = extras
            lrow = pdst - my_p * npp
            in_r = (lrow >= 0) & (lrow < npp)
            rows = jnp.clip(lrow, 0, nbr_idx.shape[0] - 1)
            row_idx = nbr_idx[rows]                   # (m, K)
            row_w = nbr_w[rows]
            hit = (in_r[:, None] & (row_idx == psrc[:, None])
                   & jnp.isfinite(row_w))
            kpos = jnp.argmax(hit, axis=1)
            found = jnp.any(hit, axis=1)
            val = jnp.where(found, INF, _NEG_INF)
            return (nbr_w.at[rows, kpos].max(val),)

        return patch
