"""Frontier-compacted waves — pay for the affected region, not the graph
(DESIGN.md §12).

A dense wave dispatches over all N vertices and all E edge slots with a
boolean [N] frontier mask gating the gather, so a 3-edge ADD on an N=1M
graph pays cold-recompute cost per wave.  This module holds the compacted
path, which the single-device engine takes by default on one source under
the rounds schedule with the segment backend (``backends.ladder_route``;
``frontier_mode="dense"`` keeps the reference, ``"sparse"`` forces it):

  * ``compact_mask`` — device-side cumsum-scan compaction of the [N]
    frontier/pending mask into a bounded [F] ascending, -1-padded
    active-vertex worklist (plus the exact occupancy count);
  * a **capacity ladder** — the wave compacts once at the largest rung and
    dispatches the smallest rung whose vertex count AND edge budgets fit
    via nested ``lax.cond``; when occupancy exceeds every rung the final
    branch IS the dense ``relax.relax_round`` computation over the edge
    pool, so the path is jit-stable and correct at any occupancy;
  * gather-style waves that touch only the OUT-adjacency rows of worklist
    vertices.  All backend layouts are dst-keyed (in-adjacency), so the
    compacted path maintains one backend-independent OUT-adjacency
    *sidecar* (``OutAdjacency``): a ``SlicedEllPlanner`` with the src/dst
    roles swapped — rows are edge *sources*, cells hold destinations, and
    high-out-degree hubs spill to an overflow lane, read only by waves
    whose worklist owns spilled entries;
  * the ADD epoch's first wave over the inserted edges alone
    (``seeded_relax``), and ladder renderings of the delete epoch and the
    bucketed drain plus vmapped [S, N] batched variants, each mirroring its
    dense twin's loop carry and stat gating exactly; and
  * ``wrap_shard_wave`` for the sharded engines: per-partition *edge*
    worklists compacted inside the wave body from
    ``eact & isfinite(offers[esrc])`` (the delta exchange already ships
    sparse offers, so only the wave body changes), with the exact dense
    shard wave as the in-``cond`` fallback.

Why this is bit-identical to the dense path (the repo's standard contract):
a wave's result is determined by its candidate multiset ``{(dist[src]+w,
src, dst)}`` plus the smallest-src-id tie rule.  The sparse wave's
candidates are exactly the live out-edges of frontier vertices — the same
set the dense wave's ``active & frontier[src]`` mask selects — and exact
float min is evaluation-order-free, so (dist, parent) match bit-for-bit.
The sparse loops keep the same [N] mask in their carry as the dense loops
(only each wave's *execution* is compacted), so (rounds, messages) match
trivially.  Correctness is therefore rung-independent: the ladder is purely
a cost policy.

Cost model: one compacted wave is O(N) cheap elementwise work for the
compaction scans plus O(edge budget) for the gathers AND the scatter-min —
the wave binary-searches its rung's edge budget over the worklist's cell
cumsum, so no F x max-width padding is ever materialized and the scatter
volume tracks the edges actually touched; a worklist that owns spilled
hub entries adds an O(C) scan of the overflow lane.  The dense wave pays
O(N + E) gathers and scatter-mins (on a TPU v5e about 0.12-0.15 s per
[E]-wide pass at 2^24 slots) — the gap is the win the paper's
small-affected-region premise promises.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import buckets
from repro.core import delete as del_mod
from repro.core import relax
from repro.core.backends.sliced import SlicedEllPlanner
from repro.core.relax import RelaxStats
from repro.core.state import INF, NO_PARENT, EdgePool, SSSPState
from repro.graphs import csr as csr_mod
from repro.kernels.relax.gather import (gathered_rows_relax,
                                        gathered_rows_relax_ref)

_INT_MAX = jnp.int32(2**31 - 1)


# ------------------------------------------------------ compaction primitive --
def prefix_sum(x: jax.Array, block: int = 1024) -> jax.Array:
    """Inclusive prefix sum of an i32[n] vector by levels: within
    ``block``-wide rows, then over the row totals.  The same integers as
    ``jnp.cumsum``, which XLA:TPU lowers to one n-wide reduce-window that
    takes 15 s to compile for a v5e at n = 2^19, where this takes under
    one."""
    n = x.shape[0]
    if n <= block:
        return jnp.cumsum(x)
    rows = -(-n // block)
    inner = jnp.cumsum(jnp.pad(x, (0, rows * block - n)).reshape(rows, block),
                       axis=1)
    tot = inner[:, -1]
    return (inner + (prefix_sum(tot, block) - tot)[:, None]).reshape(-1)[:n]


@partial(jax.jit, static_argnames=("cap",))
def compact_mask(mask: jax.Array, *, cap: int
                 ) -> tuple[jax.Array, jax.Array]:
    """Compact a bool[N] mask into an ascending i32[cap] vertex worklist.

    Cumsum-scan compaction, gather-flavoured: the i-th set vertex
    (1-based) is the first index whose inclusive prefix count reaches i,
    recovered by a vectorized binary search of ``cap`` slot numbers over
    the [N] cumsum — O(N) elementwise work plus O(cap log N) searches, and
    crucially NO [N]-element scatter (XLA:CPU scatters cost ~100ns/elem,
    which would dwarf every other per-wave cost).  Returns (worklist,
    count) where the worklist is -1-padded and ``count`` is the EXACT
    occupancy ``sum(mask)`` — when ``count > cap`` the worklist is
    truncated and the caller must fall back dense (the capacity ladder's
    job)."""
    cs = prefix_sum(mask.astype(jnp.int32))
    count = cs[-1]
    slots = jnp.arange(1, cap + 1, dtype=jnp.int32)
    wl = jnp.searchsorted(cs, slots, side="left").astype(jnp.int32)
    return jnp.where(slots <= count, wl, -1), count


def worklist_to_mask(wl: jax.Array, num_vertices: int) -> jax.Array:
    """Inverse of ``compact_mask`` for in-capacity masks: -1 padding is
    ignored (the round-trip property the tests pin)."""
    safe = jnp.clip(wl, 0, num_vertices - 1)
    return jnp.zeros((num_vertices,), jnp.bool_).at[safe].max(wl >= 0)


def capacity_ladder(num_vertices: int, cap: int = 0) -> tuple[int, ...]:
    """Worklist capacity rungs (ascending).  ``cap=0`` derives the top rung
    as N/64 (>= 256, pow2-rounded); a small first rung keeps the common
    few-vertex waves cheap while the top rung absorbs moderate cascades
    before the dense fallback."""
    if cap <= 0:
        cap = max(256, csr_mod.next_pow2(max(num_vertices, 1)) // 64)
    cap = min(csr_mod.next_pow2(cap), csr_mod.next_pow2(max(num_vertices, 1)))
    low = max(256, cap // 16)
    return (low, cap) if low < cap else (cap,)


def edge_budget(cap: int) -> int:
    """Per-rung edge/overflow capacity: 8 out-edges per worklist slot.  A
    rung is taken only when the frontier's vertex count, its total ELL
    cells AND its live hub-overflow entries all fit (``ladder_wave``), so
    the budget bounds the wave's scatter volume — the dominant cost on
    XLA:CPU — while dense-degree frontiers simply escalate a rung."""
    return 8 * cap


# ------------------------------------------------------ OUT-adjacency sidecar --
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OutState:
    """Device half of the OUT-adjacency sidecar: rows are edge SOURCES.

    Row r's cells are ``[base[r], base[r] + fill[r])`` of the flat buffer
    (``fill`` is a high-water mark, tombstones included); a row past the hub
    threshold keeps its surplus out-edges in the overflow lane, whose
    entries name their source row in ``orow``.  Empty and tombstoned
    cells and entries carry w=+inf and never win a min.  ``ocount`` counts
    each row's live overflow entries, so a wave learns from its worklist
    alone whether it must read the overflow lane at all."""

    nbr: jax.Array     # i32[L] destination ids (0 where empty)
    w: jax.Array       # f32[L] weights (+inf where empty or tombstoned)
    fill: jax.Array    # i32[R]
    base: jax.Array    # i32[R] flat offset of each row's first cell
    onbr: jax.Array    # i32[C] overflow destination ids
    orow: jax.Array    # i32[C] overflow source rows
    ow: jax.Array      # f32[C] overflow weights (+inf empty or tombstoned)
    ocount: jax.Array  # i32[R] live overflow entries per source row


@jax.jit
def out_write(st: OutState, cols: jax.Array, w: jax.Array) -> OutState:
    """Write one ADD batch at host-assigned positions.  ``cols`` stacks
    (at, row, nbr, spill): ``at`` is a flat cell below ``L`` or ``L +
    entry`` of the overflow lane, and padding slots carry ``L + C``, which
    both scatters drop.  A weight decrease of a live edge rewrites its own
    position; ``spill`` marks the fresh overflow entries, the only ones
    that count into ``ocount``."""
    at, row, nbr, spill = cols[0], cols[1], cols[2], cols[3] > 0
    L, C, R = st.w.shape[0], st.ow.shape[0], st.fill.shape[0]
    ell = at < L
    cell = jnp.where(ell, at, L)
    ent = jnp.where(ell, C, at - L)
    r = jnp.clip(row, 0, R - 1)
    return OutState(
        nbr=st.nbr.at[cell].set(nbr, mode="drop"),
        w=st.w.at[cell].set(w, mode="drop"),
        fill=st.fill.at[jnp.where(ell, r, R)].max(at - st.base[r] + 1,
                                                  mode="drop"),
        base=st.base,
        onbr=st.onbr.at[ent].set(nbr, mode="drop"),
        orow=st.orow.at[ent].set(row, mode="drop"),
        ow=st.ow.at[ent].set(w, mode="drop"),
        ocount=st.ocount.at[jnp.where(spill, r, R)].add(1, mode="drop"))


@jax.jit
def out_tombstone(st: OutState, cols: jax.Array) -> OutState:
    """Tombstone deleted edges (w := +inf) at their host-kept positions:
    O(batch) scatters, no search.  ``cols`` stacks (at, row, spill);
    ``spill`` marks the overflow entries, which leave their row's
    ``ocount``; padding slots carry ``L + C``."""
    at, row, spill = cols[0], cols[1], cols[2] > 0
    L, C, R = st.w.shape[0], st.ow.shape[0], st.fill.shape[0]
    ell = at < L
    return dataclasses.replace(
        st,
        w=st.w.at[jnp.where(ell, at, L)].set(INF, mode="drop"),
        ow=st.ow.at[jnp.where(ell, C, at - L)].set(INF, mode="drop"),
        ocount=st.ocount.at[jnp.where(spill, row, R)].add(-1, mode="drop"))


def _padded(cols, fills, dtype=np.int32) -> np.ndarray:
    """Stack same-length columns into one [k, m] array, each padded to the
    batch's next power of two with its own fill value (not a repeat: the
    sidecar's counts must not see a slot twice), so each batch size
    compiles its patch op once and uploads one array."""
    n = len(cols[0])
    out = np.empty((len(cols), csr_mod.next_pow2(max(n, 1))), dtype)
    for row, col, fill in zip(out, cols, fills):
        row[:n] = col
        row[n:] = fill
    return out


class OutAdjacency:
    """Backend-independent OUT-adjacency sidecar for the compacted waves.

    A ``SlicedEllPlanner`` with the roles swapped: planner *rows* are edge
    SOURCES and the cells hold destination ids, so gathering a worklist
    vertex's row yields its out-neighbors; high-out-degree hubs spill to
    the overflow lane.  The host keeps, per pool slot, the position the
    planner gave its edge (``at``: a flat cell, or ``cells + entry``), so a
    deletion tombstones its cell or entry directly — O(batch), however
    large the overflow lane.  The sidecar is a derived view, built from
    the allocator's host mirror on its first batch and rebuilt when a row
    outgrows its width or the lane fills, and on restore (never
    serialized)."""

    # Per-row slices, a width floor and a high hub threshold.  A row's
    # width is the next pow2 of twice its degree at the last rebuild (at
    # least ``init_k``) and tombstoned cells are not reused until the next
    # rebuild, so the floor is what absorbs sliding-window churn on rows
    # that were empty or nearly so at the load: 4 kept a GAP-kron scale-19
    # stream of 9 windows of 8,192 ADD arcs from rebuilding (31.1M cells
    # against 30.6M at 2).  Every compacted wave whose worklist owns spilled
    # entries scans the whole overflow lane, so the threshold keeps spill
    # to the few real hubs.
    def __init__(self, num_vertices: int, capacity: int, *,
                 slice_rows: int = 1, hub_k: int = 1024, init_k: int = 4):
        self.n = num_vertices
        self._knobs = dict(slice_rows=slice_rows, hub_k=hub_k, init_k=init_k)
        self.planner = SlicedEllPlanner(num_vertices, **self._knobs)
        self.at = np.full(capacity, -1, np.int32)
        self._put(self.planner.empty_host(),
                  np.zeros(self.planner.rows, np.int32))

    def _put(self, blocks, ocount: np.ndarray) -> None:
        fi, fw, fill, onbr, orow, ow = blocks
        self.state = OutState(
            nbr=jnp.asarray(fi), w=jnp.asarray(fw), fill=jnp.asarray(fill),
            base=jnp.asarray(self.planner.base, jnp.int32),
            onbr=jnp.asarray(onbr), orow=jnp.asarray(orow),
            ow=jnp.asarray(ow), ocount=jnp.asarray(ocount, jnp.int32))

    def rebuild(self, alloc) -> None:
        """Rebuild from the allocator's mirror (which already holds the
        batch that triggered it): widths grow, tombstones compact away, and
        every live slot gets its new position."""
        slots = np.flatnonzero(alloc.mactive)
        src, dst, w = alloc.active_coo()
        *blocks, at = self.planner.rebuild_host(dst, src, w,  # swapped roles
                                                positions=True)
        spilled = at >= self.planner.cells
        self.at.fill(-1)
        self.at[slots] = at
        self._put(blocks, np.bincount(src[spilled],
                                      minlength=self.planner.rows))

    def apply_adds(self, plan, alloc) -> None:
        fresh = plan.fresh
        # the first batch (a load) builds the sidecar whole: planning it as
        # appends to the empty rows would fail after a full pass (~5 s of
        # host for 15.5M edges)
        sp = None if self.planner.rebuilds == 0 else self.planner.plan_appends(
            plan.src[fresh].astype(np.int64), plan.dst[fresh], plan.w[fresh])
        if sp is None:
            self.rebuild(alloc)
            return
        self.at[plan.slots[fresh]] = sp.at
        at = self.at[plan.slots]      # weight decreases keep their position
        spill = fresh & (at >= self.planner.cells)
        L, C = self.planner.cells, self.planner.ocap
        self.state = out_write(
            self.state, jnp.asarray(_padded((at, plan.src, plan.dst, spill),
                                            (L + C, 0, 0, 0))),
            jnp.asarray(_padded((plan.w,), (np.inf,), np.float32)[0]))

    def apply_dels(self, slots: np.ndarray, src: np.ndarray) -> None:
        """Tombstone the deleted edges of pool ``slots`` (sources ``src``)."""
        at = self.at[slots]
        if (at < 0).any():
            raise RuntimeError("sidecar: a deleted slot has no position")
        self.at[slots] = -1
        L, C = self.planner.cells, self.planner.ocap
        self.state = out_tombstone(
            self.state, jnp.asarray(_padded((at, src, at >= L),
                                            (L + C, 0, 0))))

    def restore(self, alloc) -> None:
        self.planner = SlicedEllPlanner(self.n, **self._knobs)
        self.rebuild(alloc)


# ------------------------------------------------------------- sparse waves --
class WaveTally(NamedTuple):
    """Per-epoch route accounting of the ladder epochs (device scalars, or
    [S] vectors when batched): the compacted waves' summed frontier sizes
    (the ``frontier_occupancy`` obs counter), the seed waves over inserted
    edges, and the waves a rung ran compacted.  The epoch's other waves
    fell back dense."""
    occupancy: jax.Array
    seed: jax.Array
    sparse: jax.Array


def _relax_fn(use_kernel: bool, interpret: bool):
    return (partial(gathered_rows_relax, interpret=interpret) if use_kernel
            else gathered_rows_relax_ref)


def sparse_push_wave(dist: jax.Array, parent: jax.Array, frontier: jax.Array,
                     wl: jax.Array, ecs: jax.Array, ocnt: jax.Array,
                     st: OutState, *, ecap: int, num_vertices: int,
                     use_kernel: bool = False, interpret: bool = True
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One gathered-edges relaxation wave over the worklist's OUT rows.

    Edge-level compaction: each of the ``ecap`` edge slots binary-searches
    the worklist's inclusive cell cumsum ``ecs`` for its (row, cell)
    coordinate, so the candidate list covers exactly the worklist rows'
    occupied cells — no F x max-width padding.  Only when the worklist owns
    spilled entries (``ocnt > 0``) does the wave read the overflow lane:
    the entries whose source row is on the frontier compact the same way
    into another ``ecap`` slots.  The candidates are relaxed by the jnp
    reference or the Pallas gathered-edges kernel (kernels/relax/gather.py)
    — a single scatter-min + key scatter whose volume is O(edges touched),
    with the smallest-src-id rule falling out of the shared min over the
    union multiset.  The caller (``ladder_wave``) guarantees both budgets
    fit."""
    n = num_vertices
    c = wl.shape[0]
    valid = wl >= 0
    rows = jnp.clip(wl, 0, st.fill.shape[0] - 1)
    excl = ecs - jnp.where(valid, st.fill[rows], 0)   # exclusive prefix
    j = jnp.arange(ecap, dtype=jnp.int32)
    r = jnp.clip(jnp.searchsorted(ecs, j, side="right"),
                 0, c - 1).astype(jnp.int32)
    src = rows[r]
    pos = jnp.clip(st.base[src] + j - excl[r], 0, st.w.shape[0] - 1)
    cells = (src, st.nbr[pos], st.w[pos], j < ecs[-1])
    fn = _relax_fn(use_kernel, interpret)

    def ell_only(_):
        e_src, e_nbr, e_w, e_val = cells
        return fn(dist[e_src], e_src, e_nbr, e_w, e_val, num_rows=n)

    def with_overflow(_):
        olive = frontier[st.orow] & (st.ow < INF)
        ocs = prefix_sum(olive.astype(jnp.int32))
        oslots = jnp.arange(1, ecap + 1, dtype=jnp.int32)
        osel = jnp.clip(jnp.searchsorted(ocs, oslots, side="left"),
                        0, st.ow.shape[0] - 1)
        e_src, e_nbr, e_w, e_val = (
            jnp.concatenate([a, b]) for a, b in zip(
                cells, (st.orow[osel], st.onbr[osel], st.ow[osel],
                        oslots <= ocs[-1])))
        return fn(dist[e_src], e_src, e_nbr, e_w, e_val, num_rows=n)

    best, arg = jax.lax.cond(ocnt > 0, with_overflow, ell_only, 0)
    improved = best < dist
    return (jnp.where(improved, best, dist),
            jnp.where(improved, arg, parent), improved)


def ladder_wave(dist: jax.Array, parent: jax.Array, frontier: jax.Array,
                st: OutState, edges: EdgePool, *, caps: tuple[int, ...],
                num_vertices: int, use_kernel: bool = False,
                interpret: bool = True
                ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One wave through the capacity ladder: compact once at the top rung,
    dispatch the smallest rung whose vertex count, ELL cell total AND
    spilled-entry total (``ocount`` over the worklist) all fit its
    budgets, else the exact dense ``relax_round`` over the pool.  All
    branches are bit-identical, so the rung choice is purely a cost
    decision; a dense fallback pays only the O(N) compaction on top.
    Returns (dist, parent, improved, 1 if a rung ran else 0)."""
    wl, count = compact_mask(frontier, cap=caps[-1])
    valid = wl >= 0
    rows = jnp.clip(wl, 0, st.fill.shape[0] - 1)
    ecs = jnp.cumsum(jnp.where(valid, st.fill[rows], 0).astype(jnp.int32))
    ocnt = jnp.sum(jnp.where(valid, st.ocount[rows], 0))
    etotal = ecs[-1]

    def dense_branch(_):
        d, p, improved, _ = relax.relax_round(
            dist, parent, edges, frontier, num_vertices=num_vertices)
        return d, p, improved, jnp.int32(0)

    def build(levels):
        if not levels:
            return dense_branch
        c, rest = levels[0], levels[1:]
        eb = edge_budget(c)

        def rung(_):
            return (*sparse_push_wave(
                dist, parent, frontier, wl[:c], ecs[:c], ocnt, st, ecap=eb,
                num_vertices=num_vertices, use_kernel=use_kernel,
                interpret=interpret), jnp.int32(1))

        nxt = build(rest)
        fits = (count <= c) & (etotal <= eb) & (ocnt <= eb)
        return lambda op: jax.lax.cond(fits, rung, nxt, op)

    return build(list(caps))(0)


def _ladder_loop(dist, parent, frontier, st, edges, *, caps, num_vertices,
                 max_rounds=0, use_kernel=False, interpret=True):
    def wave(dist, parent, frontier):
        return ladder_wave(
            dist, parent, frontier, st, edges, caps=caps,
            num_vertices=num_vertices, use_kernel=use_kernel,
            interpret=interpret)

    return relax.converged_loop(dist, parent, frontier, wave,
                                max_rounds=max_rounds, track_occupancy=True)


# ------------------------------------------------------------ sparse epochs --
@partial(jax.jit, static_argnames=("num_vertices", "caps", "use_kernel",
                                   "interpret"))
def seeded_relax(sssp: SSSPState, edges: EdgePool, st: OutState,
                 src: jax.Array, dst: jax.Array, w: jax.Array, *,
                 num_vertices: int, caps: tuple[int, ...],
                 use_kernel: bool = False, interpret: bool = True
                 ) -> tuple[SSSPState, RelaxStats, WaveTally]:
    """The ADD epoch of the rounds schedule on the ladder route.

    Its first wave relaxes the inserted edges ``(src, dst, w)`` alone — a
    [B] candidate list scatter-min'd into [N] with the smallest-src-id tie
    rule — where the dense epoch relaxes every out-edge of their tails.
    That is the same wave: the state is converged before the epoch, so an
    old edge (u -> x) already has dist[x] <= dist[u] + w, and can neither
    improve x nor tie at x's new, strictly smaller minimum.  The seed wave
    counts as one round with the same messages, and its improved mask seeds
    the ladder loop.  Exact only from a converged state: the bucketed
    schedule, which leaves pushes pending, keeps its own path."""
    fn = _relax_fn(use_kernel, interpret)
    best, arg = fn(sssp.dist[src], src, dst, w, jnp.ones(src.shape, bool),
                   num_rows=num_vertices)
    improved = best < sssp.dist
    dist = jnp.where(improved, best, sssp.dist)
    parent = jnp.where(improved, arg, sssp.parent)
    dist, parent, rounds, msgs, occ, sparse = _ladder_loop(
        dist, parent, improved, st, edges, caps=caps,
        num_vertices=num_vertices, use_kernel=use_kernel,
        interpret=interpret)
    return (SSSPState(dist=dist, parent=parent, source=sssp.source),
            RelaxStats(rounds=rounds + 1,
                       messages=msgs + jnp.sum(improved.astype(jnp.int32))),
            WaveTally(occupancy=occ, seed=jnp.int32(1), sparse=sparse))


@partial(jax.jit, static_argnames=("num_vertices", "caps", "max_rounds",
                                   "use_kernel", "interpret"))
def sparse_relax_until_converged(
    sssp: SSSPState, edges: EdgePool, st: OutState, frontier: jax.Array, *,
    num_vertices: int, caps: tuple[int, ...],
    max_rounds: int = 0, use_kernel: bool = False, interpret: bool = True,
) -> tuple[SSSPState, RelaxStats, WaveTally]:
    """Ladder rendering of ``relax.relax_until_converged``: the same
    converged-loop driver and [N]-mask carry, each wave executed through
    the capacity ladder (the batched lanes' ADD epoch)."""
    dist, parent, rounds, msgs, occ, sparse = _ladder_loop(
        sssp.dist, sssp.parent, frontier, st, edges, caps=caps,
        num_vertices=num_vertices, max_rounds=max_rounds,
        use_kernel=use_kernel, interpret=interpret)
    return (SSSPState(dist=dist, parent=parent, source=sssp.source),
            RelaxStats(rounds=rounds, messages=msgs),
            WaveTally(occupancy=occ, seed=jnp.int32(0), sparse=sparse))


@partial(jax.jit, static_argnames=("num_vertices", "caps", "use_doubling",
                                   "use_kernel", "interpret"))
def sparse_invalidate_and_recompute(
    sssp: SSSPState, edges: EdgePool, st: OutState, seed: jax.Array, *,
    num_vertices: int, caps: tuple[int, ...],
    use_doubling: bool = True, use_kernel: bool = False,
    interpret: bool = True,
) -> tuple[SSSPState, del_mod.DeleteStats, WaveTally]:
    """Ladder deletion epoch — structurally identical to
    ``delete.invalidate_and_recompute`` (same marking, same dense bulk-pull
    over the pool's in-edges, same stat gating on ``any(seed)``); only the
    push recompute waves run through the ladder.  The pull stays dense
    because it is keyed by IN-edges of the affected set, which is exactly
    what the pool already indexes — and it runs once per epoch, not per
    wave."""
    any_seed = jnp.any(seed)
    mark = (del_mod.mark_subtree_doubling if use_doubling
            else del_mod.mark_subtree_flood)
    aff, inv_rounds = mark(sssp.parent, seed)
    aff = aff.at[sssp.source].set(False)

    dist = jnp.where(aff, INF, sssp.dist)
    parent = jnp.where(aff, NO_PARENT, sssp.parent)
    dist, parent, improved = del_mod.pull_once(dist, parent, edges, aff,
                                               num_vertices)

    state1 = SSSPState(dist=dist, parent=parent, source=sssp.source)
    state2, stats, tally = sparse_relax_until_converged(
        state1, edges, st, improved, num_vertices=num_vertices, caps=caps,
        use_kernel=use_kernel, interpret=interpret)
    zero = jnp.int32(0)
    return state2, del_mod.DeleteStats(
        invalidation_rounds=jnp.where(any_seed, inv_rounds, zero),
        affected=jnp.sum(aff.astype(jnp.int32)),
        recompute_rounds=jnp.where(any_seed, stats.rounds + 1, zero),
        recompute_messages=jnp.where(
            any_seed,
            stats.messages + jnp.sum(improved.astype(jnp.int32)), zero),
    ), tally


@partial(jax.jit, static_argnames=("num_vertices", "caps", "bucket_width",
                                   "use_kernel", "interpret"))
def sparse_drain(sssp: SSSPState, edges: EdgePool, st: OutState,
                 pend: buckets.PendingState, *, num_vertices: int,
                 caps: tuple[int, ...], bucket_width: float,
                 use_kernel: bool = False, interpret: bool = True
                 ) -> tuple[SSSPState, buckets.PendingState, RelaxStats,
                            WaveTally]:
    """Ladder bucketed drain: ``buckets.run_drain`` with each per-bucket
    active mask compacted through the ladder (pending-mask compaction per
    bucket).  Pull wave and drain discipline are byte-identical to
    ``segment_drain``, so the wave sequence and stats match by
    construction."""

    def wave(dist, parent, active):
        return ladder_wave(
            dist, parent, active, st, edges, caps=caps,
            num_vertices=num_vertices, use_kernel=use_kernel,
            interpret=interpret)

    def pull_wave(dist, parent, aff):
        return del_mod.pull_once(dist, parent, edges, aff, num_vertices)

    dist, parent, stats, occ, sparse = buckets.run_drain(
        sssp.dist, sssp.parent, pend, bucket_width=bucket_width,
        wave=wave, pull_wave=pull_wave, track_occupancy=True)
    return (SSSPState(dist=dist, parent=parent, source=sssp.source),
            buckets.empty_pending(num_vertices), stats,
            WaveTally(occupancy=occ, seed=jnp.int32(0), sparse=sparse))


# ------------------------------------------------ batched [S, N] renderings --
# jax's while_loop batching freezes converged lanes exactly as in the dense
# batched epochs, so per-lane stats match unbatched runs.  Under vmap
# ``lax.cond`` lowers to ``select`` (both ladder branches execute), so these
# are correctness-grade: bit-identical, without the cost win — the default
# route keeps batched engines dense (``backends.ladder_route``).
@partial(jax.jit, static_argnames=("num_vertices", "caps", "use_kernel",
                                   "interpret"))
def sparse_relax_batched(sssp, edges, st, frontier, *, num_vertices, caps,
                         use_kernel=False, interpret=True):
    return jax.vmap(
        lambda s: sparse_relax_until_converged(
            s, edges, st, frontier, num_vertices=num_vertices, caps=caps,
            use_kernel=use_kernel, interpret=interpret))(sssp)


@partial(jax.jit, static_argnames=("num_vertices", "caps", "use_doubling",
                                   "use_kernel", "interpret"))
def sparse_delete_batched(sssp, edges, st, seed, *, num_vertices, caps,
                          use_doubling=True, use_kernel=False,
                          interpret=True):
    return jax.vmap(
        lambda s, sd: sparse_invalidate_and_recompute(
            s, edges, st, sd, num_vertices=num_vertices, caps=caps,
            use_doubling=use_doubling, use_kernel=use_kernel,
            interpret=interpret))(sssp, seed)


@partial(jax.jit, static_argnames=("num_vertices", "caps", "bucket_width",
                                   "use_kernel", "interpret"))
def sparse_drain_batched(sssp, edges, st, pend, *, num_vertices, caps,
                         bucket_width, use_kernel=False, interpret=True):
    return jax.vmap(
        lambda s, pd: sparse_drain(
            s, edges, st, pd, num_vertices=num_vertices, caps=caps,
            bucket_width=bucket_width, use_kernel=use_kernel,
            interpret=interpret))(sssp, pend)


# ------------------------------------------------------------- sharded wave --
def wrap_shard_wave(make_wave, npp: int, cap: int):
    """Wrap a sharded backend's ``make_wave`` factory with per-partition
    edge-worklist compaction (DESIGN.md §12.4).

    The shard epochs patch the partition's COO pool arrays for EVERY
    backend, so the sparse branch can evaluate the segment-style wave over
    the compacted live-offer edges regardless of which layout the dense
    branch uses — identical candidate multiset + tie rule => bit-identical.
    ``offers`` already carry the frontier masking (the exchanges ship
    ``where(frontier, dist, INF)``), so membership is just
    ``isfinite(offers[esrc])``; unmasked pull waves naturally overflow the
    cap and take the dense branch."""

    def make(esrc, edst, ew, eact, extras, my_p):
        dense_wave = make_wave(esrc, edst, ew, eact, extras, my_p)
        row0 = my_p * npp
        n_edges = esrc.shape[0]

        def wave(offers):
            live = eact & jnp.isfinite(offers[esrc])
            ecs = jnp.cumsum(live.astype(jnp.int32))
            cnt = ecs[-1]

            def sparse(_):
                slots = jnp.arange(1, cap + 1, dtype=jnp.int32)
                safe = jnp.clip(jnp.searchsorted(ecs, slots, side="left"),
                                0, n_edges - 1)
                valid = slots <= cnt
                cs, cd, cw = esrc[safe], edst[safe], ew[safe]
                cand = jnp.where(valid, offers[cs] + cw, INF)
                dl = jnp.clip(cd - row0, 0, npp - 1)
                best = jnp.minimum(
                    jax.ops.segment_min(cand, dl, num_segments=npp), INF)
                hit = (cand == best[dl]) & (cand < INF)
                arg = jax.ops.segment_min(
                    jnp.where(hit, cs, _INT_MAX), dl, num_segments=npp)
                return best, arg

            return jax.lax.cond(cnt <= cap, sparse,
                                lambda _: dense_wave(offers), 0)

        return wave

    return make
