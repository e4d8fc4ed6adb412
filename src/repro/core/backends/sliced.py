"""Sliced hybrid backend (DESIGN.md §6): per-slice-K ELL + hub overflow COO,
behind the RelaxBackend protocol (§7).

Rows are bucketed into degree slices with per-slice pow2 K (capped at a hub
threshold), flattened into one 1-D cell buffer, plus a device COO *overflow*
segment holding hub rows' surplus in-edges, relaxed with the segment-min
kernel and min-combined with the per-slice ELL waves.  Maintenance mirrors
the dense ELL backend cell-for-cell (idempotent appends, device-side
match+tombstone DEL/min-update probing both lanes, per-slice width doubling
plus overflow doubling at mirror rebuilds).

Wave decomposition is shared between the single-device epochs and the
sharded per-partition wave (§7.2): ``sliced_gather_min`` (the per-slice ELL
lane), ``overflow_min`` (the hub-surplus COO lane) and ``combine_lanes``
(scalar min per row with the smallest-global-src-id tie rule across lanes).

Sharded participation: ``ShardedSliced`` keeps one shard-local planner per
partition; per-slice widths and the overflow capacity are synchronized
across shards at rebuild time (elementwise max of the per-shard doubling
policies) so the shard_map epochs see one static flat geometry.  Overflow
``odst`` entries are stored in *global ELL-row* space (``p*rows_pp + local
row``) — the same row space the flat cells use — so the single-device patch
ops work verbatim on the global arrays.

Batched multi-source serving (§8): both lanes and their combine are pure
jnp gathers/segment-mins over source-independent layout state, so the base
protocol's ``relax_batched``/``delete_batched`` vmap (and the sharded
engine's ``jax.vmap(wave)``) batch the stacked [S, N] trees directly.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import delete as del_mod
from repro.core import ingest
from repro.core.backends.base import (RelaxBackend, ShardedBackend, register,
                                      register_sharded, rank_within_rows)
from repro.core.relax import RelaxStats
from repro.core.state import INF, NO_PARENT, SSSPState
from repro.graphs import csr as csr_mod
from repro.kernels.relax import config as kernel_config

_NEG_INF = jnp.float32(-jnp.inf)
_INT_MAX = jnp.int32(2**31 - 1)
_next_pow2 = csr_mod.next_pow2


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SlicedEllState:
    """Device-resident hybrid sliced-ELL + overflow-COO view of the edge set.

    The ELL cells of all slices live in ONE flat buffer (``flat_idx``,
    ``flat_w``): row r's cells occupy ``[base[r], base[r] + rowk[r])`` where
    ``rowk[r]`` is r's slice width.  ``fill`` is the per-row occupancy
    high-water mark, exactly as in ``EllState``.  Hub rows (in-degree above
    the planner's hub threshold) keep their surplus in-edges in the COO
    overflow segment ``(osrc, odst, ow)``; empty/tombstoned entries there
    carry w=+inf and never win a min.  ``odst`` is in row space — vertex ids
    single-device, global ELL-row ids when sharded.
    """

    flat_idx: jax.Array  # i32[L] in-neighbor ids (0 where empty/tombstone)
    flat_w: jax.Array    # f32[L] weights (+inf where empty/tombstone)
    fill: jax.Array      # i32[R]
    base: jax.Array      # i32[R] flat offset of each row's first cell
    rowk: jax.Array      # i32[R] each row's slice width
    osrc: jax.Array      # i32[C] overflow in-neighbor ids
    odst: jax.Array      # i32[C] overflow destination rows
    ow: jax.Array        # f32[C] overflow weights (+inf empty/tombstone)


# --------------------------------------------------------------- patch ops --
@jax.jit
def sliced_append(st: SlicedEllState, pos: jax.Array, rows: jax.Array,
                  kpos: jax.Array, src: jax.Array, w: jax.Array
                  ) -> SlicedEllState:
    """Write fresh edges into planner-assigned flat cells (idempotent scatter
    — pad_pow2 repeats are no-ops).  ``pos == base[rows] + kpos``; the
    planner passes both so the device fill marks stay in sync."""
    return dataclasses.replace(
        st,
        flat_idx=st.flat_idx.at[pos].set(src),
        flat_w=st.flat_w.at[pos].set(w),
        fill=st.fill.at[rows].max(kpos + 1),
    )


@jax.jit
def sliced_spill(st: SlicedEllState, opos: jax.Array, src: jax.Array,
                 rows: jax.Array, w: jax.Array) -> SlicedEllState:
    """Append hub-surplus edges into planner-assigned overflow entries
    (idempotent scatter, same pad_pow2 contract as ``sliced_append``)."""
    return dataclasses.replace(
        st,
        osrc=st.osrc.at[opos].set(src),
        odst=st.odst.at[opos].set(rows),
        ow=st.ow.at[opos].set(w),
    )


def _sliced_match(st: SlicedEllState, rows: jax.Array, src: jax.Array,
                  width: int):
    """Locate each (src -> rows) edge's live ELL cell: (flat_pos, found).

    Gathers a ``width``-wide window per row (``width`` = max slice width,
    static) masked to the row's actual slice width — the sliced rendering of
    the dense ELL cell match.  Live edges are unique per (row, src), so at
    most one finite-weight cell matches; edges living in the overflow
    segment simply don't match here."""
    m = rows.shape[0]
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (m, width), 1)
    pos = jnp.clip(st.base[rows][:, None] + k_iota, 0,
                   st.flat_w.shape[0] - 1)
    in_row = k_iota < st.rowk[rows][:, None]
    hit = (in_row & (st.flat_idx[pos] == src[:, None])
           & jnp.isfinite(st.flat_w[pos]))
    kbest = jnp.argmax(hit, axis=1)
    sel = jnp.take_along_axis(pos, kbest[:, None], axis=1)[:, 0]
    return sel, jnp.any(hit, axis=1)


def _overflow_match(st: SlicedEllState, rows: jax.Array, src: jax.Array):
    """Locate each (src -> rows) edge's live overflow entry: (opos, found)."""
    live = jnp.isfinite(st.ow)[None, :]
    hit = (live & (st.osrc[None, :] == src[:, None])
           & (st.odst[None, :] == rows[:, None]))
    return jnp.argmax(hit, axis=1), jnp.any(hit, axis=1)


@partial(jax.jit, static_argnames=("width",))
def sliced_delete(st: SlicedEllState, rows: jax.Array, src: jax.Array,
                  *, width: int) -> SlicedEllState:
    """Tombstone deleted edges (w := +inf) wherever they live — ELL cell or
    overflow entry — located on device by source-id match.  The max-combine
    (-inf = no-op) makes both scatters order-free under batch padding."""
    sel, found = _sliced_match(st, rows, src, width)
    opos, ofound = _overflow_match(st, rows, src)
    return dataclasses.replace(
        st,
        flat_w=st.flat_w.at[sel].max(jnp.where(found, INF, _NEG_INF)),
        ow=st.ow.at[opos].max(jnp.where(ofound, INF, _NEG_INF)),
    )


@partial(jax.jit, static_argnames=("width",))
def sliced_update_min(st: SlicedEllState, rows: jax.Array, src: jax.Array,
                      w: jax.Array, *, width: int) -> SlicedEllState:
    """Weight-decrease of existing edges (on_duplicate="min"): device-side
    match + min-scatter in both lanes (+inf = no-op when unmatched)."""
    sel, found = _sliced_match(st, rows, src, width)
    opos, ofound = _overflow_match(st, rows, src)
    return dataclasses.replace(
        st,
        flat_w=st.flat_w.at[sel].min(jnp.where(found, w, INF)),
        ow=st.ow.at[opos].min(jnp.where(ofound, w, INF)),
    )


@partial(jax.jit, static_argnames=("width",))
def sliced_invariants(st: SlicedEllState, *, width: int
                      ) -> dict[str, jax.Array]:
    """Occupancy invariants over the flat buffer (mirrors ``ell_invariants``):
    cells between a row's fill mark and its slice width must be empty."""
    R = st.fill.shape[0]
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (R, width), 1)
    pos = jnp.clip(st.base[:, None] + k_iota, 0, st.flat_w.shape[0] - 1)
    beyond = (k_iota < st.rowk[:, None]) & (k_iota >= st.fill[:, None])
    return {
        "beyond_fill_empty": jnp.all(
            jnp.where(beyond, jnp.isinf(st.flat_w[pos]), True)),
        "fill_in_range": jnp.all((st.fill >= 0) & (st.fill <= st.rowk)),
    }


# ------------------------------------------------------------------- waves --
def sliced_gather_min(offers: jax.Array, flat_idx: jax.Array,
                      flat_w: jax.Array, *, widths: tuple[int, ...],
                      slice_rows: int, use_kernel: bool = False,
                      interpret: bool = True):
    """The ELL lane of one hybrid wave: per-slice gather + row-min over the
    flat cell buffer.  Returns (best f32[R], arg i32[R]) for R =
    len(widths) * slice_rows rows; arg is the smallest minimizing neighbor
    id (the shared tie rule).

    Slices are processed by width class, not by contiguous run: a
    power-law layout alternates widths from slice to slice (R-MAT scale
    20: 845 runs over 5 widths), and a block per run costs compile time
    and scratch memory in proportion to the run count (16 GB on a v5e at
    scale 20, against 1 GB by class).  A slice of width k is k consecutive
    ``slice_rows``-cell lines of the flat buffer, so one line gather
    (static line ids) pulls every slice of width k into one
    ``(rows_k, k)`` block, and a slice-row scatter puts the results back
    in row order.  (A gather of whole slices as windows takes a minute to
    compile for a TPU; the line gather takes a second.)  The Pallas
    kernel requires its rows to divide into ``min(256, rows)`` blocks, so
    its class block is split as ``slice_run_groups`` splits a run.
    """
    from repro.kernels.relax.fused import slice_run_groups
    from repro.kernels.relax.ref import ellpack_relax_ref
    from repro.kernels.relax.relax import ellpack_relax

    sr = slice_rows
    wid = np.asarray(widths, np.int64)
    line0 = csr_mod.sliced_geometry(widths, sr)[0][:-1] // sr
    best = jnp.full((len(widths), sr), INF)
    arg = jnp.full((len(widths), sr), -1, jnp.int32)
    for k in sorted(set(widths)):
        sl = np.flatnonzero(wid == k)
        lines = jnp.asarray((line0[sl, None] + np.arange(k)).reshape(-1),
                            jnp.int32)

        def take(buf, k=k, lines=lines):
            return buf.reshape(-1, sr)[lines].reshape(len(sl) * sr, k)

        idx, w = take(flat_idx), take(flat_w)
        if use_kernel:
            parts, off = [], 0
            for _, cnt in slice_run_groups((k,) * len(sl), sr):
                blk = slice(off, off + cnt * sr)
                parts.append(ellpack_relax(offers, idx[blk], w[blk],
                                           interpret=interpret))
                off += cnt * sr
            b = jnp.concatenate([p[0] for p in parts])
            a = jnp.concatenate([p[1] for p in parts])
        else:
            b, a = ellpack_relax_ref(offers, idx, w)
        rows = jnp.asarray(sl, jnp.int32)
        best = best.at[rows].set(b.reshape(len(sl), sr),
                                 indices_are_sorted=True, unique_indices=True)
        arg = arg.at[rows].set(a.reshape(len(sl), sr),
                               indices_are_sorted=True, unique_indices=True)
    return best.reshape(-1), arg.reshape(-1)


def overflow_min(offers: jax.Array, osrc: jax.Array, odst: jax.Array,
                 ow: jax.Array, nrows: int):
    """The overflow lane: the segment backend's scatter-min on the hub
    surplus.  ``odst`` must already be local row ids in [0, nrows)."""
    ocand = offers[osrc] + ow              # +inf entries can never win
    obest = jnp.minimum(
        jax.ops.segment_min(ocand, odst, num_segments=nrows), INF)
    ohit = (ocand == obest[odst]) & (ocand < INF)
    oarg = jax.ops.segment_min(jnp.where(ohit, osrc, _INT_MAX), odst,
                               num_segments=nrows)
    return obest, oarg


def combine_lanes(best: jax.Array, arg: jax.Array, obest: jax.Array,
                  oarg: jax.Array):
    """Min-combine the two lanes per row.  Parent ties break toward the
    smallest in-neighbor id ACROSS both lanes — each lane already reports
    its smallest minimizing id, so the combine is a scalar min per row —
    which keeps (dist, parent) bit-identical to the segment and dense-ELL
    backends."""
    comb = jnp.minimum(best, obest)
    ell_key = jnp.where((best == comb) & (best < INF), arg, _INT_MAX)
    coo_key = jnp.where((obest == comb) & (obest < INF), oarg, _INT_MAX)
    return comb, jnp.minimum(ell_key, coo_key)


@partial(jax.jit, static_argnames=("widths", "slice_rows", "num_vertices",
                                   "use_kernel", "interpret", "use_fused"))
def sliced_relax_wave(dist: jax.Array, parent: jax.Array,
                      st: SlicedEllState, *, widths: tuple[int, ...],
                      slice_rows: int, num_vertices: int,
                      frontier: jax.Array | None = None,
                      use_kernel: bool = False, interpret: bool = True,
                      use_fused: bool = False):
    """One hybrid relaxation wave: per-slice ELL gather+row-min min-combined
    with a segment-min over the overflow COO lane.

    ``use_fused`` routes the whole wave — frontier masking, ELL lane,
    overflow lane, lane combine — through the single fused Pallas kernel
    (kernels/relax/fused.py, DESIGN.md §9.4) instead of the three-dispatch
    composition below; both paths are bit-identical by construction."""
    n = dist.shape[0]
    if use_fused:
        from repro.kernels.relax.fused import fused_sliced_relax
        act = (jnp.ones(dist.shape, jnp.bool_) if frontier is None
               else frontier)
        comb, new_parent = fused_sliced_relax(
            dist, act, st.flat_idx, st.flat_w, st.osrc, st.odst, st.ow,
            widths=widths, slice_rows=slice_rows, interpret=interpret)
        comb, new_parent = comb[:n], new_parent[:n]
    else:
        offers = dist if frontier is None else jnp.where(frontier, dist, INF)
        best, arg = sliced_gather_min(
            offers, st.flat_idx, st.flat_w, widths=widths,
            slice_rows=slice_rows, use_kernel=use_kernel,
            interpret=interpret)
        best, arg = best[:n], arg[:n]
        obest, oarg = overflow_min(offers, st.osrc, st.odst, st.ow,
                                   num_vertices)
        comb, new_parent = combine_lanes(best, arg, obest, oarg)
    improved = comb < dist
    return (jnp.where(improved, comb, dist),
            jnp.where(improved, new_parent, parent),
            improved)


# ------------------------------------------------------------------ epochs --
@partial(jax.jit, static_argnames=("widths", "slice_rows", "num_vertices",
                                   "max_rounds", "use_kernel", "interpret",
                                   "use_fused"))
def sliced_relax_until_converged(
    sssp: SSSPState,
    st: SlicedEllState,
    frontier: jax.Array,
    *,
    widths: tuple[int, ...],
    slice_rows: int,
    num_vertices: int,
    max_rounds: int = 0,
    use_kernel: bool = False,
    interpret: bool = True,
    use_fused: bool = False,
) -> tuple[SSSPState, RelaxStats]:
    """Sliced rendering of relax.relax_until_converged: frontier-masked
    hybrid waves to fixpoint.  Same candidate sets, same tie-break =>
    bit-identical results and stats."""

    def cond(carry):
        _, _, frontier, rounds, _ = carry
        go = jnp.any(frontier)
        if max_rounds:
            go = go & (rounds < max_rounds)
        return go

    def body(carry):
        dist, parent, frontier, rounds, msgs = carry
        dist, parent, improved = sliced_relax_wave(
            dist, parent, st, widths=widths, slice_rows=slice_rows,
            num_vertices=num_vertices, frontier=frontier,
            use_kernel=use_kernel, interpret=interpret,
            use_fused=use_fused)
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


@partial(jax.jit, static_argnames=("widths", "slice_rows", "num_vertices",
                                   "use_doubling", "use_kernel",
                                   "interpret", "use_fused"))
def sliced_invalidate_and_recompute(
    sssp: SSSPState,
    st: SlicedEllState,
    seed: jax.Array,
    *,
    widths: tuple[int, ...],
    slice_rows: int,
    num_vertices: int,
    use_doubling: bool = True,
    use_kernel: bool = False,
    interpret: bool = True,
    use_fused: bool = False,
) -> tuple[SSSPState, del_mod.DeleteStats]:
    """Deletion epoch on the hybrid layout — structurally identical to
    the dense-ELL deletion epoch (same marking, same bulk-pull-as-one-
    unmasked-wave, same stat gating on ``any(seed)``), with the hybrid wave
    so hub rows also pull offers through the overflow lane."""
    any_seed = jnp.any(seed)
    mark = (del_mod.mark_subtree_doubling if use_doubling
            else del_mod.mark_subtree_flood)
    aff, inv_rounds = mark(sssp.parent, seed)
    aff = aff.at[sssp.source].set(False)

    dist = jnp.where(aff, INF, sssp.dist)
    parent = jnp.where(aff, NO_PARENT, sssp.parent)

    dist_p, parent_p, improved = sliced_relax_wave(
        dist, parent, st, widths=widths, slice_rows=slice_rows,
        num_vertices=num_vertices, use_kernel=use_kernel,
        interpret=interpret, use_fused=use_fused)
    improved = improved & aff
    dist = jnp.where(improved, dist_p, dist)
    parent = jnp.where(improved, parent_p, parent)

    state1 = SSSPState(dist=dist, parent=parent, source=sssp.source)
    state2, stats = sliced_relax_until_converged(
        state1, st, improved, widths=widths, slice_rows=slice_rows,
        num_vertices=num_vertices, use_kernel=use_kernel,
        interpret=interpret, use_fused=use_fused)
    zero = jnp.int32(0)
    return state2, del_mod.DeleteStats(
        invalidation_rounds=jnp.where(any_seed, inv_rounds, zero),
        affected=jnp.sum(aff.astype(jnp.int32)),
        recompute_rounds=jnp.where(any_seed, stats.rounds + 1, zero),
        recompute_messages=jnp.where(
            any_seed,
            stats.messages + jnp.sum(improved.astype(jnp.int32)), zero),
    )


@partial(jax.jit, static_argnames=("widths", "slice_rows", "num_vertices",
                                   "use_kernel", "interpret",
                                   "use_fused"))
def sliced_relax_batched(sssp, st, frontier, *, widths, slice_rows,
                         num_vertices, use_kernel=False, interpret=True,
                         use_fused=False):
    """Batched multi-source rendering (DESIGN.md §8): jit(vmap(epoch)) over
    the [S, N] tree stack, the shared hybrid layout captured unbatched."""
    return jax.vmap(
        lambda s: sliced_relax_until_converged(
            s, st, frontier, widths=widths, slice_rows=slice_rows,
            num_vertices=num_vertices, use_kernel=use_kernel,
            interpret=interpret, use_fused=use_fused))(sssp)


@partial(jax.jit, static_argnames=("widths", "slice_rows", "num_vertices",
                                   "use_doubling", "use_kernel",
                                   "interpret", "use_fused"))
def sliced_delete_batched(sssp, st, seed, *, widths, slice_rows,
                          num_vertices, use_doubling=True, use_kernel=False,
                          interpret=True, use_fused=False):
    """Batched deletion epoch: per-lane [S, N] seeds over the shared layout."""
    return jax.vmap(
        lambda s, sd: sliced_invalidate_and_recompute(
            s, st, sd, widths=widths, slice_rows=slice_rows,
            num_vertices=num_vertices, use_doubling=use_doubling,
            use_kernel=use_kernel, interpret=interpret,
            use_fused=use_fused))(sssp, seed)


@partial(jax.jit, static_argnames=("widths", "slice_rows", "num_vertices",
                                   "bucket_width", "use_kernel",
                                   "interpret", "use_fused"))
def sliced_drain(sssp, st, pend, *, widths, slice_rows, num_vertices: int,
                 bucket_width: float, use_kernel: bool = False,
                 interpret: bool = True, use_fused: bool = False):
    """Bucketed drain on the hybrid layout (DESIGN.md §9) — same pull
    pattern as the deletion epoch (one unmasked hybrid wave, improvements
    applied to affected rows only), so the drain's wave sequence and stats
    stay bit-identical to the segment and dense-ELL drains."""
    from repro.core import buckets

    def wave(dist, parent, active):
        return sliced_relax_wave(
            dist, parent, st, widths=widths, slice_rows=slice_rows,
            num_vertices=num_vertices, frontier=active,
            use_kernel=use_kernel, interpret=interpret,
            use_fused=use_fused)

    def pull_wave(dist, parent, aff):
        dist_p, parent_p, improved = sliced_relax_wave(
            dist, parent, st, widths=widths, slice_rows=slice_rows,
            num_vertices=num_vertices, use_kernel=use_kernel,
            interpret=interpret, use_fused=use_fused)
        improved = improved & aff
        return (jnp.where(improved, dist_p, dist),
                jnp.where(improved, parent_p, parent), improved)

    dist, parent, stats = buckets.run_drain(
        sssp.dist, sssp.parent, pend, bucket_width=bucket_width,
        wave=wave, pull_wave=pull_wave)
    return (SSSPState(dist=dist, parent=parent, source=sssp.source),
            buckets.empty_pending(num_vertices), stats)


@partial(jax.jit, static_argnames=("widths", "slice_rows", "num_vertices",
                                   "bucket_width", "use_kernel",
                                   "interpret", "use_fused"))
def sliced_drain_batched(sssp, st, pend, *, widths, slice_rows,
                         num_vertices: int, bucket_width: float,
                         use_kernel: bool = False, interpret: bool = True,
                         use_fused: bool = False):
    return jax.vmap(
        lambda s, pd: sliced_drain(
            s, st, pd, widths=widths, slice_rows=slice_rows,
            num_vertices=num_vertices, bucket_width=bucket_width,
            use_kernel=use_kernel, interpret=interpret,
            use_fused=use_fused))(sssp, pend)


# ------------------------------------------------------------ host planner --
class SlicedPlan(NamedTuple):
    """One ADD batch's placement: ELL cells + overflow spills (all numpy,
    planner-local row/position space)."""

    pos: np.ndarray    # i32[e] flat ELL cell positions (base[row] + kpos)
    rows: np.ndarray   # i32[e]
    kpos: np.ndarray   # i32[e]
    src: np.ndarray    # i32[e]
    w: np.ndarray      # f32[e]
    opos: np.ndarray   # i32[s] overflow entry positions
    osrc: np.ndarray   # i32[s]
    orows: np.ndarray  # i32[s]
    ow: np.ndarray     # f32[s]
    at: np.ndarray     # i64[m] per input edge: its cell, or cells + entry


class SlicedEllPlanner:
    """Host control plane for the hybrid layout (DESIGN.md §6): assigns ELL
    cells and overflow entries, detects per-slice / overflow exhaustion, and
    rebuilds from the host COO mirror with monotone per-slice capacity
    doubling (each slice's width doubles independently, capped at ``hub_k``;
    the overflow capacity doubles when the live surplus outgrows it).

    Hub threshold policy: a row whose fill reaches ``hub_k`` is a hub — its
    further in-edges spill to the overflow segment instead of widening the
    whole slice.  Rows below the threshold that outgrow their slice width
    trigger a rebuild, which doubles that slice's width only.

    ``row0`` makes the planner window-local: it accepts *global* destination
    ids for the vertex window ``[row0, row0 + num_vertices)`` and emits
    positions/rows in its own local space (the sharded coordinator
    globalizes them).
    """

    def __init__(self, num_vertices: int, *, slice_rows: int = 256,
                 hub_k: int = 32, init_k: int = 2, row0: int = 0):
        self.n = num_vertices
        self.row0 = row0
        self.sr = min(_next_pow2(max(slice_rows, 1)),
                      _next_pow2(max(num_vertices, 1)))
        self.rows = -(-num_vertices // self.sr) * self.sr
        self.n_slices = self.rows // self.sr
        self.hub_k = _next_pow2(max(hub_k, 1))
        init_k = min(_next_pow2(max(init_k, 1)), self.hub_k)
        self.widths = [init_k] * self.n_slices
        self.fill = np.zeros(self.rows, np.int32)
        self.ocap = 8
        self.ofill = 0
        self.rebuilds = 0
        self.spills = 0
        self._recompute_geometry()

    def _recompute_geometry(self) -> None:
        _, self.rowk, self.base, self.cells = csr_mod.sliced_geometry(
            self.widths, self.sr)

    @property
    def max_width(self) -> int:
        return max(self.widths)

    def empty_state(self) -> SlicedEllState:
        fi, fw, fill, osrc, odst, ow = self.empty_host()
        return SlicedEllState(
            flat_idx=jnp.asarray(fi), flat_w=jnp.asarray(fw),
            fill=jnp.asarray(fill),
            base=jnp.asarray(self.base, jnp.int32),
            rowk=jnp.asarray(self.rowk, jnp.int32),
            osrc=jnp.asarray(osrc), odst=jnp.asarray(odst),
            ow=jnp.asarray(ow))

    def empty_host(self):
        return (np.zeros(self.cells, np.int32),
                np.full(self.cells, INF, np.float32),
                np.zeros(self.rows, np.int32),
                np.zeros(self.ocap, np.int32),
                np.zeros(self.ocap, np.int32),
                np.full(self.ocap, INF, np.float32))

    def plan_appends(self, rows: np.ndarray, src: np.ndarray,
                     w: np.ndarray) -> SlicedPlan | None:
        """Assign each fresh edge (global dst ids) an ELL cell past its
        row's fill mark, or an overflow entry once the row is at the hub
        threshold.  Returns None when a sub-threshold row outgrows its slice
        width or the overflow segment is full — the caller must rebuild
        instead."""
        m = len(rows)
        z32 = np.empty(0, np.int32)
        zf = np.empty(0, np.float32)
        if m == 0:
            return SlicedPlan(z32, z32, z32, z32, zf, z32, z32, z32, zf,
                              np.empty(0, np.int64))
        rows = np.asarray(rows, np.int64) - self.row0
        kcand = self.fill[rows] + rank_within_rows(rows)
        to_ell = kcand < self.rowk[rows]
        over = ~to_ell
        # overflow is only legal past the hub threshold; a sub-threshold row
        # outgrowing its slice width means the slice must double -> rebuild
        if bool((over & (self.rowk[rows] < self.hub_k)).any()):
            return None
        n_spill = int(over.sum())
        if self.ofill + n_spill > self.ocap:
            return None
        # commit
        erows = rows[to_ell]
        ekpos = kcand[to_ell].astype(np.int32)
        np.maximum.at(self.fill, erows, ekpos + 1)
        sp_rank = np.cumsum(over) - 1
        opos = (self.ofill + sp_rank[over]).astype(np.int32)
        self.ofill += n_spill
        self.spills += n_spill
        pos = (self.base[erows] + ekpos).astype(np.int32)
        at = np.empty(m, np.int64)
        at[to_ell] = pos
        at[over] = self.cells + opos
        return SlicedPlan(
            pos=pos, rows=erows.astype(np.int32), kpos=ekpos,
            src=np.asarray(src)[to_ell], w=np.asarray(w)[to_ell],
            opos=opos, osrc=np.asarray(src)[over],
            orows=rows[over].astype(np.int32), ow=np.asarray(w)[over], at=at)

    def required_geometry(self, dst: np.ndarray
                          ) -> tuple[list[int], int]:
        """(widths, overflow capacity) this planner's doubling policy wants
        for a live edge set (global dst ids) — used by the sharded
        coordinator to synchronize geometry before a coupled rebuild."""
        deg = np.zeros(self.rows, np.int64)
        if len(dst):
            deg[:self.n] = np.bincount(
                np.asarray(dst, np.int64) - self.row0, minlength=self.n)
        capped = np.minimum(deg, self.hub_k)
        slice_max = capped.reshape(self.n_slices, self.sr).max(axis=1)
        widths = [
            max(cur, min(self.hub_k, _next_pow2(max(2 * int(mx), 1))))
            for cur, mx in zip(self.widths, slice_max)]
        surplus = int((deg - capped).sum())
        ocap = max(self.ocap, _next_pow2(max(2 * surplus, 8)))
        return widths, ocap

    def rebuild_host(self, src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                     positions: bool = False):
        """Numpy half of ``rebuild`` — the sharded coordinator concatenates
        these blocks partition-major before one sharded transfer.  Returns
        (flat_idx, flat_w, fill, osrc, odst, ow) with ``odst`` in the
        planner's local row space; ``positions=True`` appends each input
        edge's position (``sliced_ell_from_coo``'s ``at``)."""
        self.widths, self.ocap = self.required_geometry(dst)
        flat_idx, flat_w, fill, _, osrc, odst, ow, n_over, *at = \
            csr_mod.sliced_ell_from_coo(
                self.n, src, dst, w, slice_rows=self.sr, hub_k=self.hub_k,
                n_rows=self.rows, widths=self.widths,
                overflow_capacity=self.ocap, row0=self.row0,
                positions=positions)
        self.fill = fill
        self.ofill = n_over
        self.rebuilds += 1
        self._recompute_geometry()
        return (flat_idx, flat_w, fill, osrc, odst, ow, *at)

    def rebuild(self, src: np.ndarray, dst: np.ndarray, w: np.ndarray
                ) -> SlicedEllState:
        """Rebuild the device layout from the live COO edge set (host
        mirror): tombstones compact away, each slice's width grows to the
        next pow2 of 2x its capped max in-degree (monotone, <= hub_k), and
        the overflow capacity doubles past the live surplus."""
        flat_idx, flat_w, fill, osrc, odst, ow = self.rebuild_host(src, dst, w)
        return SlicedEllState(
            flat_idx=jnp.asarray(flat_idx), flat_w=jnp.asarray(flat_w),
            fill=jnp.asarray(fill), base=jnp.asarray(self.base, jnp.int32),
            rowk=jnp.asarray(self.rowk, jnp.int32),
            osrc=jnp.asarray(osrc), odst=jnp.asarray(odst),
            ow=jnp.asarray(ow))


# ----------------------------------------------------------------- backend --
@register
class SlicedBackend(RelaxBackend):
    """RelaxBackend over the hybrid layout: SlicedEllPlanner host control
    plane, dual-lane patch ops, hybrid epoch waves, coupled per-slice /
    overflow rebuilds from the mirror."""

    name = "sliced"

    def __init__(self, cfg, num_vertices, *, use_kernel=False, interpret=True):
        super().__init__(cfg, num_vertices, use_kernel=use_kernel,
                         interpret=interpret)
        self.use_fused = bool(getattr(cfg, "sliced_fused", False))
        self.planner = self._mk_planner()
        self.state = self.planner.empty_state()

    def _mk_planner(self) -> SlicedEllPlanner:
        return SlicedEllPlanner(
            self.n, slice_rows=self.cfg.sliced_slice_rows,
            hub_k=self.cfg.sliced_hub_k, init_k=self.cfg.sliced_init_k)

    def apply_adds(self, plan, alloc):
        """Incremental hybrid-layout maintenance for one ADD batch
        (DESIGN.md §6).  Fresh edges get planner-assigned ELL cells or — for
        rows at the hub threshold — overflow entries; weight-decreases
        resolve their cell/entry on device.  Slice-width or overflow
        exhaustion triggers a full rebuild from the host COO mirror (which
        already contains this batch, so no patch follows)."""
        fresh = plan.fresh
        sp = self.planner.plan_appends(
            plan.dst[fresh].astype(np.int64), plan.src[fresh], plan.w[fresh])
        if sp is None:
            self.state = self.planner.rebuild(*alloc.active_coo())
            return
        if len(sp.pos):
            pos_p, rows_p, kpos_p, src_p, w_p = ingest.pad_pow2(
                sp.pos, sp.rows, sp.kpos, sp.src, sp.w)
            self.state = sliced_append(
                self.state, jnp.asarray(pos_p), jnp.asarray(rows_p),
                jnp.asarray(kpos_p), jnp.asarray(src_p), jnp.asarray(w_p))
        if len(sp.opos):
            opos_p, osrc_p, orows_p, ow_p = ingest.pad_pow2(
                sp.opos, sp.osrc, sp.orows, sp.ow)
            self.state = sliced_spill(
                self.state, jnp.asarray(opos_p), jnp.asarray(osrc_p),
                jnp.asarray(orows_p), jnp.asarray(ow_p))
        if not fresh.all():
            upd = ~fresh
            rows_p, src_p, w_p = ingest.pad_pow2(
                plan.dst[upd], plan.src[upd], plan.w[upd])
            self.state = sliced_update_min(
                self.state, jnp.asarray(rows_p), jnp.asarray(src_p),
                jnp.asarray(w_p), width=self.planner.max_width)

    def apply_dels(self, rows, src):
        self.state = sliced_delete(
            self.state, jnp.asarray(rows), jnp.asarray(src),
            width=self.planner.max_width)

    def relax(self, sssp, edges, frontier):
        return sliced_relax_until_converged(
            sssp, self.state, frontier,
            widths=tuple(self.planner.widths), slice_rows=self.planner.sr,
            num_vertices=self.n, use_kernel=self.use_kernel,
            interpret=self.interpret, use_fused=self.use_fused)

    def delete(self, sssp, edges, seed):
        return sliced_invalidate_and_recompute(
            sssp, self.state, seed,
            widths=tuple(self.planner.widths), slice_rows=self.planner.sr,
            num_vertices=self.n, use_doubling=self.cfg.use_doubling,
            use_kernel=self.use_kernel, interpret=self.interpret, use_fused=self.use_fused)

    def relax_batched(self, sssp, edges, frontier):
        return sliced_relax_batched(
            sssp, self.state, frontier,
            widths=tuple(self.planner.widths), slice_rows=self.planner.sr,
            num_vertices=self.n, use_kernel=self.use_kernel,
            interpret=self.interpret, use_fused=self.use_fused)

    def delete_batched(self, sssp, edges, seed):
        return sliced_delete_batched(
            sssp, self.state, seed,
            widths=tuple(self.planner.widths), slice_rows=self.planner.sr,
            num_vertices=self.n, use_doubling=self.cfg.use_doubling,
            use_kernel=self.use_kernel, interpret=self.interpret, use_fused=self.use_fused)

    def drain(self, sssp, edges, pend, *, bucket_width):
        return sliced_drain(
            sssp, self.state, pend,
            widths=tuple(self.planner.widths), slice_rows=self.planner.sr,
            num_vertices=self.n, bucket_width=bucket_width,
            use_kernel=self.use_kernel, interpret=self.interpret, use_fused=self.use_fused)

    def drain_batched(self, sssp, edges, pend, *, bucket_width):
        return sliced_drain_batched(
            sssp, self.state, pend,
            widths=tuple(self.planner.widths), slice_rows=self.planner.sr,
            num_vertices=self.n, bucket_width=bucket_width,
            use_kernel=self.use_kernel, interpret=self.interpret, use_fused=self.use_fused)

    def restore(self, alloc):
        self.planner = self._mk_planner()
        self.state = self.planner.rebuild(*alloc.active_coo())

    def invariants(self):
        return sliced_invariants(self.state, width=self.planner.max_width)


# ----------------------------------------------------------- sharded side --
@register_sharded
class ShardedSliced(ShardedBackend):
    """One shard-local SlicedEllPlanner per partition + the per-shard flat
    buffers / overflow segments concatenated partition-major into globally
    sharded device arrays.

    Row space: vertex ``v`` (owner ``p``) lives in global ELL row
    ``p * rows_pp + (v % npp)``; flat cell positions globalize as
    ``p * L + local`` and overflow entries as ``p * ocap + local``.
    Per-slice widths and the overflow capacity are synchronized across
    shards at rebuild time (elementwise max of the per-shard policies) so
    every shard shares one static flat geometry; any shard's exhaustion
    triggers a coupled rebuild of all shards from the mirrors.
    """

    name = "sliced"
    n_extra = 5   # (flat_idx, flat_w, osrc, odst, ow) — what the wave reads

    def __init__(self, cfg, ds, allocs):
        super().__init__(cfg, ds, allocs)
        self.P, self.npp = ds.P, ds.npp
        self.use_kernel = bool(cfg.ell_use_kernel)
        self.interpret = kernel_config.default_interpret()
        self.planners = [
            SlicedEllPlanner(self.npp, slice_rows=cfg.sliced_slice_rows,
                             hub_k=cfg.sliced_hub_k,
                             init_k=cfg.sliced_init_k, row0=p * self.npp)
            for p in range(self.P)]
        p0 = self.planners[0]
        self.sr, self.rows_pp = p0.sr, p0.rows
        self._sh = ds.vertex_sharding()   # dim-0 sharding, any rank
        self._put_blocks([pl.empty_host() for pl in self.planners])

    # ---- geometry / assembly
    @property
    def widths(self) -> list[int]:
        return self.planners[0].widths    # synchronized across shards

    @property
    def max_width(self) -> int:
        return self.planners[0].max_width

    @property
    def L(self) -> int:
        return self.planners[0].cells

    @property
    def ocap(self) -> int:
        return self.planners[0].ocap

    def _put_blocks(self, blocks) -> None:
        p0, L, ocap = self.planners[0], self.L, self.ocap
        base_g = np.concatenate(
            [p * L + p0.base for p in range(self.P)]).astype(np.int32)
        rowk_g = np.tile(p0.rowk, self.P)
        # overflow odst globalizes into ELL-row space (padding entries sit
        # at each shard's row 0 with w=+inf — they never win a min)
        parts = []
        for p, b in enumerate(blocks):
            fi, fw, fill, osrc, odst, ow = b
            parts.append((fi, fw, fill, osrc,
                          (p * self.rows_pp + odst).astype(np.int32), ow))
        cat = [np.concatenate([b[i] for b in parts]) for i in range(6)]
        put = lambda a: jax.device_put(a, self._sh)  # noqa: E731
        self.state = SlicedEllState(
            flat_idx=put(cat[0]), flat_w=put(cat[1]), fill=put(cat[2]),
            base=put(base_g), rowk=put(rowk_g),
            osrc=put(cat[3]), odst=put(cat[4]), ow=put(cat[5]))

    def _pin(self) -> None:
        """Re-pin the patched arrays to the partition sharding (device-to-
        device, async — the ingest loop stays host-sync free).  On a P=1
        mesh any layout is trivially correctly sharded, so the per-batch
        device_put dispatches would be pure overhead — skip them."""
        if self.P == 1:
            return
        put = lambda a: jax.device_put(a, self._sh)  # noqa: E731
        st = self.state
        self.state = SlicedEllState(
            flat_idx=put(st.flat_idx), flat_w=put(st.flat_w),
            fill=put(st.fill), base=st.base, rowk=st.rowk,
            osrc=put(st.osrc), odst=put(st.odst), ow=put(st.ow))

    def _ellrows(self, p: int, rows_local: np.ndarray) -> np.ndarray:
        return (p * self.rows_pp
                + np.asarray(rows_local, np.int64)).astype(np.int32)

    def arrays(self):
        st = self.state
        return (st.flat_idx, st.flat_w, st.osrc, st.odst, st.ow)

    def static_key(self):
        return (self.name, tuple(self.widths), self.sr,
                self.use_kernel, self.interpret)

    # ---- patch staging
    def stage_adds(self, plans) -> None:
        app, spill, upd = [], [], []
        for p, plan in plans:
            fresh = plan.fresh
            sp = self.planners[p].plan_appends(
                plan.dst[fresh].astype(np.int64), plan.src[fresh],
                plan.w[fresh])
            if sp is None:
                self._rebuild_all()   # mirrors already contain this batch
                return
            if len(sp.pos):
                app.append(((p * self.L + sp.pos).astype(np.int32),
                            self._ellrows(p, sp.rows), sp.kpos, sp.src, sp.w))
            if len(sp.opos):
                spill.append(((p * self.ocap + sp.opos).astype(np.int32),
                              sp.osrc, self._ellrows(p, sp.orows), sp.ow))
            if not fresh.all():
                u = ~fresh
                lrows = plan.dst[u].astype(np.int64) - p * self.npp
                upd.append((self._ellrows(p, lrows), plan.src[u], plan.w[u]))
        if app:
            pos, rows, kpos, src, w = (np.concatenate(x) for x in zip(*app))
            pos, rows, kpos, src, w = ingest.pad_pow2(pos, rows, kpos, src, w)
            self.state = sliced_append(
                self.state, jnp.asarray(pos), jnp.asarray(rows),
                jnp.asarray(kpos), jnp.asarray(src), jnp.asarray(w))
        if spill:
            opos, osrc, orows, ow = (np.concatenate(x) for x in zip(*spill))
            opos, osrc, orows, ow = ingest.pad_pow2(opos, osrc, orows, ow)
            self.state = sliced_spill(
                self.state, jnp.asarray(opos), jnp.asarray(osrc),
                jnp.asarray(orows), jnp.asarray(ow))
        if upd:
            rows, src, w = (np.concatenate(x) for x in zip(*upd))
            rows, src, w = ingest.pad_pow2(rows, src, w)
            self.state = sliced_update_min(
                self.state, jnp.asarray(rows), jnp.asarray(src),
                jnp.asarray(w), width=self.max_width)
        if app or spill or upd:
            self._pin()

    def update_del_arrays(self, new_vals) -> None:
        flat_w, ow = new_vals
        self.state = dataclasses.replace(self.state, flat_w=flat_w, ow=ow)

    # ---- coupled rebuild / restore
    def _rebuild_all(self) -> None:
        want_w = list(self.widths)
        want_ocap = self.ocap
        for pl, alloc in zip(self.planners, self.allocs):
            w_p, ocap_p = pl.required_geometry(alloc.active_coo()[1])
            want_w = [max(a, b) for a, b in zip(want_w, w_p)]
            want_ocap = max(want_ocap, ocap_p)
        for pl in self.planners:
            pl.widths = list(want_w)
            pl.ocap = want_ocap
        self._put_blocks([pl.rebuild_host(*alloc.active_coo())
                          for pl, alloc in zip(self.planners, self.allocs)])

    def restore(self) -> None:
        self.planners = [
            SlicedEllPlanner(self.npp, slice_rows=self.cfg.sliced_slice_rows,
                             hub_k=self.cfg.sliced_hub_k,
                             init_k=self.cfg.sliced_init_k, row0=p * self.npp)
            for p in range(self.P)]
        self._rebuild_all()

    # ---- wave / in-epoch DEL patch
    @classmethod
    def shard_wave_factory(cls, static, npp):
        _, widths, sr, use_kernel, interpret = static
        rows_pp = len(widths) * sr

        def make_wave(esrc, edst, ew, eact, extras, my_p):
            flat_idx, flat_w, osrc, odst, ow = extras
            row0_ell = my_p * rows_pp

            def wave(offers):
                best, arg = sliced_gather_min(
                    offers, flat_idx, flat_w, widths=widths, slice_rows=sr,
                    use_kernel=use_kernel, interpret=interpret)
                best, arg = best[:npp], arg[:npp]
                dl = jnp.clip(odst - row0_ell, 0, npp - 1)
                obest, oarg = overflow_min(offers, osrc, dl, ow, npp)
                return combine_lanes(best, arg, obest, oarg)

            return wave

        return make_wave

    del_mutated = (1, 4)   # flat_w, ow

    @classmethod
    def shard_del_patch(cls, static, npp):
        _, widths, sr, _, _ = static
        rows_pp = len(widths) * sr
        _, rowk_np, base_np, _ = csr_mod.sliced_geometry(list(widths), sr)
        width = max(widths)

        def patch(extras, psrc, pdst, my_p):
            """Tombstone deleted edges in this shard's blocks, both lanes:
            the in-epoch rendering of ``sliced_delete`` against the shard's
            LOCAL geometry (static base/rowk from the synced widths).
            Foreign/unmatched entries no-op under the -inf/max combine."""
            flat_idx, flat_w, osrc, odst, ow = extras
            L = flat_w.shape[0]
            base_l = jnp.asarray(base_np, jnp.int32)
            rowk_l = jnp.asarray(rowk_np, jnp.int32)
            lrow = pdst - my_p * npp
            in_r = (lrow >= 0) & (lrow < npp)
            rows = jnp.clip(lrow, 0, rows_pp - 1)
            m = pdst.shape[0]
            k_iota = jax.lax.broadcasted_iota(jnp.int32, (m, width), 1)
            pos = jnp.clip(base_l[rows][:, None] + k_iota, 0, L - 1)
            in_row = k_iota < rowk_l[rows][:, None]
            hit = (in_r[:, None] & in_row
                   & (flat_idx[pos] == psrc[:, None])
                   & jnp.isfinite(flat_w[pos]))
            kbest = jnp.argmax(hit, axis=1)
            sel = jnp.take_along_axis(pos, kbest[:, None], axis=1)[:, 0]
            found = jnp.any(hit, axis=1)
            flat_w = flat_w.at[sel].max(jnp.where(found, INF, _NEG_INF))
            # overflow lane: this shard's odst block holds global ELL rows
            # of the form my_p*rows_pp + local_vertex_row
            odst_l = odst - my_p * rows_pp
            ohit = (jnp.isfinite(ow)[None, :] & in_r[:, None]
                    & (osrc[None, :] == psrc[:, None])
                    & (odst_l[None, :] == lrow[:, None]))
            opos = jnp.argmax(ohit, axis=1)
            ofound = jnp.any(ohit, axis=1)
            ow = ow.at[opos].max(jnp.where(ofound, INF, _NEG_INF))
            return flat_w, ow

        return patch
