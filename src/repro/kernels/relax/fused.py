"""Fused Pallas kernel for one hybrid sliced-ELL + overflow-COO wave.

The unfused hybrid wave (core/backends/sliced.py) is three dispatches per
equal-width run group plus two combine passes: per-group ELL gather+row-min,
a segment-min over the hub overflow COO lane, and the scalar min-combine
with the smallest-src-id tie rule — with the frontier/bucket mask
materialized as a full masked ``offers`` vector up front.  This module fuses
all of it into ONE kernel per run group (DESIGN.md §9.4):

  * the bucket/frontier row mask is applied in-kernel (``offers =
    where(active, dist, inf)`` never hits HBM);
  * each grid block row-mins its ``(bm, k)`` ELL tile as before;
  * the SAME kernel scans the entire overflow COO segment and folds the
    entries whose destination row lands in the block via a scatter-min
    into the block's rows (out-of-block entries drop) — an O(C)-per-block
    segment-min, exact for any odst distribution.  A dense ``(bm, C)``
    row-match mask would be branch-free but costs O(rows x C) total, which
    loses to the unfused scatter path as soon as the overflow lane grows
    past a few hundred entries;
  * both lanes min-combine in registers under the shared smallest-id tie
    rule, so the kernel's ``(best, arg)`` output is bit-identical to
    ``combine_lanes(sliced_gather_min(...), overflow_min(...))``.

Tiling follows the run-group rules: runs of equal-width slices merge into
contiguous row-major ``(rows_g, k)`` blocks (``slice_run_groups`` below,
shared with the unfused path, whose 256-row main/remainder split the fused
path RE-COALESCES: one pallas_call per distinct-width run, block =
``(rows_g, k)`` with k the run's slice width, grid=1).  One block per run
is what keeps the overflow lane at one COO scan per run — a 256-row grid
would rescan the whole segment once per block and lose to the unfused
path as soon as the lane grows.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.relax.config import resolve_interpret

_INF = jnp.float32(jnp.inf)
_INT_MAX = jnp.int32(2**31 - 1)


def slice_run_groups(widths: tuple[int, ...] | list[int],
                     slice_rows: int) -> list[tuple[int, int]]:
    """Merge runs of equal-width slices and split each into a
    multiple-of-256-rows main block plus a remainder: list of
    ``(k, n_slices)`` groups, in row order.  Shared by the fused kernel and
    the unfused ``sliced_gather_min`` so both tile identically."""
    per_blk = max(1, 256 // slice_rows)
    runs: list[list[int]] = []
    for k in widths:
        if runs and runs[-1][0] == k:
            runs[-1][1] += 1
        else:
            runs.append([k, 1])
    groups: list[tuple[int, int]] = []
    for k, cnt in runs:
        main = (cnt // per_blk) * per_blk
        if main:
            groups.append((k, main))
        if cnt - main:
            groups.append((k, cnt - main))
    return groups


def _mk_kernel(row0: int, bm: int):
    """Kernel body for one run group: ELL tile row-min + full-overflow-lane
    fold + in-register lane combine.  ``row0`` is the group's first global
    row; the block's rows are ``[row0 + i*bm, row0 + (i+1)*bm)``."""

    def kernel(dist_ref, act_ref, idx_ref, w_ref, osrc_ref, odst_ref, ow_ref,
               best_ref, arg_ref):
        # literals must be built inside the kernel (Pallas rejects captured
        # device constants)
        _INF = jnp.float32(jnp.inf)
        _INT_MAX = jnp.int32(2**31 - 1)
        # bucket/frontier mask fused into the offer read — inactive rows
        # offer +inf and can never win a min
        offers = jnp.where(act_ref[...], dist_ref[...], _INF)

        # ELL lane: gather + row-min over this block's (bm, k) tile
        idx = idx_ref[...]
        cand = jnp.take(offers, idx, axis=0) + w_ref[...]
        best = jnp.min(cand, axis=1)
        is_min = (cand == best[:, None]) & (cand < _INF)
        arg = jnp.min(jnp.where(is_min, idx, _INT_MAX), axis=1)

        # overflow lane: scan the WHOLE COO segment, segment-min into this
        # block's rows via scatter-min — entries whose destination falls
        # outside the block drop; empty/tombstoned entries carry w=+inf and
        # never win.  Two passes give the smallest-src-id argmin: the value
        # scatter, then a key scatter gated on matching the row minimum
        # (the clip-gathered minimum of an out-of-block entry may spuriously
        # compare equal, but its key scatter drops too, so it cannot leak).
        blk0 = row0 + pl.program_id(0) * bm
        osrc = osrc_ref[...]
        lrow = odst_ref[...] - blk0
        # scatter mode="drop" only drops indices >= bm — NEGATIVE indices
        # wrap (NumPy semantics), so remap rows before the block to bm
        lrow = jnp.where(lrow >= 0, lrow, bm)
        ocand = jnp.take(offers, osrc, axis=0) + ow_ref[...]
        obest = jnp.full((bm,), _INF).at[lrow].min(ocand, mode="drop")
        row_min = jnp.take(obest, lrow, mode="clip")
        okey = jnp.where((ocand == row_min) & (ocand < _INF), osrc, _INT_MAX)
        oarg = jnp.full((bm,), _INT_MAX).at[lrow].min(okey, mode="drop")

        # lane combine, smallest minimizing src id across both lanes —
        # exactly combine_lanes(), evaluated in registers
        comb = jnp.minimum(best, obest)
        ell_key = jnp.where((best == comb) & (best < _INF), arg, _INT_MAX)
        coo_key = jnp.where((obest == comb) & (obest < _INF), oarg, _INT_MAX)
        best_ref[...] = comb
        arg_ref[...] = jnp.minimum(ell_key, coo_key)

    return kernel


def fused_sliced_relax(dist: jax.Array, active: jax.Array,
                       flat_idx: jax.Array, flat_w: jax.Array,
                       osrc: jax.Array, odst: jax.Array, ow: jax.Array, *,
                       widths: tuple[int, ...], slice_rows: int,
                       interpret: bool | None = None):
    """One fused hybrid wave over the flat sliced-ELL buffer plus the
    overflow COO segment: returns ``(best f32[R], arg i32[R])`` for
    ``R = len(widths) * slice_rows`` rows, already lane-combined —
    bit-identical to the unfused three-dispatch composition.

    ``active`` is the bucket/frontier row mask over offer SOURCES (vertex
    space); pass all-True for an unmasked pull wave.  ``odst`` must be in
    the same row space the groups cover (vertex ids single-device).
    """
    interpret = resolve_interpret(interpret)
    C = ow.shape[0]
    if C == 0:          # static degenerate shape: keep the kernel uniform
        osrc = jnp.zeros(1, jnp.int32)
        odst = jnp.full(1, -1, jnp.int32)
        ow = jnp.full(1, _INF, jnp.float32)
        C = 1
    n = dist.shape[0]
    # re-coalesce the unfused path's 256-row main/remainder split: ONE
    # pallas_call (grid=1, block = the whole run) per distinct-width run,
    # so the overflow COO segment is scanned once per run, not per block
    groups: list[list[int]] = []
    for k, cnt in slice_run_groups(widths, slice_rows):
        if groups and groups[-1][0] == k:
            groups[-1][1] += cnt
        else:
            groups.append([k, cnt])
    bests, args_ = [], []
    off_cells = 0
    off_rows = 0
    for k, cnt in groups:
        rows_g = slice_rows * cnt
        bm = rows_g
        blk = slice(off_cells, off_cells + rows_g * k)
        blk_idx = flat_idx[blk].reshape(rows_g, k)
        blk_w = flat_w[blk].reshape(rows_g, k)
        cost = pl.CostEstimate(
            flops=3 * rows_g * k + 4 * C,
            bytes_accessed=5 * n + 8 * rows_g * k + 12 * C + 8 * rows_g,
            transcendentals=0)
        b, a = pl.pallas_call(
            _mk_kernel(off_rows, bm),
            grid=(rows_g // bm,),
            in_specs=[
                pl.BlockSpec((n,), lambda i: (0,)),       # dist (whole)
                pl.BlockSpec((n,), lambda i: (0,)),       # active (whole)
                pl.BlockSpec((bm, k), lambda i: (i, 0)),  # ELL idx tile
                pl.BlockSpec((bm, k), lambda i: (i, 0)),  # ELL w tile
                pl.BlockSpec((C,), lambda i: (0,)),       # overflow src
                pl.BlockSpec((C,), lambda i: (0,)),       # overflow dst
                pl.BlockSpec((C,), lambda i: (0,)),       # overflow w
            ],
            out_specs=[
                pl.BlockSpec((bm,), lambda i: (i,)),
                pl.BlockSpec((bm,), lambda i: (i,)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((rows_g,), jnp.float32),
                jax.ShapeDtypeStruct((rows_g,), jnp.int32),
            ],
            cost_estimate=cost,
            interpret=interpret,
        )(dist, active, blk_idx, blk_w, osrc, odst, ow)
        bests.append(b)
        args_.append(a)
        off_cells += rows_g * k
        off_rows += rows_g
    return jnp.concatenate(bests), jnp.concatenate(args_)


@partial(jax.jit, static_argnames=("widths", "slice_rows", "interpret"))
def _fused_wave_jit(dist, active, flat_idx, flat_w, osrc, odst, ow, *,
                    widths, slice_rows, interpret=True):
    return fused_sliced_relax(
        dist, active, flat_idx, flat_w, osrc, odst, ow,
        widths=widths, slice_rows=slice_rows, interpret=interpret)


def fused_cost(widths: tuple[int, ...] | list[int], slice_rows: int,
               num_vertices: int, overflow_cap: int) -> dict[str, float]:
    """Analytic flop/byte model of one fused wave — what the pallas_call
    cost_estimate claims, summed over run groups.  ``roofline`` validation
    (tests/test_fused_relax.py) checks the compiled interpret-mode HLO
    against this model via ``roofline/hlo_analysis.py``."""
    C = max(overflow_cap, 1)
    flops = 0.0
    bytes_ = 0.0
    runs: list[list[int]] = []
    for k, cnt in slice_run_groups(tuple(widths), slice_rows):
        if runs and runs[-1][0] == k:
            runs[-1][1] += cnt
        else:
            runs.append([k, cnt])
    for k, cnt in runs:
        rows_g = slice_rows * cnt
        # ELL lane: add + min-reduce + argmin select per cell; overflow
        # lane: one gather+add+scatter-min chain per entry per RUN (one
        # block per run — the whole COO segment is scanned once per run)
        flops += 3.0 * rows_g * k + 4.0 * C
        bytes_ += (5.0 * num_vertices       # dist f32 + active bool
                   + 8.0 * rows_g * k       # idx i32 + w f32 tiles
                   + 12.0 * C               # overflow triplet, per run
                   + 8.0 * rows_g)          # best f32 + arg i32 out
    return {"flops": flops, "bytes": bytes_,
            "intensity": flops / max(bytes_, 1.0)}
