"""Monotone (insertion-mode) relaxation — the bulk equivalent of the paper's
``DistanceUpdate`` flood (Listing 3/5).

One *round* delivers every in-flight ``DistanceUpdate`` simultaneously:

    cand_e  = dist[src_e] + w_e                (for active, frontier-masked e)
    best_v  = min over {e : dst_e == v} cand_e (segment_min)
    improved_v = best_v < dist_v
    parent_v  := src of an edge attaining best_v (ties -> smallest src id)

and the engine loops rounds until no vertex improves.  Monotonicity of the
paper's insertion mode (Appendix A) makes this reordering exact: the fixpoint
is the same as under any asynchronous delivery order.

Frontier masking reproduces the paper's work-efficiency: only edges whose
source improved in the previous round can deliver a better distance, so all
other edges are masked out of the segment reduction.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.state import INF, NO_PARENT, EdgePool, SSSPState


class RelaxStats(NamedTuple):
    rounds: jax.Array          # i32[] — BSP rounds until convergence
    messages: jax.Array        # i32[] — total "DistanceUpdate deliveries" (improvements)


def relax_round(
    dist: jax.Array,
    parent: jax.Array,
    edges: EdgePool,
    frontier: jax.Array,
    *,
    num_vertices: int,
    tie_perm: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One bulk message wave. Returns (dist, parent, new_frontier, n_improved).

    Every [E] gather or scatter costs a full random pass over the pool (on
    a TPU v5e about 0.15 s at 2^24 slots, against a few ms for an [N]
    elementwise op), so the wave makes two gathers and two scatters: the
    frontier mask rides on the [N] offer vector (non-offering sources offer
    +inf), and the tie-break compares against one [N] target vector that is
    -inf wherever the vertex did not improve.  ``cand`` is finite exactly
    on live frontier edges, so both forms select the same edges."""
    offers = jnp.where(frontier, dist, INF)
    cand = jnp.where(edges.active, offers[edges.src] + edges.w, INF)
    best = jax.ops.segment_min(cand, edges.dst, num_segments=num_vertices)
    best = jnp.minimum(best, INF)  # segment_min fills empty segments with +inf already
    improved = best < dist

    # argmin edge per dst, tie-break by smallest src id so the result is
    # deterministic (the paper's async runtime is nondeterministic here; a
    # deterministic rule keeps tests and stability metrics reproducible).
    # ``tie_perm`` (i32[N] permutation) overrides the tie order — the
    # ReMo-from-scratch baseline draws a fresh permutation per query to
    # model the async runtime's run-to-run arbitrariness among equally
    # valid shortest-path trees (paper §5.4).
    target = jnp.where(improved, best, -INF)
    hit = cand == target[edges.dst]
    key = edges.src if tie_perm is None else tie_perm[edges.src]
    cand_key = jnp.where(hit, key, jnp.int32(2**31 - 1))
    best_key = jax.ops.segment_min(cand_key, edges.dst,
                                   num_segments=num_vertices)
    if tie_perm is None:
        new_parent = best_key
    else:
        win = hit & (cand_key == best_key[edges.dst])
        cand_src = jnp.where(win, edges.src, jnp.int32(2**31 - 1))
        new_parent = jax.ops.segment_min(cand_src, edges.dst,
                                         num_segments=num_vertices)

    dist = jnp.where(improved, best, dist)
    parent = jnp.where(improved, new_parent, parent)
    return dist, parent, improved, jnp.sum(improved.astype(jnp.int32))


def converged_loop(dist: jax.Array, parent: jax.Array, frontier: jax.Array,
                   wave, *, max_rounds: int = 0,
                   track_occupancy: bool = False
                   ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                              jax.Array, jax.Array]:
    """The shared wave-to-fixpoint driver: loop ``wave(dist, parent,
    frontier) -> (dist, parent, improved)`` while the frontier is non-empty,
    counting rounds and improvement messages exactly as the original dense
    loop did.  Both the dense epochs here and the frontier-compacted epochs
    (core/frontier.py, DESIGN.md §12) run through this loop, so their
    (rounds, messages) accounting matches by construction.

    ``track_occupancy=True`` is the ladder's accounting: ``wave`` returns a
    fourth value, 1 when the wave ran compacted and 0 when it fell back
    dense, and the loop sums those and ``sum(frontier)`` per wave
    (device-side, no host sync — §2.4); otherwise both slots ride along at
    0.  Returns (dist, parent, rounds, messages, occupancy, sparse waves).
    """

    def cond(carry):
        _, _, frontier, rounds, _, _, _ = carry
        go = jnp.any(frontier)
        if max_rounds:
            go = go & (rounds < max_rounds)
        return go

    def body(carry):
        dist, parent, frontier, rounds, msgs, occ, sparse = carry
        if track_occupancy:
            occ = occ + jnp.sum(frontier.astype(jnp.int32))
            dist, parent, improved, took = wave(dist, parent, frontier)
            sparse = sparse + took
        else:
            dist, parent, improved = wave(dist, parent, frontier)
        return (dist, parent, improved, rounds + 1,
                msgs + jnp.sum(improved.astype(jnp.int32)), occ, sparse)

    zero = jnp.int32(0)
    dist, parent, _, rounds, msgs, occ, sparse = jax.lax.while_loop(
        cond, body, (dist, parent, frontier, zero, zero, zero, zero))
    return dist, parent, rounds, msgs, occ, sparse


@partial(jax.jit, static_argnames=("num_vertices", "max_rounds"))
def relax_until_converged(
    sssp: SSSPState,
    edges: EdgePool,
    frontier: jax.Array,
    *,
    num_vertices: int,
    max_rounds: int = 0,
    tie_perm: jax.Array | None = None,
) -> tuple[SSSPState, RelaxStats]:
    """Run rounds until fixpoint (== the paper's epoch drain).

    ``max_rounds=0`` means unbounded (guaranteed to terminate: distances are
    strictly decreasing and bounded below — Appendix A.1).  A positive bound
    is used by the straggler-mitigation path of the distributed engine.
    """

    def wave(dist, parent, frontier):
        dist, parent, improved, _ = relax_round(
            dist, parent, edges, frontier, num_vertices=num_vertices,
            tie_perm=tie_perm)
        return dist, parent, improved

    dist, parent, rounds, msgs, _, _ = converged_loop(
        sssp.dist, sssp.parent, frontier, wave, max_rounds=max_rounds)
    return (
        SSSPState(dist=dist, parent=parent, source=sssp.source),
        RelaxStats(rounds=rounds, messages=msgs),
    )


def full_frontier(num_vertices: int) -> jax.Array:
    return jnp.ones((num_vertices,), jnp.bool_)


def frontier_from_vertices(vertices: jax.Array, num_vertices: int) -> jax.Array:
    """Boolean frontier from a (possibly padded with -1) vertex id list."""
    f = jnp.zeros((num_vertices,), jnp.bool_)
    safe = jnp.clip(vertices, 0, num_vertices - 1)
    upd = vertices >= 0
    return f.at[safe].max(upd)
