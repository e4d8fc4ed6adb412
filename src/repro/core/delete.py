"""Deletion mode: invalidation + recomputation (paper §4.1, Listings 4/8/9).

Invalidation
------------
The paper floods ``SetToInfinity`` down the successor sets — O(depth) message
waves.  With the implicit-successor representation (children of v are the
vertices whose ``parent`` is v), marking the affected subtree T(v) is
*descendant marking over the parent forest*.  We provide two implementations:

* ``mark_subtree_flood`` — the paper-faithful wave-by-wave flood
  (one round per tree level), and
* ``mark_subtree_doubling`` — beyond-paper pointer doubling: O(log depth)
  rounds.  Each round jumps ``ptr := parent[ptr]`` after folding in
  ``aff |= aff[ptr]``; this is the classic parallel tree-contraction trick and
  is exact because the parent forest is static during invalidation
  (SetToInfinity is the only in-flight message type — paper Appendix A.1).

Recomputation
-------------
Affected vertices get ``dist=inf, parent=-1`` and then *pull* once from all
valid in-neighbours (bulk ``DistanceQuery``), after which ordinary monotone
push relaxation re-converges (bulk ``DistanceUpdate`` responses).  The pull is
a single masked segment-min over edges whose dst is affected; this realizes
"each invalidated vertex queries its incoming neighbours" in one wave.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.state import INF, NO_PARENT, EdgePool, SSSPState
from repro.core import relax


class DeleteStats(NamedTuple):
    invalidation_rounds: jax.Array
    affected: jax.Array          # i32[] — |T|, size of invalidated subtree
    recompute_rounds: jax.Array
    recompute_messages: jax.Array


def mark_subtree_flood(parent: jax.Array, seed: jax.Array,
                       gate: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Paper-faithful successor flood. ``seed``: bool[N]. Returns (aff, rounds).

    ``gate`` (device bool) short-circuits the loop when False — the bucketed
    lazy-deletion path passes ``any(seed)`` so the frequent non-tree deletion
    costs zero flood iterations instead of a full no-op sweep.  ``None``
    preserves the original loop byte-for-byte for the eager epochs."""

    def cond(carry):
        aff, grew, _ = carry
        return grew if gate is None else grew & gate

    def body(carry):
        aff, _, rounds = carry
        # a vertex joins T if its parent is already in T
        child_join = jnp.where(parent >= 0, aff[jnp.clip(parent, 0)], False)
        new = aff | child_join
        return new, jnp.any(new != aff), rounds + 1

    aff, _, rounds = jax.lax.while_loop(cond, body, (seed, jnp.bool_(True), jnp.int32(0)))
    return aff, rounds


def mark_subtree_doubling(parent: jax.Array, seed: jax.Array,
                          gate: jax.Array | None = None
                          ) -> tuple[jax.Array, jax.Array]:
    """Pointer-doubling descendant marking: O(log depth) rounds (beyond-paper).

    ``gate`` as in ``mark_subtree_flood``: an early-exit predicate for the
    lazy path.  Note the loop must otherwise run until the pointers are fully
    collapsed even when ``aff`` stops growing mid-way (gap distributions can
    stall a round and resume), so the gate is the only extra exit."""
    n = parent.shape[0]

    def cond(carry):
        _, _, grew, _ = carry
        return grew if gate is None else grew & gate

    def body(carry):
        aff, ptr, _, rounds = carry
        valid = ptr >= 0
        hop = jnp.where(valid, aff[jnp.clip(ptr, 0)], False)
        new_aff = aff | hop
        # double: ptr := ptr[ptr] (stays -1 once off-tree)
        nxt = jnp.where(valid, ptr[jnp.clip(ptr, 0)], NO_PARENT)
        grew = jnp.any(new_aff != aff) | jnp.any(nxt != ptr)
        return new_aff, nxt, grew, rounds + 1

    aff, _, _, rounds = jax.lax.while_loop(
        cond, body, (seed, parent, jnp.bool_(True), jnp.int32(0))
    )
    return aff, rounds


def pull_once(dist: jax.Array, parent: jax.Array, edges: EdgePool,
              aff: jax.Array, num_vertices: int
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One bulk DistanceQuery wave (Listing 9): affected vertices pull their
    best offer from valid (finite-dist) in-neighbours.  Returns
    (dist', parent', improved) — the improved mask is the push frontier the
    recomputation (or the bucketed drain) continues from.

    Like ``relax.relax_round`` it makes two [E] gathers and two scatters:
    the affected mask applies to the [N] result, and invalid (+inf)
    sources offer +inf, so the candidates of affected rows are exactly the
    live edges from finite-dist in-neighbours."""
    cand = jnp.where(edges.active, dist[edges.src] + edges.w, INF)
    best = jax.ops.segment_min(cand, edges.dst, num_segments=num_vertices)
    improved = aff & (best < dist)
    target = jnp.where(improved, best, -INF)
    hit = cand == target[edges.dst]
    cand_src = jnp.where(hit, edges.src, jnp.int32(2**31 - 1))
    new_parent = jax.ops.segment_min(cand_src, edges.dst,
                                     num_segments=num_vertices)
    return (jnp.where(improved, best, dist),
            jnp.where(improved, new_parent, parent), improved)


@partial(jax.jit, static_argnames=("num_vertices", "use_doubling"))
def invalidate_and_recompute(
    sssp: SSSPState,
    edges: EdgePool,
    seed: jax.Array,
    *,
    num_vertices: int,
    use_doubling: bool = True,
) -> tuple[SSSPState, DeleteStats]:
    """Full deletion epoch given invalidation seeds (bool[N]).

    ``seed`` marks heads of deleted tree edges (possibly several — consecutive
    deletions may be batched; Appendix A's argument covers the union of
    subtrees since invalidation completes before any recomputation starts).

    An all-false seed (non-tree deletion) is safe and cheap: the state comes
    back unchanged and every stat is 0 — so callers need no blocking
    ``bool(jnp.any(seed))`` check before dispatching (DESIGN.md §2.4).
    """
    any_seed = jnp.any(seed)
    mark = mark_subtree_doubling if use_doubling else mark_subtree_flood
    aff, inv_rounds = mark(sssp.parent, seed)
    # Never invalidate the source itself (its dist is 0 by definition; a
    # deleted edge cannot be on the source's path to itself).
    aff = aff.at[sssp.source].set(False)

    dist = jnp.where(aff, INF, sssp.dist)
    parent = jnp.where(aff, NO_PARENT, sssp.parent)

    # --- Recomputation phase -------------------------------------------------
    # Bulk DistanceQuery: pull from *valid* (finite-dist) in-neighbours into
    # affected vertices only.  Edges out of affected vertices are excluded for
    # this wave (their dist is inf -> they offer nothing), matching Listing 9's
    # "if connected, reply with best offer".
    dist, parent, improved = pull_once(dist, parent, edges, aff, num_vertices)

    # Then ordinary monotone relaxation from the re-seeded vertices drains the
    # epoch (responses propagate down the rebuilt subtree).
    state1 = SSSPState(dist=dist, parent=parent, source=sssp.source)
    state2, stats = relax.relax_until_converged(
        state1, edges, improved, num_vertices=num_vertices
    )
    zero = jnp.int32(0)
    return state2, DeleteStats(
        invalidation_rounds=jnp.where(any_seed, inv_rounds, zero),
        affected=jnp.sum(aff.astype(jnp.int32)),
        recompute_rounds=jnp.where(any_seed, stats.rounds + 1, zero),
        recompute_messages=jnp.where(
            any_seed,
            stats.messages + jnp.sum(improved.astype(jnp.int32)), zero),
    )


def deletion_seed_for_edges(
    sssp: SSSPState,
    del_src: jax.Array,
    del_dst: jax.Array,
    num_vertices: int,
) -> jax.Array:
    """Listing 4: only deletions of *tree* edges (parent[head]==tail) seed
    invalidation; non-tree deletions need no algorithmic work."""
    is_tree = sssp.parent[del_dst] == del_src
    f = jnp.zeros((num_vertices,), jnp.bool_)
    safe = jnp.clip(del_dst, 0, num_vertices - 1)
    return f.at[safe].max(is_tree & (del_dst >= 0))


@partial(jax.jit, static_argnames=("num_vertices",))
def deletion_seed_for_edges_batched(
    sssp: SSSPState,
    del_src: jax.Array,
    del_dst: jax.Array,
    num_vertices: int,
) -> jax.Array:
    """Per-lane [S, N] seeds for a batched multi-source engine (DESIGN.md
    §8): whether a deleted edge is a tree edge depends on each lane's
    parent forest.  Jitted so the per-deletion hot path stays on the pjit
    fast path."""
    return jax.vmap(
        lambda s: deletion_seed_for_edges(s, del_src, del_dst,
                                          num_vertices))(sssp)
