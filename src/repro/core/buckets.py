"""Bucketed (delta-stepping) wave schedule — DESIGN.md §9.

The rounds schedule (core/relax.py) settles EVERY epoch to fixpoint with one
global wave per round; at high delete probability the per-epoch converge
loops dominate ingest wall-clock (ROADMAP open item #2).  The bucketed
schedule exploits the same property that makes the paper's asynchronous
runtime correct — insertion-mode relaxation is monotone, so ANY delivery
order reaches the same fixpoint — to defer convergence work and batch it
into distance-class buckets:

  * ingest epochs do only the work the paper's correctness argument needs
    *immediately*: deletions run invalidation (seed -> mark -> SetToInfinity)
    right away, but the recomputation pull and all push waves are deferred;
    insertions just enqueue the tails as push obligations;
  * the deferred work lives in a ``PendingState``: ``push`` marks vertices
    whose current distance has not been offered to their out-neighbours yet,
    ``pull`` marks invalidated vertices awaiting their bulk DistanceQuery;
  * a *drain* (run at query / checkpoint / whenever a converged tree is
    needed) settles the pending set one bucket at a time: each wave only
    activates pending vertices whose tentative distance falls in the lowest
    nonempty bucket ``[q*w, (q+1)*w)`` — the delta-stepping discipline —
    so every vertex pushes a settled value exactly once per improvement
    chain instead of re-cascading per epoch.

Why the final state is bit-identical to the rounds schedule: the fixpoint of
the monotone Bellman operator over the live edge set is unique, and every
candidate is a single binary ``dist[src] + w`` float add, so deferred and
eager settling compute the same distances bit-for-bit.  Parents follow
because at the last improving wave of any vertex every candidate equal to
its final distance comes from a genuinely minimizing in-edge (a stale source
distance would contradict fixpointness), and all schedules break ties among
those by the same smallest-src-id rule.  See DESIGN.md §9 for the invariant
("every finite distance is witnessed by its parent chain over live edges")
that makes interleaved deletions safe under deferral.

Round accounting: waves executed, same as the rounds schedule — but the
totals are *not* comparable wave-for-wave, so tests gate a rounds *budget*
(bucketed total <= rounds-schedule total) instead of exact equality.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import delete as del_mod
from repro.core import relax
from repro.core.relax import RelaxStats
from repro.core.state import INF, NO_PARENT, EdgePool, SSSPState

WAVE_SCHEDULES = ("rounds", "buckets")


class PendingState(NamedTuple):
    """Deferred-work masks carried across bucketed epochs (bool[N] each, or
    [S, N] on a batched multi-source engine)."""
    push: jax.Array   # settled-but-unoffered vertices (push obligations)
    pull: jax.Array   # invalidated vertices awaiting the bulk DistanceQuery


def empty_pending(num_vertices: int,
                  num_sources: int | None = None) -> PendingState:
    shape = ((num_vertices,) if num_sources is None
             else (num_sources, num_vertices))
    return PendingState(push=jnp.zeros(shape, jnp.bool_),
                        pull=jnp.zeros(shape, jnp.bool_))


def pending_occupancy(pend: PendingState) -> tuple[jax.Array, jax.Array]:
    """Lazy device occupancy of the pending masks — (push, pull) counts as
    i32 device scalars, or [S] per-lane vectors on a batched engine.  Fed
    to the obs counter registry at drain entry (DESIGN.md §10.1): no host
    sync, just one cheap eager reduction the registry accumulates."""
    return (jnp.sum(pend.push.astype(jnp.int32), axis=-1),
            jnp.sum(pend.pull.astype(jnp.int32), axis=-1))


def bucket_limit(cur: jax.Array, bucket_width: float) -> jax.Array:
    """Exclusive upper bound of the lowest nonempty bucket given the minimum
    pending distance ``cur``.  ``bucket_width=inf`` degenerates to one
    all-encompassing bucket (== the plain converge drain)."""
    width = jnp.float32(bucket_width)
    return (jnp.floor(cur / width) + 1.0) * width


def bucket_active(dist: jax.Array, push: jax.Array,
                  bucket_width: float) -> jax.Array:
    """Active mask for one drain wave: pending vertices inside the lowest
    nonempty bucket.  The strict-progress guard ``dist == cur`` keeps the
    minimum pending vertex active even if float rounding ever lands the
    bucket limit at or below ``cur``."""
    cur = jnp.min(jnp.where(push, dist, INF))
    limit = bucket_limit(cur, bucket_width)
    return push & ((dist < limit) | (dist == cur))


@jax.jit
def enqueue_push(pend: PendingState, frontier: jax.Array,
                 dist: jax.Array) -> PendingState:
    """Fold an ADD epoch's frontier (inserted-edge tails) into the pending
    push set — the bucketed rendering of 'relax from the tails', deferred.
    Currently-unreachable tails (dist=inf) are pruned: their offers are
    worthless now, and if a later wave ever improves them the improved mask
    re-enqueues them with all their out-edges.  ``frontier`` is the shared
    [N] tail mask; ``dist`` may be [N] or batched [S, N] (broadcasts)."""
    return PendingState(push=pend.push | (frontier & jnp.isfinite(dist)),
                        pull=pend.pull)


# ------------------------------------------------------------ lazy deletion --
def _lazy_invalidate_one(sssp: SSSPState, pend: PendingState,
                         del_src: jax.Array, del_dst: jax.Array,
                         *, num_vertices: int, use_doubling: bool
                         ) -> tuple[SSSPState, PendingState,
                                    "del_mod.DeleteStats"]:
    """Invalidation-only deletion epoch on one tree: seed from the CURRENT
    witness forest, mark the dependent subtree, SetToInfinity — and defer
    the recomputation into the pending state.  Correct on a partially
    settled tree because ``parent`` always witnesses ``dist`` over live
    edges: exactly the bounds that depended on the deleted edge are the
    marked subtree."""
    is_tree = sssp.parent[del_dst] == del_src
    safe = jnp.clip(del_dst, 0, num_vertices - 1)
    seed = jnp.zeros((num_vertices,), jnp.bool_).at[safe].max(
        is_tree & (del_dst >= 0))
    any_seed = jnp.any(seed)
    mark = (del_mod.mark_subtree_doubling if use_doubling
            else del_mod.mark_subtree_flood)
    aff, inv_rounds = mark(sssp.parent, seed, gate=any_seed)
    aff = aff.at[sssp.source].set(False)

    dist = jnp.where(aff, INF, sssp.dist)
    parent = jnp.where(aff, NO_PARENT, sssp.parent)
    # invalidated vertices stop offering; they re-enter via the drain pull
    pend = PendingState(push=pend.push & jnp.isfinite(dist),
                        pull=pend.pull | aff)
    zero = jnp.int32(0)
    stats = del_mod.DeleteStats(
        invalidation_rounds=jnp.where(any_seed, inv_rounds, zero),
        affected=jnp.sum(aff.astype(jnp.int32)),
        recompute_rounds=zero, recompute_messages=zero)
    return SSSPState(dist=dist, parent=parent, source=sssp.source), pend, stats


@partial(jax.jit, static_argnames=("num_vertices", "use_doubling"))
def lazy_delete(sssp: SSSPState, edges: EdgePool, pend: PendingState,
                del_src: jax.Array, del_dst: jax.Array, slots: jax.Array,
                *, num_vertices: int, use_doubling: bool = True):
    """ONE fused device dispatch per deletion event: deactivate the slots,
    seed + mark + invalidate, update the pending masks.  Everything the
    rounds schedule spreads over three dispatches plus a converge loop."""
    edges = EdgePool(src=edges.src, dst=edges.dst, w=edges.w,
                     active=edges.active.at[slots].set(False))
    sssp, pend, stats = _lazy_invalidate_one(
        sssp, pend, del_src, del_dst, num_vertices=num_vertices,
        use_doubling=use_doubling)
    return sssp, edges, pend, stats


@partial(jax.jit, static_argnames=("num_vertices", "use_doubling"))
def lazy_delete_batched(sssp: SSSPState, edges: EdgePool, pend: PendingState,
                        del_src: jax.Array, del_dst: jax.Array,
                        slots: jax.Array, *, num_vertices: int,
                        use_doubling: bool = True):
    """Batched [S, N] lanes: the edge pool is shared (deactivated once), the
    seeds/marks are per-lane — whether a deleted edge is a tree edge depends
    on each lane's witness forest."""
    edges = EdgePool(src=edges.src, dst=edges.dst, w=edges.w,
                     active=edges.active.at[slots].set(False))
    sssp, pend, stats = jax.vmap(
        lambda s, pd: _lazy_invalidate_one(
            s, pd, del_src, del_dst, num_vertices=num_vertices,
            use_doubling=use_doubling))(sssp, pend)
    return sssp, edges, pend, stats


# ------------------------------------------------------------------- drains --
def run_drain(dist: jax.Array, parent: jax.Array, pend: PendingState,
              *, bucket_width: float,
              wave: Callable[[jax.Array, jax.Array, jax.Array], tuple],
              pull_wave: Callable[[jax.Array, jax.Array, jax.Array], tuple],
              track_occupancy: bool = False):
    """Generic drain driver, shared by all backends' jitted entry points.

    ``wave(dist, parent, active) -> (dist', parent', improved)`` is one
    frontier-masked relaxation wave; ``pull_wave(dist, parent, aff)`` is the
    backend's bulk DistanceQuery into the accumulated invalidated set.  Both
    must evaluate the same candidate sets with the same smallest-src-id tie
    rule as the rounds schedule, so the drain's wave sequence — and hence
    (dist, parent) AND the round/message counters — is bit-identical across
    backends.

    Phase structure: one cond-gated pull (counted as a round when it runs),
    then threshold-paced waves.  The bucket limit is recomputed from the
    minimum pending distance every wave, so settling the lowest bucket to
    fixpoint and advancing to the next is emergent — no inner loop, and the
    limit is one broadcast scalar (the sharded drain computes it from the
    already-allgathered offers: no new collectives).

    ``track_occupancy=True`` (the frontier-compacted drain, DESIGN.md §12)
    is the ladder's accounting, as in ``relax.converged_loop``: ``wave``
    returns a fourth value (1 when it ran compacted), and the drain also
    returns the summed active-vertex counts (the ``frontier_occupancy``
    obs signal per §2.4) and the compacted waves; the 3-tuple return shape
    is kept for the other callers.
    """
    any_pull = jnp.any(pend.pull)

    def do_pull(args):
        d, p = args
        return pull_wave(d, p, pend.pull)

    def no_pull(args):
        d, p = args
        return d, p, jnp.zeros_like(pend.pull)

    dist, parent, imp = jax.lax.cond(any_pull, do_pull, no_pull,
                                     (dist, parent))
    push = pend.push | imp
    rounds0 = jnp.where(any_pull, jnp.int32(1), jnp.int32(0))
    msgs0 = jnp.sum(imp.astype(jnp.int32))

    def cond(carry):
        _, _, push, _, _, _, _ = carry
        return jnp.any(push)

    def body(carry):
        dist, parent, push, rounds, msgs, occ, sparse = carry
        active = bucket_active(dist, push, bucket_width)
        if track_occupancy:
            occ = occ + jnp.sum(active.astype(jnp.int32))
            dist, parent, improved, took = wave(dist, parent, active)
            sparse = sparse + took
        else:
            dist, parent, improved = wave(dist, parent, active)
        push = (push & ~active) | improved
        return (dist, parent, push, rounds + 1,
                msgs + jnp.sum(improved.astype(jnp.int32)), occ, sparse)

    zero = jnp.int32(0)
    dist, parent, _, rounds, msgs, occ, sparse = jax.lax.while_loop(
        cond, body, (dist, parent, push, rounds0, msgs0, zero, zero))
    stats = RelaxStats(rounds=rounds, messages=msgs)
    if track_occupancy:
        return dist, parent, stats, occ, sparse
    return dist, parent, stats


@partial(jax.jit, static_argnames=("num_vertices", "bucket_width"))
def segment_drain(sssp: SSSPState, edges: EdgePool, pend: PendingState,
                  *, num_vertices: int, bucket_width: float
                  ) -> tuple[SSSPState, PendingState, RelaxStats]:
    """COO scatter-min drain (the segment backend's bucketed settle)."""

    def wave(dist, parent, active):
        dist, parent, improved, _ = relax.relax_round(
            dist, parent, edges, active, num_vertices=num_vertices)
        return dist, parent, improved

    def pull_wave(dist, parent, aff):
        return del_mod.pull_once(dist, parent, edges, aff, num_vertices)

    dist, parent, stats = run_drain(
        sssp.dist, sssp.parent, pend, bucket_width=bucket_width,
        wave=wave, pull_wave=pull_wave)
    return (SSSPState(dist=dist, parent=parent, source=sssp.source),
            empty_pending(num_vertices), stats)


@partial(jax.jit, static_argnames=("num_vertices", "bucket_width"))
def segment_drain_batched(sssp: SSSPState, edges: EdgePool,
                          pend: PendingState, *, num_vertices: int,
                          bucket_width: float):
    """[S, N] lanes: vmapped drain — jax's while_loop batching rule freezes
    each lane's carry once its own pending set empties, so per-lane stats
    stay bit-identical to unbatched runs (see base.RelaxBackend notes)."""
    return jax.vmap(
        lambda s, pd: segment_drain(s, edges, pd, num_vertices=num_vertices,
                                    bucket_width=bucket_width))(sssp, pend)
