"""Distributed SSSP-Del: shard_map over a vertex-partitioned device mesh.

Shared-nothing mapping (paper §3 -> TPU):

  * vertices are range-partitioned over the *flattened* mesh axes (every chip
    owns ``Npp = N/P`` contiguous vertices and their SSSP state);
  * edges live with the partition of their **dst** (each chip owns up to
    ``Epp`` in-edges of its vertices) so the per-round scatter-min is local;
  * the shard-local candidate evaluation is a pluggable *wave*
    (``wave(offers) -> (best, arg)``, DESIGN.md §7.2): the exchange
    strategies below assemble the global ``offers`` vector (dist masked to
    the offering set) and the wave — segment-min over the pool slice by
    default, an ELL/sliced gather-min when the sharded dynamic engine plugs
    a relaxation backend in — reduces it per owned row with the shared
    smallest-src-id tie-break;
  * the only cross-partition traffic is the paper's "messages": ``dist[src]``
    offers.  Two exchange strategies:
      - ``"allgather"`` (paper-faithful bulk): all_gather the dist (+frontier)
        vectors each round — the BSP rendering of "send DistanceUpdate to all
        out-neighbours";
      - ``"delta"`` (beyond-paper): each round all_gathers only a fixed-size
        buffer of (index, value) pairs for vertices that *improved* last round
        — message-compression; falls back to dense gather on overflow.
  * convergence is detected with a ``psum`` over per-partition improvement
    counts (the paper's distributed epoch/termination detection).

Everything below is pure shard_map + lax collectives; the same code lowers on
1 CPU device (P=1), 8 forced host devices (tests) and the 256/512-chip
production meshes (launch/dryrun.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.backends.segment import shard_segment_wave
from repro.core.state import INF, NO_PARENT
from repro.graphs import csr as csr_mod
from repro.graphs import partition as part_mod

BIG = jnp.int32(2**31 - 1)


def inactive_dst_layout(P: int, npp: int, epp: int) -> np.ndarray:
    """dst ids for an all-inactive (or padding) pool slot range: every slot
    points at its owner partition's first row, keeping the shard-local
    segment ids ``dst - row0`` inside [0, npp).  The single source of truth
    for the padding-row invariant (place_edges, the sharded engine's empty
    pools)."""
    return np.repeat(np.arange(P, dtype=np.int64) * npp, epp).astype(np.int32)


def per_partition_occupancy(mask: jax.Array, P: int, npp: int) -> jax.Array:
    """Live counts of a sharded bool vertex mask for the obs counter
    registry (DESIGN.md §10.1): an [N] mask reshapes to (P, npp) and sums
    shard-local rows — each partition reduces only the window it owns, no
    collective, no host sync — yielding a [P] per-partition vector the
    registry accumulates lazily.  A batched [S, N] mask reduces over the
    vertex axis instead ([S] per-lane totals, folded through the existing
    sharded-sum machinery — still no new collective pattern)."""
    if mask.ndim == 2:
        return jnp.sum(mask.astype(jnp.int32), axis=-1)
    return jnp.sum(mask.astype(jnp.int32).reshape(P, npp), axis=-1)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    num_vertices: int        # padded: divisible by P
    edges_per_part: int      # static per-partition edge capacity
    mesh_axes: tuple[str, ...]  # axes to flatten into the vertex partition
    exchange: str = "allgather"  # or "delta"
    delta_cap: int = 4096    # per-part (idx,val) slots for "delta" exchange
    max_rounds: int = 0      # 0 = run to fixpoint; >0 = straggler bound


def _flat_axis_size(mesh: Mesh, names: Sequence[str]) -> int:
    s = 1
    for n in names:
        s *= mesh.shape[n]
    return s


class DistributedSSSP:
    """Builds the jitted shard_map epoch functions for a given mesh."""

    def __init__(self, mesh: Mesh, cfg: DistConfig):
        self.mesh = mesh
        self.cfg = cfg
        self.P = _flat_axis_size(mesh, cfg.mesh_axes)
        assert cfg.num_vertices % self.P == 0, (
            f"num_vertices {cfg.num_vertices} must divide P={self.P}")
        self.npp = cfg.num_vertices // self.P
        ax = cfg.mesh_axes
        self.vspec = P(ax)          # vertex arrays: sharded dim 0
        self.espec = P(ax)          # edge arrays: sharded dim 0 (dst-owner order)
        self.rspec = P()            # replicated scalars
        # batched multi-source vertex arrays [S, N]: source axis replicated,
        # vertex axis sharded (serving layer, DESIGN.md §8)
        self.vspec_ms = P(None, ax)

    # -------------------------------------------------------------- sharding
    def vertex_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.vspec)

    def vertex_sharding_ms(self) -> NamedSharding:
        """Sharding for stacked [S, N] multi-source vertex arrays."""
        return NamedSharding(self.mesh, self.vspec_ms)

    def edge_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.espec)

    # ------------------------------------------------------------ partition
    def place_edges(self, src: np.ndarray, dst: np.ndarray, w: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Host-side: bucket edges by dst partition, pad each bucket to Epp.

        Returns (src, dst, w, active) of shape (P*Epp,) in partition-major
        order — the layout the edge sharding expects.  Fully numpy-vectorized
        (DESIGN.md §2.5): a stable owner sort plus a per-owner rank gives each
        edge its flat output position — no per-partition Python copy loop.
        """
        P_, npp, epp = self.P, self.npp, self.cfg.edges_per_part
        owner = np.minimum(np.asarray(dst, np.int64) // npp, P_ - 1)
        counts = np.bincount(owner, minlength=P_)
        if len(owner) and counts.max() > epp:
            raise ValueError(f"partition overflow: max {counts.max()} > Epp {epp}"
                             " — raise edges_per_part or rebalance")
        order = np.argsort(owner, kind="stable")
        owner_s = owner[order]
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        rank = np.arange(len(order)) - starts[owner_s]
        pos = owner_s * epp + rank
        out_src = np.zeros(P_ * epp, np.int32)
        out_dst = inactive_dst_layout(P_, npp, epp)
        out_w = np.zeros(P_ * epp, np.float32)
        out_act = np.zeros(P_ * epp, np.bool_)
        out_src[pos] = src[order]
        out_dst[pos] = dst[order]
        out_w[pos] = w[order]
        out_act[pos] = True
        return out_src, out_dst, out_w, out_act

    # --------------------------------------------------------------- epochs
    def _apply_wave(self, dist_sh, parent_sh, wave, offers):
        """Shared tail of every round: evaluate the local wave on the
        assembled offers and fold improvements into (dist, parent)."""
        best, arg = wave(offers)
        improved = best < dist_sh
        dist_sh = jnp.where(improved, best, dist_sh)
        parent_sh = jnp.where(improved, arg, parent_sh)
        return dist_sh, parent_sh, improved

    def _round_allgather(self, dist_sh, parent_sh, frontier_sh, wave):
        """One BSP message wave with dense dist/frontier exchange.  Sources
        outside the frontier offer +inf — the offers-vector rendering of the
        old per-edge ``active & frontier[src]`` mask (bit-identical)."""
        ax = self.cfg.mesh_axes
        dist_full = jax.lax.all_gather(dist_sh, ax, tiled=True)
        front_full = jax.lax.all_gather(frontier_sh, ax, tiled=True)
        offers = jnp.where(front_full, dist_full, INF)
        return self._apply_wave(dist_sh, parent_sh, wave, offers)

    def _round_delta(self, dist_sh, parent_sh, frontier_sh, wave, row0):
        """Delta-compressed wave: exchange only (idx,val) of improved vertices.

        Each partition packs the indices of its frontier vertices into a
        fixed ``delta_cap`` buffer (global ids; slot 0-padded with id=-1),
        all_gathers the small buffers, scatters them into a local copy of the
        *stale* dist vector, and proceeds as usual.  Overflow falls back to a
        dense all_gather for that round (flagged via psum).
        """
        ax = self.cfg.mesh_axes
        cap = self.cfg.delta_cap
        n_front = jnp.sum(frontier_sh.astype(jnp.int32))
        overflow = n_front > cap
        any_overflow = jax.lax.psum(overflow.astype(jnp.int32), ax) > 0

        # pack local frontier (idx, dist) — global ids
        local_ids = row0 + jnp.arange(self.npp, dtype=jnp.int32)
        order = jnp.argsort(~frontier_sh)  # frontier first (stable)
        take = order[:cap]
        sel = frontier_sh[take]
        pack_idx = jnp.where(sel, local_ids[take], -1)
        pack_val = jnp.where(sel, dist_sh[take], INF)

        all_idx = jax.lax.all_gather(pack_idx, ax, tiled=True)   # (P*cap,)
        all_val = jax.lax.all_gather(pack_val, ax, tiled=True)

        def sparse_dist():
            base = jnp.full((self.cfg.num_vertices,), INF, dist_sh.dtype)
            safe = jnp.clip(all_idx, 0, self.cfg.num_vertices - 1)
            return base.at[safe].min(jnp.where(all_idx >= 0, all_val, INF))

        def dense_dist():
            return jax.lax.all_gather(dist_sh, ax, tiled=True)

        # No separate frontier gather: in the sparse case the offers are
        # +inf for every non-frontier src, which masks those candidates; in
        # the dense-fallback round all sources offer (a superset — safe,
        # costs one extra wave's work only on overflow rounds).
        offers = jax.lax.cond(any_overflow, dense_dist, sparse_dist)
        return self._apply_wave(dist_sh, parent_sh, wave, offers)

    def _relax_body(self, dist_sh, parent_sh, frontier_sh, wave):
        """Relaxation rounds to fixpoint with the given local wave.  Returns
        (dist, parent, rounds, messages); ``messages`` counts DistanceUpdate
        deliveries (improvements summed over partitions) — same semantics as
        core/relax.RelaxStats, for any backend's wave."""
        ax = self.cfg.mesh_axes
        row0 = (jnp.int32(self._flat_index()) * self.npp)

        def rnd(dist, parent, frontier):
            if self.cfg.exchange == "delta":
                return self._round_delta(dist, parent, frontier, wave, row0)
            return self._round_allgather(dist, parent, frontier, wave)

        def cond(carry):
            _, _, _, go, rounds, _ = carry
            keep = go
            if self.cfg.max_rounds:
                keep = keep & (rounds < self.cfg.max_rounds)
            return keep

        def body(carry):
            dist, parent, frontier, _, rounds, msgs = carry
            dist, parent, improved = rnd(dist, parent, frontier)
            n_imp = jax.lax.psum(jnp.sum(improved.astype(jnp.int32)), ax)
            return dist, parent, improved, n_imp > 0, rounds + 1, msgs + n_imp

        init_go = jax.lax.psum(
            jnp.sum(frontier_sh.astype(jnp.int32)), ax) > 0
        dist_sh, parent_sh, _, _, rounds, msgs = jax.lax.while_loop(
            cond, body, (dist_sh, parent_sh, frontier_sh, init_go,
                         jnp.int32(0), jnp.int32(0)))
        return dist_sh, parent_sh, rounds, msgs

    def _flat_index(self):
        """Flattened partition index from the (possibly multiple) mesh axes."""
        idx = jnp.int32(0)
        for name in self.cfg.mesh_axes:
            idx = idx * self.mesh.shape[name] + jax.lax.axis_index(name)
        return idx

    # ---- public jitted entry points ----------------------------------------
    def make_relax_epoch(self):
        """epoch(dist, parent, frontier, esrc, edst, ew, eact) -> (dist, parent, rounds)"""
        cfg = self.cfg

        @jax.jit
        @partial(jax.shard_map, mesh=self.mesh,
                 in_specs=(self.vspec, self.vspec, self.vspec,
                           self.espec, self.espec, self.espec, self.espec),
                 out_specs=(self.vspec, self.vspec, self.rspec),
                 check_vma=False)
        def epoch(dist, parent, frontier, esrc, edst, ew, eact):
            row0 = jnp.int32(self._flat_index()) * self.npp
            wave = shard_segment_wave(esrc, edst, ew, eact, row0, self.npp)
            d, p, r, _ = self._relax_body(dist, parent, frontier, wave)
            return d, p, r

        return epoch

    def make_delete_epoch(self):
        """delete(dist, parent, seed, esrc, edst, ew, eact) -> (dist, parent, rounds)

        seed: bool[N] (sharded) marking invalidation roots (heads of deleted
        tree edges; computed host-side or by ``seed_from_deletions`` below).
        Performs: pointer-doubling subtree marking -> invalidate -> pull ->
        push-relax to fixpoint.  eact must already exclude the deleted edges.
        """
        ax = self.cfg.mesh_axes

        @jax.jit
        @partial(jax.shard_map, mesh=self.mesh,
                 in_specs=(self.vspec, self.vspec, self.vspec,
                           self.espec, self.espec, self.espec, self.espec),
                 out_specs=(self.vspec, self.vspec, self.rspec),
                 check_vma=False)
        def delete_epoch(dist, parent, seed, esrc, edst, ew, eact):
            row0 = jnp.int32(self._flat_index()) * self.npp
            wave = shard_segment_wave(esrc, edst, ew, eact, row0, self.npp)

            if self.cfg.exchange == "delta":
                aff, inv_rounds = self._invalidate_delta(parent, seed, row0)
            else:
                aff, inv_rounds = self._invalidate_doubling(parent, seed)

            dist = jnp.where(aff, INF, dist)
            parent = jnp.where(aff, NO_PARENT, parent)

            if self.cfg.exchange == "delta":
                dist, parent, rounds, _ = self._recompute_delta(
                    dist, parent, aff, esrc, edst, eact, wave, row0)
            else:
                dist, parent, rounds, _ = self._recompute_pull_push(
                    dist, parent, aff, wave)
            return dist, parent, rounds + inv_rounds

        return delete_epoch

    # -------------------------------------------------- recomputation impls
    # Shared by the static delete epoch above and the sharded dynamic
    # engine's deletion epochs (core/dist_engine.py) — one implementation so
    # the bit-identical equivalence contract has a single source of truth,
    # for ANY backend's wave.  Both return (dist, parent, rounds, messages)
    # with the same semantics as core/delete.DeleteStats'
    # recompute_{rounds,messages}.

    def _recompute_pull_push(self, dist, parent, aff, wave):
        """Dense pull wave (bulk DistanceQuery: one unmasked wave, counted
        as one round, improvements folded into affected rows only —
        unaffected rows cannot improve, the pre-deletion state was
        converged) + push to fixpoint."""
        ax = self.cfg.mesh_axes
        offers = jax.lax.all_gather(dist, ax, tiled=True)
        best, arg = wave(offers)
        improved = (best < dist) & aff
        dist = jnp.where(improved, best, dist)
        parent = jnp.where(improved, arg, parent)
        n_pull = jax.lax.psum(jnp.sum(improved.astype(jnp.int32)), ax)
        dist, parent, rounds, msgs = self._relax_body(
            dist, parent, improved, wave)
        return dist, parent, rounds + 1, msgs + n_pull

    def _recompute_delta(self, dist, parent, aff, esrc, edst, eact, wave,
                         row0):
        """Bulk DistanceQuery, message form (paper Listing 9): each partition
        broadcasts the ids of the srcs its affected vertices need offers from
        (packed, delta_cap); owners of queried valid vertices become the PUSH
        frontier and normal delta relaxation delivers the offers.  Same
        fixpoint as the dense pull (Appendix A); O(P*cap) bytes instead of
        O(N).  Overflow falls back to every valid vertex pushing once.

        The request set is packed from the COO pool slice (maintained for
        every backend); the offer delivery itself runs through the wave.
        """
        ax = self.cfg.mesh_axes
        dl = edst - row0
        req = eact & aff[dl]
        cap = self.cfg.delta_cap
        order = jnp.argsort(~req)
        take = order[:cap]
        sel = req[take]
        pack = jnp.where(sel, esrc[take], -1)
        overflow = jax.lax.psum(
            (jnp.sum(req.astype(jnp.int32)) > cap).astype(jnp.int32),
            ax) > 0
        all_q = jax.lax.all_gather(pack, ax, tiled=True)

        def sparse_front():
            base = jnp.zeros((self.cfg.num_vertices,), jnp.bool_)
            safe = jnp.clip(all_q, 0, self.cfg.num_vertices - 1)
            base = base.at[safe].max(all_q >= 0)
            local_ids = row0 + jnp.arange(self.npp, dtype=jnp.int32)
            return base[local_ids]

        def dense_front():
            return jnp.ones((self.npp,), jnp.bool_)

        queried = jax.lax.cond(overflow, dense_front, sparse_front)
        frontier0 = queried & jnp.isfinite(dist)
        return self._relax_body(dist, parent, frontier0, wave)

    # ------------------------------------------------- bucketed drain impls
    # The sharded rendering of core/buckets.run_drain (DESIGN.md §9): one
    # pull wave into the accumulated invalidated set, then bucket-threshold-
    # paced push waves.  The bucket limit is a replicated scalar computed
    # from the SAME gathered data a normal round exchanges (dist plus one
    # bool mask) — every partition derives identical (cur, limit), so the
    # schedule needs NO new collective primitives, and the wave sequence —
    # hence final (dist, parent) AND the round/message counters — is
    # bit-identical to the single-device drain.

    def _bucket_offers_allgather(self, dist, push, bucket_width):
        from repro.core.buckets import bucket_limit
        ax = self.cfg.mesh_axes
        dist_full = jax.lax.all_gather(dist, ax, tiled=True)
        push_full = jax.lax.all_gather(push, ax, tiled=True)
        cur = jnp.min(jnp.where(push_full, dist_full, INF))
        limit = bucket_limit(cur, bucket_width)
        act_full = push_full & ((dist_full < limit) | (dist_full == cur))
        offers = jnp.where(act_full, dist_full, INF)
        active = push & ((dist < limit) | (dist == cur))
        return offers, active

    def _bucket_offers_delta(self, dist, push, row0, bucket_width):
        """Delta-compressed drain wave: pack the WHOLE pending set (ids +
        dists); ``cur`` from the packed values is exact because every pending
        vertex is packed when no partition overflows.  Overflow falls back to
        the dense gathers — the offers stay bucket-gated there too, so the
        wave sequence is unchanged (unlike ``_round_delta``'s superset
        fallback, a superset here would break the pacing parity)."""
        from repro.core.buckets import bucket_limit
        ax = self.cfg.mesh_axes
        cap = self.cfg.delta_cap
        n = self.cfg.num_vertices
        overflow = jax.lax.psum(
            (jnp.sum(push.astype(jnp.int32)) > cap).astype(jnp.int32),
            ax) > 0
        local_ids = row0 + jnp.arange(self.npp, dtype=jnp.int32)
        order = jnp.argsort(~push)
        take = order[:cap]
        sel = push[take]
        pack_idx = jnp.where(sel, local_ids[take], -1)
        pack_val = jnp.where(sel, dist[take], INF)
        all_idx = jax.lax.all_gather(pack_idx, ax, tiled=True)
        all_val = jax.lax.all_gather(pack_val, ax, tiled=True)

        def sparse():
            cur = jnp.min(all_val)
            limit = bucket_limit(cur, bucket_width)
            act = (all_val < limit) | (all_val == cur)
            base = jnp.full((n,), INF, dist.dtype)
            safe = jnp.clip(all_idx, 0, n - 1)
            offers = base.at[safe].min(
                jnp.where((all_idx >= 0) & act, all_val, INF))
            return offers, cur

        def dense():
            dist_full = jax.lax.all_gather(dist, ax, tiled=True)
            push_full = jax.lax.all_gather(push, ax, tiled=True)
            cur = jnp.min(jnp.where(push_full, dist_full, INF))
            limit = bucket_limit(cur, bucket_width)
            act_full = push_full & ((dist_full < limit) | (dist_full == cur))
            return jnp.where(act_full, dist_full, INF), cur

        offers, cur = jax.lax.cond(overflow, dense, sparse)
        limit = bucket_limit(cur, bucket_width)
        active = push & ((dist < limit) | (dist == cur))
        return offers, active

    def _drain_body(self, dist, parent, push, pull, wave, row0, bucket_width):
        """Sharded drain: (dist, parent, rounds, messages), counters equal to
        ``run_drain``'s.  Pull phase runs unconditionally (collectives are
        uniform across partitions) but improvements fold into ``pull`` rows
        only and the round is counted iff any lane pulled — state-identical
        to the single-device ``lax.cond`` gating."""
        ax = self.cfg.mesh_axes
        any_pull = jax.lax.psum(jnp.sum(pull.astype(jnp.int32)), ax) > 0
        offers = jax.lax.all_gather(dist, ax, tiled=True)
        best, arg = wave(offers)
        improved = (best < dist) & pull
        dist = jnp.where(improved, best, dist)
        parent = jnp.where(improved, arg, parent)
        push = push | improved
        msgs0 = jax.lax.psum(jnp.sum(improved.astype(jnp.int32)), ax)
        rounds0 = jnp.where(any_pull, jnp.int32(1), jnp.int32(0))

        def cond(carry):
            return carry[3]

        def body(carry):
            dist, parent, push, _, rounds, msgs = carry
            if self.cfg.exchange == "delta":
                offers, active = self._bucket_offers_delta(
                    dist, push, row0, bucket_width)
            else:
                offers, active = self._bucket_offers_allgather(
                    dist, push, bucket_width)
            dist, parent, improved = self._apply_wave(
                dist, parent, wave, offers)
            push = (push & ~active) | improved
            tot = jax.lax.psum(
                jnp.stack([jnp.sum(improved.astype(jnp.int32)),
                           jnp.sum(push.astype(jnp.int32))]), ax)
            return dist, parent, push, tot[1] > 0, rounds + 1, msgs + tot[0]

        init_go = jax.lax.psum(jnp.sum(push.astype(jnp.int32)), ax) > 0
        dist, parent, _, _, rounds, msgs = jax.lax.while_loop(
            cond, body, (dist, parent, push, init_go, rounds0, msgs0))
        return dist, parent, rounds, msgs

    def _bucket_offers_allgather_ms(self, dist, push, bucket_width):
        from repro.core.buckets import bucket_limit
        ax = self.cfg.mesh_axes
        dist_full = jax.lax.all_gather(dist, ax, tiled=True, axis=1)
        push_full = jax.lax.all_gather(push, ax, tiled=True, axis=1)
        cur = jnp.min(jnp.where(push_full, dist_full, INF),
                      axis=1, keepdims=True)                       # [S, 1]
        limit = bucket_limit(cur, bucket_width)
        act_full = push_full & ((dist_full < limit) | (dist_full == cur))
        offers = jnp.where(act_full, dist_full, INF)
        active = push & ((dist < limit) | (dist == cur))
        return offers, active

    def _bucket_offers_delta_ms(self, dist, push, row0, bucket_width):
        """Per-lane packing with a per-lane dense-fallback select (both
        operands computed — the batched rendering of the unbatched
        ``lax.cond``, same wave sequence per lane)."""
        from repro.core.buckets import bucket_limit
        ax = self.cfg.mesh_axes
        cap = self.cfg.delta_cap
        n = self.cfg.num_vertices
        overflow = jax.lax.psum(
            (jnp.sum(push.astype(jnp.int32), axis=1)
             > cap).astype(jnp.int32), ax) > 0                     # [S]
        local_ids = row0 + jnp.arange(self.npp, dtype=jnp.int32)
        order = jnp.argsort(~push, axis=1)
        take = order[:, :cap]
        sel = jnp.take_along_axis(push, take, axis=1)
        pack_idx = jnp.where(sel, local_ids[take], -1)
        pack_val = jnp.where(sel, jnp.take_along_axis(dist, take, axis=1),
                             INF)
        all_idx = jax.lax.all_gather(pack_idx, ax, tiled=True, axis=1)
        all_val = jax.lax.all_gather(pack_val, ax, tiled=True, axis=1)
        dist_full = jax.lax.all_gather(dist, ax, tiled=True, axis=1)
        push_full = jax.lax.all_gather(push, ax, tiled=True, axis=1)
        cur_sparse = jnp.min(all_val, axis=1, keepdims=True)
        cur_dense = jnp.min(jnp.where(push_full, dist_full, INF),
                            axis=1, keepdims=True)
        cur = jnp.where(overflow[:, None], cur_dense, cur_sparse)   # [S, 1]
        limit = bucket_limit(cur, bucket_width)
        act_pack = (all_val < limit) | (all_val == cur)
        safe = jnp.clip(all_idx, 0, n - 1)
        sparse = jax.vmap(lambda s_, v: jnp.full((n,), INF, dist.dtype)
                          .at[s_].min(v))(
            safe, jnp.where((all_idx >= 0) & act_pack, all_val, INF))
        act_full = push_full & ((dist_full < limit) | (dist_full == cur))
        dense = jnp.where(act_full, dist_full, INF)
        offers = jnp.where(overflow[:, None], dense, sparse)
        active = push & ((dist < limit) | (dist == cur))
        return offers, active

    def _drain_body_ms(self, dist, parent, push, pull, wave_b, row0,
                       bucket_width):
        """Batched drain over [S, npp] lanes; per-lane ``go`` gates freeze a
        drained lane's round counter exactly where its unbatched drain would
        exit (same trick as ``_relax_body_ms``)."""
        ax = self.cfg.mesh_axes
        S = dist.shape[0]
        any_pull = jax.lax.psum(
            jnp.sum(pull.astype(jnp.int32), axis=1), ax) > 0        # [S]
        offers = jax.lax.all_gather(dist, ax, tiled=True, axis=1)
        best, arg = wave_b(offers)
        improved = (best < dist) & pull
        dist = jnp.where(improved, best, dist)
        parent = jnp.where(improved, arg, parent)
        push = push | improved
        msgs0 = jax.lax.psum(jnp.sum(improved.astype(jnp.int32), axis=1), ax)
        rounds0 = any_pull.astype(jnp.int32)

        def cond(carry):
            return jnp.any(carry[3])

        def body(carry):
            dist, parent, push, go, rounds, msgs = carry
            if self.cfg.exchange == "delta":
                offers, active = self._bucket_offers_delta_ms(
                    dist, push, row0, bucket_width)
            else:
                offers, active = self._bucket_offers_allgather_ms(
                    dist, push, bucket_width)
            dist, parent, improved = self._apply_wave(
                dist, parent, wave_b, offers)
            push = (push & ~active) | improved
            n_imp = jax.lax.psum(
                jnp.sum(improved.astype(jnp.int32), axis=1), ax)
            n_push = jax.lax.psum(
                jnp.sum(push.astype(jnp.int32), axis=1), ax)
            return (dist, parent, push, n_push > 0,
                    rounds + go.astype(jnp.int32), msgs + n_imp)

        init_go = jax.lax.psum(
            jnp.sum(push.astype(jnp.int32), axis=1), ax) > 0
        dist, parent, _, _, rounds, msgs = jax.lax.while_loop(
            cond, body, (dist, parent, push, init_go, rounds0, msgs0))
        return dist, parent, rounds, msgs

    # --------------------------------------------------- invalidation impls
    # ``gate`` (optional replicated bool, or [S] per-lane bool on the _ms
    # variants) short-circuits the marking loop when no partition seeded —
    # the bucketed schedule's lazy deletion epoch passes ``any_seed`` so
    # non-tree deletions cost zero marking rounds, matching the gated
    # single-device ``mark_subtree_*``.  Stats stay identical either way:
    # callers already mask inv_rounds with the same any_seed.

    def _invalidate_doubling(self, parent, seed, gate=None):
        """Pointer-doubling subtree marking with dense all_gathers of the
        (aff, ptr) vectors — O(log depth) rounds x O(N) bytes/round."""
        ax = self.cfg.mesh_axes

        def dcond(carry):
            _, _, grew, _ = carry
            return grew if gate is None else grew & gate

        def dbody(carry):
            aff, ptr, _, rounds = carry
            aff_full = jax.lax.all_gather(aff, ax, tiled=True)
            par_full = jax.lax.all_gather(ptr, ax, tiled=True)
            valid = ptr >= 0
            safe = jnp.clip(ptr, 0)
            hop = jnp.where(valid, aff_full[safe], False)
            new_aff = aff | hop
            nxt = jnp.where(valid, par_full[safe], NO_PARENT)
            grew_local = jnp.any(new_aff != aff) | jnp.any(nxt != ptr)
            grew = jax.lax.psum(grew_local.astype(jnp.int32), ax) > 0
            return new_aff, nxt, grew, rounds + 1

        aff, _, _, inv_rounds = jax.lax.while_loop(
            dcond, dbody, (seed, parent, jnp.bool_(True), jnp.int32(0)))
        return aff, inv_rounds

    def _invalidate_flood_dense(self, parent, seed, gate=None):
        """Paper-faithful level-by-level SetToInfinity flood with dense aff
        gathers — one round per tree level.  The distributed rendering of
        ``delete.mark_subtree_flood`` (identical wave/round structure, so the
        sharded engine's stats match the single-device flood path exactly)."""
        ax = self.cfg.mesh_axes

        def dcond(carry):
            _, grew, _ = carry
            return grew if gate is None else grew & gate

        def dbody(carry):
            aff, _, rounds = carry
            aff_full = jax.lax.all_gather(aff, ax, tiled=True)
            join = jnp.where(parent >= 0, aff_full[jnp.clip(parent, 0)], False)
            new = aff | join
            grew = jax.lax.psum(
                jnp.sum((new != aff).astype(jnp.int32)), ax) > 0
            return new, grew, rounds + 1

        aff, _, inv_rounds = jax.lax.while_loop(
            dcond, dbody, (seed, jnp.bool_(True), jnp.int32(0)))
        return aff, inv_rounds

    def _invalidate_delta(self, parent, seed, row0, gate=None):
        """Paper-faithful SetToInfinity flood with delta-compressed frontier
        exchange: each wave broadcasts only the NEWLY affected vertex ids
        (packed (idx) buffers, delta_cap per partition) — O(depth) rounds x
        O(P*cap) bytes.  Overflow rounds fall back to a dense aff gather.
        Beyond-paper vs the doubling variant: 10-40x fewer wire bytes on
        shallow subtrees (EXPERIMENTS.md §Perf C3)."""
        ax = self.cfg.mesh_axes
        cap = self.cfg.delta_cap
        n = self.cfg.num_vertices
        local_ids = row0 + jnp.arange(self.npp, dtype=jnp.int32)

        def dcond(carry):
            _, _, grew, _ = carry
            return grew if gate is None else grew & gate

        def dbody(carry):
            aff, frontier, _, rounds = carry
            n_front = jnp.sum(frontier.astype(jnp.int32))
            overflow = jax.lax.psum(
                (n_front > cap).astype(jnp.int32), ax) > 0

            order = jnp.argsort(~frontier)
            take = order[:cap]
            sel = frontier[take]
            pack = jnp.where(sel, local_ids[take], -1)
            all_ids = jax.lax.all_gather(pack, ax, tiled=True)   # (P*cap,)

            def sparse_base():
                base = jnp.zeros((n,), jnp.bool_)
                safe = jnp.clip(all_ids, 0, n - 1)
                return base.at[safe].max(all_ids >= 0)

            def dense_base():
                return jax.lax.all_gather(aff, ax, tiled=True)

            base = jax.lax.cond(overflow, dense_base, sparse_base)
            valid = parent >= 0
            join = jnp.where(valid, base[jnp.clip(parent, 0)], False)
            new = join & ~aff
            aff2 = aff | new
            grew = jax.lax.psum(jnp.sum(new.astype(jnp.int32)), ax) > 0
            return aff2, new, grew, rounds + 1

        aff, _, _, inv_rounds = jax.lax.while_loop(
            dcond, dbody, (seed, seed, jnp.bool_(True), jnp.int32(0)))
        return aff, inv_rounds

    # ------------------------------------------- batched multi-source impls
    # The serving layer's [S, npp] renderings of the bodies above
    # (DESIGN.md §8): S stacked trees advance through ONE shared loop over
    # the shared graph.  Written with an explicit leading source dimension
    # (not vmap) so no collective ever needs a batching rule — all_gather
    # takes ``axis=1``, psum reduces [S] vectors elementwise; only the pure
    # shard-local ``wave`` is vmapped by the caller.
    #
    # Per-lane bit-identity argument: a lane whose frontier has drained
    # offers +inf everywhere, so its (dist, parent, frontier) are natural
    # fixpoints of every further round — no select-masking needed — and the
    # per-lane ``go`` gate stops its round counter exactly where the
    # unbatched while_loop would have exited.  Messages need no gate: a
    # drained lane improves nothing, so its per-round count is already 0.

    def _relax_body_ms(self, dist, parent, frontier, wave_b):
        """Batched ``_relax_body``: dist/parent/frontier are [S, npp];
        returns (dist, parent, rounds[S], messages[S]) — each lane equal to
        what the unbatched body returns for its source."""
        ax = self.cfg.mesh_axes
        row0 = jnp.int32(self._flat_index()) * self.npp
        S = dist.shape[0]

        def rnd(dist, parent, frontier):
            if self.cfg.exchange == "delta":
                return self._round_delta_ms(dist, parent, frontier, wave_b,
                                            row0)
            return self._round_allgather_ms(dist, parent, frontier, wave_b)

        def cond(carry):
            return jnp.any(carry[3])

        def body(carry):
            dist, parent, frontier, go, rounds, msgs = carry
            dist, parent, improved = rnd(dist, parent, frontier)
            n_imp = jax.lax.psum(
                jnp.sum(improved.astype(jnp.int32), axis=1), ax)
            return (dist, parent, improved, n_imp > 0,
                    rounds + go.astype(jnp.int32), msgs + n_imp)

        init_go = jax.lax.psum(
            jnp.sum(frontier.astype(jnp.int32), axis=1), ax) > 0
        dist, parent, _, _, rounds, msgs = jax.lax.while_loop(
            cond, body, (dist, parent, frontier, init_go,
                         jnp.zeros((S,), jnp.int32),
                         jnp.zeros((S,), jnp.int32)))
        return dist, parent, rounds, msgs

    def _round_allgather_ms(self, dist, parent, frontier, wave_b):
        ax = self.cfg.mesh_axes
        dist_full = jax.lax.all_gather(dist, ax, tiled=True, axis=1)
        front_full = jax.lax.all_gather(frontier, ax, tiled=True, axis=1)
        offers = jnp.where(front_full, dist_full, INF)
        return self._apply_wave(dist, parent, wave_b, offers)

    def _round_delta_ms(self, dist, parent, frontier, wave_b, row0):
        """Per-lane delta packing; overflow lanes fall back to the dense
        gather via a per-lane select (both operands are computed — the
        batched rendering of the unbatched ``lax.cond``, same fixpoint)."""
        ax = self.cfg.mesh_axes
        cap = self.cfg.delta_cap
        n = self.cfg.num_vertices
        overflow = jax.lax.psum(
            (jnp.sum(frontier.astype(jnp.int32), axis=1)
             > cap).astype(jnp.int32), ax) > 0                     # [S]
        local_ids = row0 + jnp.arange(self.npp, dtype=jnp.int32)
        order = jnp.argsort(~frontier, axis=1)   # frontier first (stable)
        take = order[:, :cap]
        sel = jnp.take_along_axis(frontier, take, axis=1)
        pack_idx = jnp.where(sel, local_ids[take], -1)
        pack_val = jnp.where(sel, jnp.take_along_axis(dist, take, axis=1),
                             INF)
        all_idx = jax.lax.all_gather(pack_idx, ax, tiled=True, axis=1)
        all_val = jax.lax.all_gather(pack_val, ax, tiled=True, axis=1)
        safe = jnp.clip(all_idx, 0, n - 1)
        sparse = jax.vmap(lambda s_, v: jnp.full((n,), INF, dist.dtype)
                          .at[s_].min(v))(
            safe, jnp.where(all_idx >= 0, all_val, INF))
        dense = jax.lax.all_gather(dist, ax, tiled=True, axis=1)
        offers = jnp.where(overflow[:, None], dense, sparse)
        return self._apply_wave(dist, parent, wave_b, offers)

    def _recompute_pull_push_ms(self, dist, parent, aff, wave_b):
        """Batched ``_recompute_pull_push``: one unmasked pull wave per
        lane, improvements folded into affected rows only, then the batched
        push body to fixpoint."""
        ax = self.cfg.mesh_axes
        offers = jax.lax.all_gather(dist, ax, tiled=True, axis=1)
        best, arg = wave_b(offers)
        improved = (best < dist) & aff
        dist = jnp.where(improved, best, dist)
        parent = jnp.where(improved, arg, parent)
        n_pull = jax.lax.psum(jnp.sum(improved.astype(jnp.int32), axis=1), ax)
        dist, parent, rounds, msgs = self._relax_body_ms(
            dist, parent, improved, wave_b)
        return dist, parent, rounds + 1, msgs + n_pull

    def _recompute_delta_ms(self, dist, parent, aff, esrc, edst, eact,
                            wave_b, row0):
        """Batched ``_recompute_delta``: the request set is packed per lane
        from the shared pool slice (each lane's affected rows differ)."""
        ax = self.cfg.mesh_axes
        cap = self.cfg.delta_cap
        n = self.cfg.num_vertices
        S = dist.shape[0]
        dl = edst - row0
        req = eact[None, :] & aff[:, dl]                          # [S, epp]
        order = jnp.argsort(~req, axis=1)
        take = order[:, :cap]
        sel = jnp.take_along_axis(req, take, axis=1)
        pack = jnp.where(sel, esrc[take], -1)
        overflow = jax.lax.psum(
            (jnp.sum(req.astype(jnp.int32), axis=1)
             > cap).astype(jnp.int32), ax) > 0
        all_q = jax.lax.all_gather(pack, ax, tiled=True, axis=1)
        safe = jnp.clip(all_q, 0, n - 1)
        base = jax.vmap(lambda s_, m: jnp.zeros((n,), jnp.bool_)
                        .at[s_].max(m))(safe, all_q >= 0)
        local_ids = row0 + jnp.arange(self.npp, dtype=jnp.int32)
        sparse_front = jnp.take(base, local_ids, axis=1)
        queried = jnp.where(overflow[:, None],
                            jnp.ones((S, self.npp), jnp.bool_), sparse_front)
        frontier0 = queried & jnp.isfinite(dist)
        return self._relax_body_ms(dist, parent, frontier0, wave_b)

    def _invalidate_doubling_ms(self, parent, seed, gate=None):
        """Batched pointer-doubling marking over [S, npp] per-lane forests."""
        ax = self.cfg.mesh_axes
        S = parent.shape[0]

        def dcond(carry):
            return jnp.any(carry[2])

        def dbody(carry):
            aff, ptr, go, rounds = carry
            aff_full = jax.lax.all_gather(aff, ax, tiled=True, axis=1)
            par_full = jax.lax.all_gather(ptr, ax, tiled=True, axis=1)
            valid = ptr >= 0
            safe = jnp.clip(ptr, 0)
            hop = jnp.where(valid,
                            jnp.take_along_axis(aff_full, safe, axis=1),
                            False)
            new_aff = aff | hop
            nxt = jnp.where(valid,
                            jnp.take_along_axis(par_full, safe, axis=1),
                            NO_PARENT)
            grew_local = (jnp.any(new_aff != aff, axis=1)
                          | jnp.any(nxt != ptr, axis=1))
            grew = jax.lax.psum(grew_local.astype(jnp.int32), ax) > 0
            if gate is not None:
                grew = grew & gate
            return new_aff, nxt, grew, rounds + go.astype(jnp.int32)

        go0 = jnp.ones((S,), jnp.bool_) if gate is None else gate
        aff, _, _, inv_rounds = jax.lax.while_loop(
            dcond, dbody, (seed, parent, go0, jnp.zeros((S,), jnp.int32)))
        return aff, inv_rounds

    def _invalidate_flood_dense_ms(self, parent, seed, gate=None):
        """Batched level-by-level SetToInfinity flood over per-lane forests."""
        ax = self.cfg.mesh_axes
        S = parent.shape[0]

        def dcond(carry):
            return jnp.any(carry[1])

        def dbody(carry):
            aff, go, rounds = carry
            aff_full = jax.lax.all_gather(aff, ax, tiled=True, axis=1)
            join = jnp.where(
                parent >= 0,
                jnp.take_along_axis(aff_full, jnp.clip(parent, 0), axis=1),
                False)
            new = aff | join
            grew = jax.lax.psum(
                jnp.sum((new != aff).astype(jnp.int32), axis=1), ax) > 0
            if gate is not None:
                grew = grew & gate
            return new, grew, rounds + go.astype(jnp.int32)

        go0 = jnp.ones((S,), jnp.bool_) if gate is None else gate
        aff, _, inv_rounds = jax.lax.while_loop(
            dcond, dbody, (seed, go0, jnp.zeros((S,), jnp.int32)))
        return aff, inv_rounds

    def _invalidate_delta_ms(self, parent, seed, row0, gate=None):
        """Batched delta-compressed flood; per-lane packing, per-lane dense
        fallback select (same structure as ``_round_delta_ms``)."""
        ax = self.cfg.mesh_axes
        cap = self.cfg.delta_cap
        n = self.cfg.num_vertices
        S = parent.shape[0]
        local_ids = row0 + jnp.arange(self.npp, dtype=jnp.int32)

        def dcond(carry):
            return jnp.any(carry[2])

        def dbody(carry):
            aff, frontier, go, rounds = carry
            overflow = jax.lax.psum(
                (jnp.sum(frontier.astype(jnp.int32), axis=1)
                 > cap).astype(jnp.int32), ax) > 0
            order = jnp.argsort(~frontier, axis=1)
            take = order[:, :cap]
            sel = jnp.take_along_axis(frontier, take, axis=1)
            pack = jnp.where(sel, local_ids[take], -1)
            all_ids = jax.lax.all_gather(pack, ax, tiled=True, axis=1)
            safe = jnp.clip(all_ids, 0, n - 1)
            sparse = jax.vmap(lambda s_, m: jnp.zeros((n,), jnp.bool_)
                              .at[s_].max(m))(safe, all_ids >= 0)
            dense = jax.lax.all_gather(aff, ax, tiled=True, axis=1)
            base = jnp.where(overflow[:, None], dense, sparse)
            valid = parent >= 0
            join = jnp.where(
                valid, jnp.take_along_axis(base, jnp.clip(parent, 0), axis=1),
                False)
            new = join & ~aff
            aff2 = aff | new
            grew = jax.lax.psum(
                jnp.sum(new.astype(jnp.int32), axis=1), ax) > 0
            if gate is not None:
                grew = grew & gate
            return aff2, new, grew, rounds + go.astype(jnp.int32)

        go0 = jnp.ones((S,), jnp.bool_) if gate is None else gate
        aff, _, _, inv_rounds = jax.lax.while_loop(
            dcond, dbody, (seed, seed, go0, jnp.zeros((S,), jnp.int32)))
        return aff, inv_rounds

    def make_seed_from_deletions(self):
        """seed(parent, del_src, del_dst) -> bool[N] invalidation seeds.

        del_src/del_dst: replicated i32[K] (pad with -1).  A deletion seeds
        iff it was a tree edge (Listing 4)."""

        @jax.jit
        @partial(jax.shard_map, mesh=self.mesh,
                 in_specs=(self.vspec, self.rspec, self.rspec),
                 out_specs=self.vspec,
                 check_vma=False)
        def seed_fn(parent, del_src, del_dst):
            row0 = jnp.int32(self._flat_index()) * self.npp
            local = (del_dst >= row0) & (del_dst < row0 + self.npp) & (del_dst >= 0)
            safe = jnp.clip(del_dst - row0, 0, self.npp - 1)
            is_tree = parent[safe] == del_src
            f = jnp.zeros((self.npp,), jnp.bool_)
            return f.at[safe].max(local & is_tree)

        return seed_fn

    # ------------------------------------------------------------- host init
    def init_vertex_arrays(self, source: int):
        n = self.cfg.num_vertices
        dist = np.full(n, np.inf, np.float32); dist[source] = 0.0
        parent = np.full(n, -1, np.int32)
        sh = self.vertex_sharding()
        return (jax.device_put(dist, sh), jax.device_put(parent, sh))

    def init_vertex_arrays_ms(self, sources):
        """Stacked [S, N] multi-source vertex state, sharded along the
        vertex axis (row ``i`` == ``init_vertex_arrays(sources[i])``)."""
        n = self.cfg.num_vertices
        s = len(sources)
        dist = np.full((s, n), np.inf, np.float32)
        dist[np.arange(s), np.asarray(sources)] = 0.0
        parent = np.full((s, n), -1, np.int32)
        sh = self.vertex_sharding_ms()
        return (jax.device_put(dist, sh), jax.device_put(parent, sh))

    def put_edges(self, src, dst, w, active):
        sh = self.edge_sharding()
        return (jax.device_put(src.astype(np.int32), sh),
                jax.device_put(dst.astype(np.int32), sh),
                jax.device_put(w.astype(np.float32), sh),
                jax.device_put(active, sh))

    def frontier_of(self, vertices: np.ndarray):
        f = np.zeros(self.cfg.num_vertices, np.bool_)
        f[vertices[vertices >= 0]] = True
        return jax.device_put(f, self.vertex_sharding())
