"""RelaxBackend protocol — one relaxation backend = layout state + host
planner + jitted patch ops + wave computation + rebuild policy + checkpoint
participation (DESIGN.md §7).

Both dynamic engines consume backends through this seam:

  * ``SSSPDelEngine`` (core/engine.py) holds ONE ``RelaxBackend`` instance
    and calls ``apply_adds`` / ``apply_dels`` / ``relax`` / ``delete`` /
    ``restore`` — no per-backend branching in the ingest path;
  * ``ShardedSSSPDelEngine`` (core/dist_engine.py) holds one
    ``ShardedBackend`` coordinator, which in turn owns one shard-local
    planner per partition plus the globally sharded device layout arrays,
    and plugs the backend's wave into the shard_map epochs' relaxation body
    in place of the hardwired segment-min (DESIGN.md §7.2).

The equivalence contract travels with the protocol: every backend's wave
evaluates the same candidate set (all live in-edges of each row, offers
masked by the frontier) with the same smallest-src-id tie-break, so
``(dist, parent)`` and the round/message counters are bit-identical across
backends AND across the partition-count axis (test_backend_equiv.py,
test_dist_engine.py).

Registries: ``BACKENDS`` (single-device classes) and ``SHARDED_BACKENDS``
(their sharded coordinators), populated by the ``@register`` /
``@register_sharded`` decorators when the package imports its submodules.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any, Callable, ClassVar

import jax
import numpy as np

from repro.kernels.relax import config as kernel_config

if TYPE_CHECKING:  # only for annotations; no runtime import cycles
    from repro.core.ingest import PlannedAdds, SlotAllocator
    from repro.core.relax import RelaxStats
    from repro.core.state import EdgePool, SSSPState
    from repro.core.delete import DeleteStats


BACKENDS: dict[str, type["RelaxBackend"]] = {}
SHARDED_BACKENDS: dict[str, type["ShardedBackend"]] = {}


def register(cls: type["RelaxBackend"]) -> type["RelaxBackend"]:
    BACKENDS[cls.name] = cls
    return cls


def register_sharded(cls: type["ShardedBackend"]) -> type["ShardedBackend"]:
    SHARDED_BACKENDS[cls.name] = cls
    return cls


# ------------------------------------------------------------- validation --
# Knobs that only make sense for a particular backend: setting one away from
# its dataclass default while selecting a different backend is a config bug
# that used to surface as a confusing failure deep inside layout init.
# ``ell_use_kernel`` is the one genuinely shared knob: both ELL-layout
# backends (ellpack, sliced) consume it.
_SLICED_KNOBS = ("sliced_slice_rows", "sliced_hub_k", "sliced_init_k",
                 "sliced_fused")
_ELLPACK_KNOBS = ("ell_block_rows", "ell_init_k")
_ELL_SHARED_KNOBS = ("ell_use_kernel",)

# ``relax_backend="auto"`` (single-device engines only): start on the dense
# ELL layout and fall back to the sliced/hybrid layout when a rebuild's
# ``K*N`` cell allocation blows past ``ELL_BLOWUP_RATIO`` times the live
# edge count — the power-law-hub pathology (DESIGN.md §6).  Both layouts'
# knobs are therefore legitimate under "auto".
AUTO_BACKEND = "auto"
ELL_BLOWUP_RATIO = 16

WAVE_SCHEDULES = ("rounds", "buckets")
FRONTIER_MODES = ("dense", "sparse", "auto")


def ladder_route(cfg: Any) -> bool:
    """Whether the single-device engine runs its push waves through the
    frontier-compacted capacity ladder (DESIGN.md §12.3).  ``"dense"`` is
    the reference and ``"sparse"`` forces the ladder everywhere; the
    default ``"auto"`` takes it where it pays and nothing is lost: one
    source (under ``vmap`` a ``lax.cond`` runs both branches), the rounds
    schedule and the segment backend, whose dense round is the ladder's
    own fallback.  Which waves then run compacted is decided on the
    device, per wave."""
    mode = getattr(cfg, "frontier_mode", "dense")
    if mode != "auto":
        return mode == "sparse"
    return (getattr(cfg, "sources", None) is None
            and getattr(cfg, "wave_schedule", "rounds") == "rounds"
            and getattr(cfg, "relax_backend", "segment") == "segment")


def validate_backend_config(cfg: Any) -> None:
    """Raise ``ValueError`` at construction time for an unknown
    ``relax_backend`` or backend knobs that don't apply to the selected
    backend — instead of failing deep inside layout init (or, worse,
    silently ignoring a knob the user believes they tuned).  Shared by
    ``EngineConfig`` and ``ShardedEngineConfig`` (__post_init__)."""
    kernel_config.check_kernel_request(cfg)
    name = getattr(cfg, "relax_backend", "segment")
    if name not in BACKENDS and name != AUTO_BACKEND:
        raise ValueError(
            f"unknown relax_backend {name!r}; valid backends: "
            f"{sorted(BACKENDS) + [AUTO_BACKEND]}")
    defaults = {f.name: f.default for f in dataclasses.fields(cfg)}
    schedule = getattr(cfg, "wave_schedule", "rounds")
    if schedule not in WAVE_SCHEDULES:
        raise ValueError(
            f"unknown wave_schedule {schedule!r}; valid schedules: "
            f"{list(WAVE_SCHEDULES)}")
    width = getattr(cfg, "bucket_width", 1.0)
    # "auto" = pick delta from the live weight distribution at drain time
    # (DESIGN.md §9.5); any other string — and non-positive/NaN numbers —
    # is a config bug.  The string check must precede the numeric compare
    # (a str/float ``>`` would raise the wrong exception type).
    if isinstance(width, str):
        if width != "auto":
            raise ValueError(
                f"bucket_width must be > 0 or 'auto'; got {width!r}")
    elif not width > 0:   # also rejects NaN
        raise ValueError(
            f"bucket_width must be > 0 (inf = one bucket); got {width!r}")
    if (schedule == "rounds" and "bucket_width" in defaults
            and width != defaults["bucket_width"]):
        raise ValueError(
            f"bucket_width={width!r} configures the buckets schedule; "
            f"remove it or select wave_schedule='buckets'")
    mode = getattr(cfg, "frontier_mode", "dense")
    if mode not in FRONTIER_MODES:
        raise ValueError(
            f"unknown frontier_mode {mode!r}; valid modes: "
            f"{list(FRONTIER_MODES)}")
    cap = getattr(cfg, "frontier_cap", 0)
    if cap < 0:
        raise ValueError(f"frontier_cap must be >= 0 (0 = derive); got {cap}")
    if mode == "dense":
        for k in ("frontier_cap", "frontier_kernel"):
            if k in defaults and getattr(cfg, k) != defaults[k]:
                raise ValueError(
                    f"{k}={getattr(cfg, k)!r} configures the sparse "
                    f"frontier path; remove it or select "
                    f"frontier_mode='sparse'/'auto'")
    misapplied: list[tuple[tuple[str, ...], str]] = []
    if name not in ("sliced", AUTO_BACKEND):
        misapplied.append((_SLICED_KNOBS, "sliced"))
    if name not in ("ellpack", AUTO_BACKEND):
        misapplied.append((_ELLPACK_KNOBS, "dense-ELL"))
    if name == "segment":
        misapplied.append((_ELL_SHARED_KNOBS, "ELL-layout"))
    for knobs, layout in misapplied:
        for k in knobs:
            if k in defaults and getattr(cfg, k) != defaults[k]:
                raise ValueError(
                    f"{k}={getattr(cfg, k)!r} is a backend knob that does "
                    f"not apply to relax_backend={name!r} (it configures "
                    f"the {layout} layout); remove it or select the "
                    f"matching backend")


# ------------------------------------------------------ single-device side --
class RelaxBackend:
    """One relaxation backend for the single-device engine.

    Owns the device layout state (if any), the host planner that assigns
    incremental patch positions, the jitted patch ops (ADD append / DEL
    tombstone / min-update), the epoch wave computation, and the rebuild
    policy.  Checkpoint participation is via ``restore``: layout state is a
    derived view and is never serialized — it is rebuilt from the edge-pool
    mirror (``SlotAllocator``) on restore.
    """

    name: ClassVar[str]

    def __init__(self, cfg: Any, num_vertices: int, *,
                 use_kernel: bool = False, interpret: bool = True):
        self.cfg = cfg
        self.n = num_vertices
        self.use_kernel = use_kernel
        self.interpret = interpret

    # --- incremental layout maintenance (device patch ops; no host sync)
    def apply_adds(self, plan: "PlannedAdds", alloc: "SlotAllocator") -> None:
        """Patch the layout for one planned ADD batch (or rebuild from the
        alloc's host mirror on capacity overflow — the mirror already
        contains the batch).  No-op for layouts derived per-epoch."""

    def apply_dels(self, rows: np.ndarray, src: np.ndarray) -> None:
        """Tombstone deleted edges (padded batch; located on device)."""

    # --- epochs (jitted; same candidate sets + tie-break as segment)
    def relax(self, sssp: "SSSPState", edges: "EdgePool",
              frontier: jax.Array) -> tuple["SSSPState", "RelaxStats"]:
        raise NotImplementedError

    def delete(self, sssp: "SSSPState", edges: "EdgePool",
               seed: jax.Array) -> tuple["SSSPState", "DeleteStats"]:
        raise NotImplementedError

    # --- batched multi-source epochs (serving layer, DESIGN.md §8)
    # One shared graph layout, S stacked trees: ``sssp`` carries [S, N]
    # dist/parent and an [S] source vector; the wave is vmapped over the
    # source axis.  jax's while_loop batching rule freezes each lane's
    # carry once ITS OWN convergence predicate goes false, so every lane —
    # dist, parent, AND the [S] per-lane round/message stats — is
    # bit-identical to an unbatched run (tests/test_serving.py).
    #
    # The implementations below are the generic fallback: an UNJITTED
    # per-call vmap (it must close over the CURRENT layout state, which a
    # jit closure would staleley capture).  Every built-in backend
    # overrides them with a module-level jitted jit(vmap(epoch)) entry
    # point that takes its layout arrays as explicit arguments — the
    # per-call vmap re-trace otherwise dominates batched ingest (~8x).
    def relax_batched(self, sssp: "SSSPState", edges: "EdgePool",
                      frontier: jax.Array
                      ) -> tuple["SSSPState", "RelaxStats"]:
        """Batched ``relax``: frontier is shared (ADD tails are
        source-independent), the trees are vmapped."""
        return jax.vmap(self.relax, in_axes=(0, None, None))(
            sssp, edges, frontier)

    def delete_batched(self, sssp: "SSSPState", edges: "EdgePool",
                       seed: jax.Array
                       ) -> tuple["SSSPState", "DeleteStats"]:
        """Batched ``delete``: seeds are per-lane ([S, N] — whether a
        deleted edge is a tree edge depends on each lane's parent forest)."""
        return jax.vmap(self.delete, in_axes=(0, None, 0))(sssp, edges, seed)

    # --- bucketed drains (wave_schedule="buckets", DESIGN.md §9)
    # ``drain`` settles the engine's deferred PendingState bucket-by-bucket
    # (core/buckets.py run_drain discipline): one cond-gated recompute pull
    # into the accumulated invalidated set, then threshold-paced push waves.
    # Same candidate sets + tie rule as ``relax``/``delete``, so the drained
    # (dist, parent) — and the wave sequence itself — is bit-identical
    # across backends.
    def drain(self, sssp: "SSSPState", edges: "EdgePool", pend: Any,
              *, bucket_width: float
              ) -> tuple["SSSPState", Any, "RelaxStats"]:
        raise NotImplementedError

    def drain_batched(self, sssp: "SSSPState", edges: "EdgePool", pend: Any,
                      *, bucket_width: float
                      ) -> tuple["SSSPState", Any, "RelaxStats"]:
        """Batched [S, N] drain (generic unjitted-vmap fallback; built-ins
        override with a module-level jitted entry, as for relax_batched)."""
        return jax.vmap(
            lambda s, pd: self.drain(s, edges, pd, bucket_width=bucket_width)
        )(sssp, pend)

    # --- checkpoint participation / diagnostics
    def restore(self, alloc: "SlotAllocator") -> None:
        """Rebuild layout state from the pool mirror after a restore."""

    def invariants(self) -> dict[str, jax.Array]:
        """Device-side occupancy invariants (diagnostics/tests)."""
        return {}

    def layout_counters(self) -> dict[str, int]:
        """Monotone host-side layout event totals for the obs layer
        (DESIGN.md §10): rebuild count and overflow-lane placements so far.
        Engines diff successive calls (``EngineObs.note_layout``); totals
        may reset when the "auto" policy swaps layouts — deltas clamp.
        Works for all three backends: segment has no planner (zeros), the
        ELL-family planners carry ``rebuilds``, sliced also ``spills``."""
        pl = getattr(self, "planner", None)
        return {"rebuilds": int(getattr(pl, "rebuilds", 0)),
                "overflow_hits": int(getattr(pl, "spills", 0))}


def make_backend(name: str, cfg: Any, *, num_vertices: int | None = None,
                 use_kernel: bool = False, interpret: bool = True
                 ) -> RelaxBackend:
    if name not in BACKENDS:
        raise ValueError(f"unknown relax_backend {name!r}; valid backends: "
                         f"{sorted(BACKENDS)}")
    return BACKENDS[name](
        cfg, cfg.num_vertices if num_vertices is None else num_vertices,
        use_kernel=use_kernel, interpret=interpret)


# ------------------------------------------------------------ sharded side --
class ShardedBackend:
    """Sharded coordinator for one backend: per-partition shard-local
    planners plus the globally sharded device layout arrays (DESIGN.md §7.2).

    dst-owner edge placement makes every shard's in-edges local, so shard
    ``p``'s layout rows are exactly its owned vertex window
    ``[p*npp, (p+1)*npp)``; the global device arrays are the per-shard
    blocks concatenated partition-major and sharded along dim 0, so the
    shard_map epochs see each shard's own block.

    Layout patches run as separate jitted scatters on the global arrays
    *before* the fused epoch (indices are exact — no foreign-entry masking
    needed) and never read device memory back; rebuilds come from the
    per-partition ``SlotAllocator`` host mirrors.  Geometry (ELL width K /
    per-slice widths / overflow capacity) is synchronized across shards at
    rebuild time — shard_map needs one static per-shard block shape.
    """

    name: ClassVar[str]
    n_extra: ClassVar[int] = 0   # sharded layout arrays fed to the epochs

    def __init__(self, cfg: Any, ds: Any, allocs: list["SlotAllocator"]):
        self.cfg = cfg
        self.ds = ds
        self.allocs = allocs

    def arrays(self) -> tuple[jax.Array, ...]:
        """The global sharded layout arrays, in wave-factory order."""
        return ()

    def static_key(self) -> tuple:
        """Static geometry the epoch closures bake in (epoch-cache key
        suffix; array *shapes* re-trace automatically and need not appear)."""
        return (self.name,)

    def stage_adds(self, plans: list[tuple[int, "PlannedAdds"]]) -> None:
        """Patch the layout for one ADD batch (list of per-partition plans),
        rebuilding all shards from the mirrors on any shard's overflow."""

    def restore(self) -> None:
        """Rebuild the sharded layout from the per-partition mirrors."""

    # wave/patch factories: classmethods so epoch closures capture only
    # static config (never a coordinator instance — the epoch cache must not
    # pin device buffers or host mirrors of dead engines).
    @classmethod
    def shard_wave_factory(cls, static: tuple, npp: int) -> Callable:
        """Return ``make_wave(esrc, edst, ew, eact, extras, my_p) -> wave``
        where ``wave(offers) -> (best f32[npp], arg i32[npp])`` evaluates
        one local relaxation wave: per-row min over the shard's in-edges of
        ``offers[src] + w`` and the smallest minimizing global src id."""
        raise NotImplementedError

    # DEL tombstoning runs INSIDE the fused del epoch (not as a staged
    # patch): deletions are per-event under the paper-faithful mode, so an
    # extra device dispatch per deletion would dominate the sharded ingest
    # overhead.  ``del_mutated`` names the extras the patch replaces; the
    # epoch returns them and the engine hands them back via
    # ``update_del_arrays``.
    del_mutated: ClassVar[tuple[int, ...]] = ()

    @classmethod
    def shard_del_patch(cls, static: tuple, npp: int) -> Callable | None:
        """Return ``patch(extras, psrc, pdst, my_p) -> mutated`` tombstoning
        the (padded, replicated, global-vertex-id) deleted edges in this
        shard's layout block — foreign entries no-op via the -inf/max trick
        — or None when the backend has no layout to patch."""
        return None

    def update_del_arrays(self, new_vals: tuple) -> None:
        """Fold the del epoch's mutated layout arrays back into the
        coordinator state (order matches ``del_mutated``)."""

    def layout_counters(self) -> dict[str, int]:
        """Sharded twin of ``RelaxBackend.layout_counters``.  Rebuilds are
        coupled (any shard's overflow rebuilds ALL shards, so every planner
        advances together) — the max over planners counts global rebuild
        EVENTS, matching the single-device figure.  Overflow-lane
        placements are genuinely per-partition and sum."""
        pls = getattr(self, "planners", None) or []
        return {
            "rebuilds": max((int(getattr(p, "rebuilds", 0)) for p in pls),
                            default=0),
            "overflow_hits": sum(int(getattr(p, "spills", 0)) for p in pls),
        }


def make_sharded_backend(name: str, cfg: Any, ds: Any,
                         allocs: list["SlotAllocator"]) -> ShardedBackend:
    if name not in SHARDED_BACKENDS:
        raise ValueError(f"unknown relax_backend {name!r}; valid backends: "
                         f"{sorted(SHARDED_BACKENDS)}")
    return SHARDED_BACKENDS[name](cfg, ds, allocs)


# ------------------------------------------------------- planner utilities --
def rank_within_rows(rows: np.ndarray) -> np.ndarray:
    """Rank of each batch entry among the entries targeting the same row,
    in stable batch order — the cell-offset assignment all ELL-family
    planners use (kpos candidate = fill[row] + rank)."""
    m = len(rows)
    order = np.argsort(rows, kind="stable")
    sr = rows[order]
    starts = np.nonzero(np.r_[True, sr[1:] != sr[:-1]])[0]
    sizes = np.diff(np.r_[starts, m])
    rank = np.empty(m, np.int64)
    rank[order] = np.arange(m) - np.repeat(starts, sizes)
    return rank
