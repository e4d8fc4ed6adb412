"""Relaxation-backend equivalence: every registered RelaxBackend must be a
drop-in for the segment backend — bit-identical (dist, parent) on any
dynamic stream, and all must satisfy the Dijkstra oracle at every query
point (DESIGN.md §2.2, §6, §7).

The sweep crosses backend-relevant switches (doubling vs flood invalidation,
batched vs per-event deletions) and runs with deliberately tiny initial ELL
widths / hub thresholds so the capacity-doubling rebuild path (dense), the
per-slice doubling rebuilds AND the hub overflow-spill path (sliced) are all
exercised repeatedly.

The same contract extends across the *partition-count* axis: the sharded
engine (core/dist_engine.py, DESIGN.md §5/§7.2) must be bit-identical to
every single-device backend on the same streams — P=1 here, P=8 forced host
devices in tests/test_dist_engine.py.
"""
import numpy as np
import pytest

from repro.core import events as ev
from repro.core.backends import EllpackBackend, SlicedBackend
from repro.core.dist_engine import ShardedEngineConfig, ShardedSSSPDelEngine
from repro.core.engine import EngineConfig, SSSPDelEngine
from repro.core.oracle import check_tree, edges_of_pool
from repro.graphs import generators, window


# tiny hub threshold + slice rows: many slices, frequent spills & rebuilds
SLICED_KW = dict(sliced_slice_rows=32, sliced_hub_k=4, sliced_init_k=1)
# per-backend construction kwargs (backend knobs only apply to their
# backend — EngineConfig validation enforces it)
BACKEND_KW = {
    "segment": {},
    # ell_init_k=2 forces the capacity-doubling rebuild path several times
    "ellpack": dict(ell_init_k=2),
    "sliced": SLICED_KW,
}


def _dynamic_stream(seed: int, *, n=90, m=520, delta=0.6):
    n, src, dst, w = generators.erdos_renyi(n, m, seed=seed)
    log = window.sliding_window_stream(src, dst, w, window=m // 3,
                                       delta=delta, seed=seed,
                                       query_every=m // 2)
    return n, len(src), log


def _run(backend: str, n: int, cap: int, log, source: int, *,
         use_doubling: bool, batch_deletions: bool, **kw) -> SSSPDelEngine:
    eng = SSSPDelEngine(EngineConfig(
        n, cap + 64, source, relax_backend=backend,
        use_doubling=use_doubling, batch_deletions=batch_deletions, **kw))
    eng.ingest_log(log)
    return eng


def _oracle_check(eng: SSSPDelEngine, n: int, source: int):
    q = eng.query()
    e = eng.state.edges
    es, ed, ew = edges_of_pool(e.src, e.dst, e.w, e.active)
    check_tree(n, es, ed, ew, source, q.dist, q.parent)
    bk = eng.backend
    for k, ok in bk.invariants().items():
        assert bool(ok), f"{bk.name} invariant violated: {k}"
    if isinstance(bk, (EllpackBackend, SlicedBackend)):
        # the device fill marks must track the host planner's exactly
        np.testing.assert_array_equal(np.asarray(bk.state.fill),
                                      bk.planner.fill)
    return q


@pytest.mark.parametrize("use_doubling", [False, True])
@pytest.mark.parametrize("batch_deletions", [False, True])
def test_backends_bit_identical_on_dynamic_stream(use_doubling, batch_deletions):
    n, m, log = _dynamic_stream(seed=11 + 2 * use_doubling + batch_deletions)
    source = 3
    ell = _run("ellpack", n, m, log, source, use_doubling=use_doubling,
               batch_deletions=batch_deletions, **BACKEND_KW["ellpack"])
    seg = _run("segment", n, m, log, source, use_doubling=use_doubling,
               batch_deletions=batch_deletions)
    sld = _run("sliced", n, m, log, source, use_doubling=use_doubling,
               batch_deletions=batch_deletions, **BACKEND_KW["sliced"])
    q_ell = _oracle_check(ell, n, source)
    q_seg = _oracle_check(seg, n, source)
    q_sld = _oracle_check(sld, n, source)
    np.testing.assert_array_equal(q_seg.dist, q_ell.dist)
    np.testing.assert_array_equal(q_seg.parent, q_ell.parent)
    np.testing.assert_array_equal(q_seg.dist, q_sld.dist)
    np.testing.assert_array_equal(q_seg.parent, q_sld.parent)
    # same waves, same improvements — the stats must agree too
    assert seg.n_rounds == ell.n_rounds == sld.n_rounds
    assert seg.n_messages == ell.n_messages == sld.n_messages
    assert ell.backend.planner.rebuilds >= 1, "rebuild path not exercised"
    assert sld.backend.planner.rebuilds >= 1, \
        "sliced rebuild path not exercised"
    assert sld.backend.planner.spills >= 1, \
        "hub overflow-spill path not exercised"


@pytest.mark.parametrize("backend", ["segment", "ellpack", "sliced"])
def test_sharded_engine_joins_the_equivalence_contract(backend):
    """Partition axis: every backend, sharded (P=1) vs single-device — same
    dist, parent, and wave stats on the same dynamic stream (DESIGN.md
    §5.4/§7.2); and all sharded backends equal the single-device segment
    engine transitively."""
    n, m, log = _dynamic_stream(seed=11)
    source = 3
    kw = BACKEND_KW[backend]
    seg = _run("segment", n, m, log, source,
               use_doubling=True, batch_deletions=False)
    shd = ShardedSSSPDelEngine(ShardedEngineConfig(
        n, m + 64, source, relax_backend=backend, **kw))
    shd.ingest_log(log)
    q_seg, q_shd = seg.query(), shd.query()
    np.testing.assert_array_equal(q_seg.dist, q_shd.dist)
    np.testing.assert_array_equal(q_seg.parent, q_shd.parent)
    assert seg.n_rounds == shd.n_rounds
    assert seg.n_messages == shd.n_messages
    assert seg.rounds_by_kind == shd.rounds_by_kind


@pytest.mark.parametrize("backend", ["segment", "ellpack", "sliced"])
@pytest.mark.parametrize("mode", ["sparse", "auto"])
@pytest.mark.parametrize("schedule", ["rounds", "buckets"])
def test_frontier_modes_join_the_equivalence_contract(backend, mode,
                                                      schedule):
    """Frontier axis (DESIGN.md §12): the compacted sparse path is one
    shared backend-independent implementation, so it must keep every
    backend inside the bit-identity contract — same (dist, parent) and
    wave stats as that backend's dense run, under both wave schedules.
    ``frontier_cap=16`` keeps both ladder rungs AND the in-cond dense
    fallback exercised on these streams."""
    n, m, log = _dynamic_stream(seed=41)
    source = 3
    kw = dict(BACKEND_KW[backend], wave_schedule=schedule)
    dense = _run(backend, n, m, log, source, use_doubling=True,
                 batch_deletions=False, **kw)
    sparse = _run(backend, n, m, log, source, use_doubling=True,
                  batch_deletions=False, frontier_mode=mode,
                  frontier_cap=16, **kw)
    q_d = _oracle_check(dense, n, source)
    q_s = _oracle_check(sparse, n, source)
    np.testing.assert_array_equal(q_d.dist, q_s.dist)
    np.testing.assert_array_equal(q_d.parent, q_s.parent)
    assert dense.n_rounds == sparse.n_rounds
    assert dense.n_messages == sparse.n_messages


def test_backends_identical_parents_under_pervasive_ties():
    """Unit weights make equal-cost predecessors pervasive (paper §5.4); the
    smallest-src-id rule must make all backends pick the same parent."""
    n, src, dst, w = generators.erdos_renyi(100, 900, seed=21)
    w = np.ones_like(w)
    log = window.sliding_window_stream(src, dst, w, window=300, delta=0.5,
                                       seed=21, query_every=400)
    res = {}
    for backend in ("segment", "ellpack", "sliced"):
        eng = SSSPDelEngine(EngineConfig(n, len(src) + 64, 2,
                                         relax_backend=backend,
                                         **BACKEND_KW[backend]))
        eng.ingest_log(log)
        res[backend] = _oracle_check(eng, n, 2)
    for backend in ("ellpack", "sliced"):
        np.testing.assert_array_equal(res["segment"].dist, res[backend].dist)
        np.testing.assert_array_equal(res["segment"].parent,
                                      res[backend].parent)


def test_capacity_doubling_under_degree_growth():
    """A hub whose in-degree doubles batch over batch must force repeated
    capacity-doubling rebuilds, each preserving oracle-exactness."""
    n, hub = 130, 0
    eng = SSSPDelEngine(EngineConfig(n, 512, 1, relax_backend="ellpack",
                                     ell_init_k=2))
    eng.ingest_log(ev.adds([1], [hub], [10.0]))
    k_seen = {eng.backend.planner.k}
    nxt = 2
    for size in (4, 8, 16, 32, 64):
        tails = np.arange(nxt, nxt + size)
        nxt += size
        eng.ingest_log(ev.adds([1] * size, tails, [1.0] * size))  # reach tails
        eng.ingest_log(ev.adds(tails, [hub] * size,
                               np.linspace(2.0, 3.0, size)))
        k_seen.add(eng.backend.planner.k)
        _oracle_check(eng, n, 1)
    assert eng.backend.planner.rebuilds >= 3
    assert len(k_seen) >= 3, f"ELL width never doubled: {sorted(k_seen)}"


def test_ellpack_oracle_at_every_query_point():
    n, m, log = _dynamic_stream(seed=5, delta=0.8)
    eng = SSSPDelEngine(EngineConfig(n, m + 64, 0, relax_backend="ellpack",
                                     ell_init_k=2))
    for batch in log.runs():
        if batch.kind == ev.ADD:
            eng._ingest_adds(batch)
        elif batch.kind == ev.DEL:
            eng._ingest_dels(batch)
        else:
            _oracle_check(eng, n, 0)
    _oracle_check(eng, n, 0)


def test_ellpack_min_duplicate_policy_matches_segment():
    # repeated adds of the same edge with shrinking weights must propagate
    # as weight-decreases under on_duplicate="min" in all backends
    n = 8
    tiny = {"segment": {},
            "ellpack": dict(ell_init_k=2),
            "sliced": dict(sliced_slice_rows=4, sliced_hub_k=2,
                           sliced_init_k=1)}
    res = {}
    for backend in ("segment", "ellpack", "sliced"):
        eng = SSSPDelEngine(EngineConfig(
            n, 32, 0, relax_backend=backend, on_duplicate="min",
            **tiny[backend]))
        eng.ingest_log(ev.adds([0, 1, 0, 0], [1, 2, 2, 1],
                               [4.0, 1.0, 9.0, 2.0]))
        eng.ingest_log(ev.adds([0], [1], [1.0]))   # decrease 0->1 to 1.0
        eng.ingest_log(ev.adds([0], [2], [20.0]))  # increase is dropped
        res[backend] = _oracle_check(eng, n, 0)
    for backend in ("ellpack", "sliced"):
        np.testing.assert_array_equal(res["segment"].dist, res[backend].dist)
        np.testing.assert_array_equal(res["segment"].parent,
                                      res[backend].parent)
    assert res["segment"].dist[2] == pytest.approx(2.0)


@pytest.mark.parametrize("backend", ["ellpack", "sliced"])
def test_ell_backends_checkpoint_restore_roundtrip(backend):
    n, m, log = _dynamic_stream(seed=9)
    kw = BACKEND_KW[backend]
    eng = SSSPDelEngine(EngineConfig(n, m + 64, 0, relax_backend=backend,
                                     **kw))
    half = len(log) // 2
    eng.ingest_log(log[:half])
    ckpt = eng.checkpoint()
    eng.ingest_log(log[half:])
    want = eng.query()

    eng2 = SSSPDelEngine(EngineConfig(n, m + 64, 0, relax_backend=backend,
                                      **{k: v for k, v in kw.items()
                                         if not k.startswith("ell_")}))
    eng2.restore(ckpt)
    eng2.ingest_log(log[half:])
    got = eng2.query()
    np.testing.assert_array_equal(want.dist, got.dist)
    np.testing.assert_array_equal(want.parent, got.parent)
    _oracle_check(eng2, n, 0)


def test_arch_config_bridges_backend_selection():
    import dataclasses
    from repro.configs import sssp_del as c_sssp
    arch = dataclasses.replace(c_sssp.REDUCED, relax_backend="ellpack",
                               num_vertices=64, ell_init_k=2)
    eng = arch.make_engine(edge_capacity=256, source=0)
    assert isinstance(eng, SSSPDelEngine)
    assert isinstance(eng.backend, EllpackBackend)
    eng.ingest_log(ev.adds([0, 1, 2], [1, 2, 3], [1.0, 1.0, 1.0]))
    _oracle_check(eng, 64, 0)
    sh = dataclasses.replace(arch, edges_per_part=256) \
        .make_engine(partitions=1, source=0)
    assert sh.cfg.relax_backend == "ellpack" and sh.cfg.ell_init_k == 2
    assert sh.cfg.edges_per_part == 256 and sh.P == 1


def test_arch_config_deprecated_bridges_warn_but_work():
    """engine_config / sharded_engine_config stay as thin shims that point
    at make_engine (DESIGN.md §11.5)."""
    import dataclasses
    import warnings
    from repro.configs import sssp_del as c_sssp
    arch = dataclasses.replace(c_sssp.REDUCED, num_vertices=64,
                               edges_per_part=256)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        cfg = arch.engine_config(edge_capacity=256, source=0)
        sh_cfg = arch.sharded_engine_config(source=0)
    assert [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert cfg.num_vertices == 64 and sh_cfg.edges_per_part == 256


@pytest.mark.parametrize("backend", ["ellpack", "sliced"])
def test_ell_backends_non_tree_deletion_is_free(backend):
    n = 6
    eng = SSSPDelEngine(EngineConfig(n, 64, 0, relax_backend=backend,
                                     **BACKEND_KW[backend]))
    eng.ingest_log(ev.adds([0, 0, 1], [1, 2, 2], [1.0, 1.0, 5.0]))
    rounds_before = eng.n_rounds
    eng.ingest_log(ev.dels([1], [2]))  # not a tree edge (0->2 is shorter)
    assert eng.n_rounds == rounds_before  # stats stay zero without a host sync
    _oracle_check(eng, n, 0)


def test_backends_bit_identical_on_power_law_hub_stream():
    """The sliced backend's home turf (DESIGN.md §6): a mixed ADD/DEL/QUERY
    stream over in-degree power-law hubs, where dense ELL's global K blows
    up and hub rows run through BOTH lanes (slice cells + overflow).  All
    three backends must stay bit-identical in (dist, parent) and stats, and
    the unit weights make equal-cost predecessors pervasive."""
    n, m = 128, 1100
    nv, src, dst, w = generators.power_law_hubs(n, m, n_hubs=3, seed=31,
                                                orientation="in")
    source = int(np.bincount(dst, minlength=nv).argmax())  # a hub
    log = window.sliding_window_stream(src, dst, w, window=len(src) // 3,
                                       delta=0.5, seed=31,
                                       query_every=len(src) // 2)
    hub_kw = {"segment": {},
              "ellpack": dict(ell_init_k=2),
              "sliced": dict(sliced_slice_rows=32, sliced_hub_k=8,
                             sliced_init_k=1)}
    res = {}
    for backend in ("segment", "ellpack", "sliced"):
        eng = SSSPDelEngine(EngineConfig(
            nv, len(src) + 64, source, relax_backend=backend,
            **hub_kw[backend]))
        eng.ingest_log(log)
        res[backend] = (_oracle_check(eng, nv, source), eng)
    q_seg, seg = res["segment"]
    for backend in ("ellpack", "sliced"):
        q, eng = res[backend]
        np.testing.assert_array_equal(q_seg.dist, q.dist)
        np.testing.assert_array_equal(q_seg.parent, q.parent)
        assert seg.n_rounds == eng.n_rounds
        assert seg.n_messages == eng.n_messages
    sld = res["sliced"][1].backend
    assert sld.planner.spills >= 1 or sld.planner.ofill > 0, \
        "hub stream never touched the overflow lane"
    # the hybrid stores far fewer device values than the dense block it
    # replaces (ELL cell = idx+w, overflow entry = src+dst+w)
    dense_vals = 2 * res["ellpack"][1].backend.state.nbr_w.size
    hybrid_vals = 2 * sld.state.flat_w.size + 3 * sld.state.ow.size
    assert hybrid_vals < dense_vals, (hybrid_vals, dense_vals)
