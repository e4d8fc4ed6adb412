"""Frontier-compacted sparse epochs (DESIGN.md §12): the compaction
primitive's properties (round-trip, exact count, -1 padding, cap
truncation), the gathered-rows kernel's bit-parity with its jnp reference,
and the engine-level contract — ``frontier_mode="sparse"/"auto"`` must be
bit-identical in (dist, parent) AND equal in (rounds, messages) to the
dense path on any dynamic stream, at any ladder capacity (a tiny
``frontier_cap`` forces the in-``cond`` dense fallback every wave, so the
fallback branch is exercised under the same assertion).
"""
import inspect

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import events as ev
from repro.core import frontier as frontier_mod
from repro.core.engine import ROUTES, EngineConfig, SSSPDelEngine
from repro.graphs import generators, window
from repro.kernels.relax.gather import (gathered_rows_relax,
                                        gathered_rows_relax_ref)


# ----------------------------------------------------- compaction primitive
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n,cap", [(64, 64), (257, 32), (1000, 256),
                                   (3000, 256)])
def test_compact_mask_roundtrip(seed, n, cap):
    rng = np.random.default_rng(seed)
    mask = rng.random(n) < rng.uniform(0.0, 0.5)
    wl, count = frontier_mod.compact_mask(jnp.asarray(mask), cap=cap)
    wl = np.asarray(wl)
    assert int(count) == int(mask.sum())          # exact occupancy, always
    assert wl.shape == (cap,)
    k = min(int(mask.sum()), cap)
    # kept slots are the first k set vertices in ascending order ...
    np.testing.assert_array_equal(wl[:k], np.flatnonzero(mask)[:k])
    # ... and everything past them is -1 padding
    assert (wl[k:] == -1).all()
    if int(mask.sum()) <= cap:
        back = np.asarray(frontier_mod.worklist_to_mask(jnp.asarray(wl), n))
        np.testing.assert_array_equal(back, mask)  # lossless round-trip


@pytest.mark.parametrize("n,block", [(1, 4), (7, 4), (8, 4), (65, 4),
                                     (1000, 16), (70000, 1024)])
def test_prefix_sum_matches_cumsum(n, block):
    x = np.random.default_rng(n).integers(0, 9, n).astype(np.int32)
    got = frontier_mod.prefix_sum(jnp.asarray(x), block)
    np.testing.assert_array_equal(np.asarray(got), np.cumsum(x))


def test_compact_mask_overflow_truncates_and_reports():
    n = 100
    mask = jnp.ones((n,), jnp.bool_)
    wl, count = frontier_mod.compact_mask(mask, cap=16)
    assert int(count) == n      # the ladder's dense-fallback signal
    np.testing.assert_array_equal(np.asarray(wl), np.arange(16))


def test_capacity_ladder_shape():
    for n in (10, 300, 1 << 20):
        caps = frontier_mod.capacity_ladder(n)
        assert caps == tuple(sorted(caps)) and caps[0] >= 1
    assert frontier_mod.capacity_ladder(1 << 20, cap=512) == (256, 512)
    # explicit cap is pow2-rounded and clamped to next_pow2(n)
    assert frontier_mod.capacity_ladder(100, cap=4096)[-1] == 128


# ------------------------------------------------------ gathered-rows kernel
@pytest.mark.parametrize("seed", [0, 3])
def test_gather_kernel_matches_reference(seed):
    rng = np.random.default_rng(seed)
    m, n = 85, 40                   # 1-D compacted edge list
    src = rng.integers(0, n, m).astype(np.int32)
    wd = np.where(rng.random(m) < 0.9,
                  rng.uniform(0, 3, m), np.inf).astype(np.float32)
    nbr = rng.integers(0, n, m).astype(np.int32)
    w = rng.uniform(0.1, 1.0, m).astype(np.float32)
    mask = rng.random(m) < 0.7
    args = (jnp.asarray(wd), jnp.asarray(src), jnp.asarray(nbr),
            jnp.asarray(w), jnp.asarray(mask))
    b_ref, a_ref = gathered_rows_relax_ref(*args, num_rows=n)
    b_krn, a_krn = gathered_rows_relax(*args, num_rows=n, interpret=True)
    np.testing.assert_array_equal(np.asarray(b_ref), np.asarray(b_krn))
    np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_krn))


# ----------------------------------------------------- engine-level parity
def _stream(seed, *, n=90, m=520, delta=0.6):
    n, src, dst, w = generators.erdos_renyi(n, m, seed=seed)
    log = window.sliding_window_stream(src, dst, w, window=m // 3,
                                       delta=delta, seed=seed,
                                       query_every=m // 2)
    return n, len(src), log


def _run(n, cap, log, source, **kw):
    eng = SSSPDelEngine(EngineConfig(n, cap + 64, source, **kw))
    eng.ingest_log(log)
    return eng


def _assert_same(ref, eng):
    qr, qe = ref.query(), eng.query()
    np.testing.assert_array_equal(np.asarray(qr.dist), np.asarray(qe.dist))
    np.testing.assert_array_equal(np.asarray(qr.parent),
                                  np.asarray(qe.parent))
    np.testing.assert_array_equal(np.asarray(ref.n_rounds),
                                  np.asarray(eng.n_rounds))
    np.testing.assert_array_equal(np.asarray(ref.n_messages),
                                  np.asarray(eng.n_messages))


@pytest.mark.parametrize("mode", ["sparse", "auto"])
@pytest.mark.parametrize("schedule", ["rounds", "buckets"])
def test_sparse_engine_bit_identical(mode, schedule):
    n, m, log = _stream(seed=31)
    ref = _run(n, m, log, 3, wave_schedule=schedule, frontier_mode="dense")
    eng = _run(n, m, log, 3, wave_schedule=schedule, frontier_mode=mode)
    _assert_same(ref, eng)


def test_sparse_tiny_cap_forces_dense_fallback():
    """frontier_cap small enough that real cascades overflow every rung:
    the ladder's final (dense relax_round) branch must carry the epoch and
    stay bit-identical."""
    n, m, log = _stream(seed=32)
    ref = _run(n, m, log, 3, frontier_mode="dense")
    eng = _run(n, m, log, 3, frontier_mode="sparse", frontier_cap=8)
    _assert_same(ref, eng)


def test_sparse_pallas_kernel_path():
    n, m, log = _stream(seed=33)
    ref = _run(n, m, log, 3, frontier_mode="dense")
    eng = _run(n, m, log, 3, frontier_mode="sparse", frontier_kernel=True)
    _assert_same(ref, eng)


def test_sparse_batched_sources():
    n, m, log = _stream(seed=34)
    srcs = (3, 17, 40)
    ref = _run(n, m, log, 0, sources=srcs, frontier_mode="dense")
    eng = _run(n, m, log, 0, sources=srcs, frontier_mode="sparse",
               frontier_cap=16)
    _assert_same(ref, eng)


def test_frontier_occupancy_counter_surfaces():
    n, m, log = _stream(seed=35)
    eng = _run(n, m, log, 3, observability=True)      # the default route
    occ = eng.metrics_snapshot()["counters"].get("frontier_occupancy", 0)
    assert occ > 0   # ladder epochs fold per-wave active counts (§2.4)
    dense = _run(n, m, log, 3, frontier_mode="dense", observability=True)
    assert "frontier_occupancy" not in dense.metrics_snapshot()["counters"]


def test_frontier_knob_discipline():
    with pytest.raises(ValueError, match="frontier_mode"):
        EngineConfig(10, 16, 0, frontier_mode="bogus")
    with pytest.raises(ValueError, match="frontier_cap"):
        # a ladder knob on the dense reference
        EngineConfig(10, 16, 0, frontier_mode="dense", frontier_cap=64)


# ------------------------------------- the default route against the dense
HUB_K = inspect.signature(frontier_mod.OutAdjacency).parameters[
    "hub_k"].default


def _hub_graph(seed):
    """A skewed graph: vertex 0's out-degree exceeds the sidecar's hub
    threshold, so its surplus lives in the overflow lane, plus a sparse
    random remainder."""
    rng = np.random.default_rng(seed)
    n = HUB_K + 400
    hub_dst = rng.permutation(np.arange(1, n))[:HUB_K + 200]
    _, s, d, _ = generators.erdos_renyi(n, 3 * n, seed=seed)
    src = np.r_[np.zeros(len(hub_dst), np.int64), s]
    dst = np.r_[hub_dst, d]
    order = rng.permutation(len(src))
    w = rng.integers(1, 16, len(src)).astype(np.float32)
    return n, src[order], dst[order], w


def _uniform_graph(seed):
    n, s, d, _ = generators.erdos_renyi(600, 3000, seed=seed)
    w = np.random.default_rng(seed).integers(1, 16, len(s))
    return n, s, d, w.astype(np.float32)


def _churn(src, dst, w, *, batch, windows, hub_batch=False):
    """Load, then sliding windows: DEL the oldest ``batch`` live edges, ADD
    the next ``batch`` (with ``hub_batch``, every window's ADDs also
    re-insert hub arcs deleted earlier), QUERY."""
    load = len(src) - batch * windows
    parts = [ev.adds(src[:load], dst[:load], w[:load]), ev.query_marker()]
    hub = np.flatnonzero(src == 0)
    for k in range(windows):
        gone = np.arange(k * batch, (k + 1) * batch)
        new = np.arange(load + k * batch, load + (k + 1) * batch)
        if hub_batch:
            gone = np.union1d(gone, hub[2 * k:2 * k + 2])
            new = np.union1d(new, hub[2 * k - 2:2 * k] if k else [])
        new = new.astype(np.int64)
        parts += [ev.dels(src[gone], dst[gone]),
                  ev.adds(src[new], dst[new], w[new]), ev.query_marker()]
    return ev.EventLog.concatenate(parts)


def _same_state(ref, eng):
    _assert_same(ref, eng)
    assert ref.rounds_by_kind == eng.rounds_by_kind


@pytest.mark.parametrize("case", ["skewed", "skewed_hub_tails", "uniform",
                                  "overflows_every_rung"])
def test_default_route_bit_identical_to_dense(case):
    """The default route (seed wave + ladder, decided per wave on the
    device) against ``frontier_mode="dense"`` over sliding-window churn:
    (dist, parent, rounds, rounds_by_kind, messages) identical."""
    graph = _uniform_graph if case == "uniform" else _hub_graph
    n, src, dst, w = graph(41)
    log = _churn(src, dst, w, batch=24, windows=6,
                 hub_batch=case == "skewed_hub_tails")
    kw = dict(batch_deletions=True)
    if case == "overflows_every_rung":
        kw["frontier_cap"] = 8
    ref = _run(n, len(src), log, 5, frontier_mode="dense",
               batch_deletions=True)
    eng = _run(n, len(src), log, 5, **kw)
    _same_state(ref, eng)
    routes = eng.rounds_by_route
    assert routes["seed"] == 7          # the load and one per window
    if case == "overflows_every_rung":
        assert routes["dense"] > 0      # the ladder's own dense fallback
    else:
        assert routes["sparse"] > 0
    if case != "uniform":
        assert eng._out.planner.ofill > 0   # the overflow lane is live


# ------------------------------------------------------------- the sidecar
def _sidecar(n, src, dst, w, hub_k):
    from repro.core import ingest
    alloc = ingest.make_allocator(len(src) + 16)
    out = frontier_mod.OutAdjacency(n, len(src) + 16, hub_k=hub_k)
    out.apply_adds(alloc.plan_adds(src, dst, w), alloc)
    return alloc, out


def _as_sliced(out):
    from repro.core.backends.sliced import SlicedEllState
    st = out.state
    return SlicedEllState(
        flat_idx=st.nbr, flat_w=st.w, fill=st.fill, base=st.base,
        rowk=jnp.asarray(out.planner.rowk, jnp.int32), osrc=st.onbr,
        odst=st.orow, ow=st.ow)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_position_tombstone_matches_search(seed):
    """Tombstoning at the host-kept positions equals the old device search
    (``sliced_delete``, which matches every deleted edge against the cells
    and the whole overflow lane), on deletions that hit both; the per-row
    overflow counts follow."""
    from repro.core.backends.sliced import sliced_delete
    rng = np.random.default_rng(seed)
    n, src, dst, w = _hub_graph(seed)
    alloc, out = _sidecar(n, src, dst, w, hub_k=64)
    assert out.planner.ofill > 0
    pick = rng.choice(len(src), 300, replace=False)
    want = sliced_delete(_as_sliced(out), jnp.asarray(src[pick]),
                         jnp.asarray(dst[pick]), width=out.planner.max_width)
    slots, psrc, _ = alloc.plan_dels(src[pick], dst[pick])
    spilled_before = (out.at[slots] >= out.planner.cells).sum()
    assert 0 < spilled_before < len(slots)   # both lanes are hit
    out.apply_dels(slots, psrc)
    np.testing.assert_array_equal(np.asarray(out.state.w),
                                  np.asarray(want.flat_w))
    np.testing.assert_array_equal(np.asarray(out.state.ow),
                                  np.asarray(want.ow))
    live = np.isfinite(np.asarray(out.state.ow))
    np.testing.assert_array_equal(
        np.asarray(out.state.ocount),
        np.bincount(np.asarray(out.state.orow)[live],
                    minlength=out.planner.rows))


def test_sidecar_churn_after_load_does_not_rebuild():
    """Sliding-window churn of a few tenths of a percent of the edges after
    the load (the benchmark's windows) fits the width floor: the sidecar's
    planner never rebuilds after the load's."""
    n, s, d, w = generators.rmat(11, 16, seed=3)
    keep = s < d
    arcs = [np.stack([a, b], 1).ravel() for a, b in
            ((s[keep], d[keep]), (d[keep], s[keep]), (w[keep], w[keep]))]
    log = _churn(*arcs, batch=64, windows=12)
    eng = SSSPDelEngine(EngineConfig(n, len(arcs[0]) + 64, 0,
                                     batch_deletions=True))
    head = len(arcs[0]) - 64 * 12 + 1      # the load and its query
    eng.ingest_log(log[:head])
    rebuilds = eng._out.planner.rebuilds
    eng.ingest_log(log[head:])
    assert eng._out.planner.rebuilds == rebuilds == 1


@pytest.mark.parametrize("kw", [
    dict(),
    dict(frontier_mode="dense"),
    dict(frontier_mode="sparse", frontier_cap=16),
    dict(wave_schedule="buckets"),
    dict(frontier_mode="sparse", wave_schedule="buckets"),
    dict(sources=(3, 17, 40)),
], ids=["default", "dense", "sparse", "buckets", "sparse-buckets",
        "batched"])
def test_route_counters_sum_to_n_rounds(kw):
    n, m, log = _stream(seed=36)
    eng = _run(n, m, log, 3, **kw)
    routes = eng.rounds_by_route
    assert set(routes) == set(ROUTES)
    total = sum(np.asarray(v) for v in routes.values())
    np.testing.assert_array_equal(total, np.asarray(eng.n_rounds))
    assert all((np.asarray(v) >= 0).all() for v in routes.values())
    reported = eng.query().epoch_stats["rounds_by_route"]
    for k in ROUTES:
        np.testing.assert_array_equal(reported[k], routes[k])
    ladder = kw.get("frontier_mode") == "sparse" or kw == {}
    assert np.any(routes["sparse"]) == ladder == eng._ladder
    assert np.any(routes["seed"]) == (ladder and "wave_schedule" not in kw)


def test_batched_sources_never_take_the_ladder():
    n, m, log = _stream(seed=37)
    eng = _run(n, m, log, 0, sources=(3, 17, 40))
    assert not eng._ladder
    routes = eng.rounds_by_route
    np.testing.assert_array_equal(routes["seed"], 0)
    np.testing.assert_array_equal(routes["sparse"], 0)
    np.testing.assert_array_equal(routes["dense"] + routes["invalidation"]
                                  + routes["pull"], eng.n_rounds)
