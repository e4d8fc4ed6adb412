"""SSSP-Del paper benchmarks — one function per paper table/figure.

  table2_static_baseline  — Galois-analogue static solve (Conv/Load/SP) vs
                            streaming ingest + on-demand solve (paper Table 2)
  fig1_query_latency      — SSSP-Del vs ReMo-from-scratch across
                            (window x delta) configs (paper Fig. 1)
  fig2_latency_over_time  — latency growth along the stream (paper Fig. 2)
  fig3_source_selection   — latency across datasets x top-3 sources (Fig. 3)
  fig4_stability          — predecessor stability vs baseline (Fig. 4)
  fig5_throughput         — ingest events/s vs delete probability (Fig. 5)
  fig6_batch_bsp          — GraphBolt-model batch engine vs on-demand
                            queries at matched intervals (Fig. 6)

Every run cross-checks the final tree against the Dijkstra oracle.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common as C
from repro.core import events as ev
from repro.core import oracle
from repro.core.baseline import BatchedBSPEngine, ReMoBaseline, StaticSolver
from repro.core.engine import EngineConfig, SSSPDelEngine


def _engine(ds: C.Dataset, source: int, cap_mult: float = 1.3,
            **kw) -> SSSPDelEngine:
    cap = int(len(ds.src) * cap_mult) + 64
    return SSSPDelEngine(EngineConfig(num_vertices=ds.n, edge_capacity=cap,
                                      source=int(source), **kw))


def _check_oracle(eng: SSSPDelEngine, sink: C.CsvSink, tag: str) -> None:
    e = eng.state.edges
    src, dst, w = (np.asarray(e.src), np.asarray(e.dst), np.asarray(e.w))
    act = np.asarray(e.active)
    dist_ref, _ = oracle.dijkstra(eng.cfg.num_vertices, src[act], dst[act],
                                  w[act], eng.cfg.source)
    dist = np.asarray(eng.state.sssp.dist)
    ok = bool(np.allclose(np.where(np.isfinite(dist), dist, -1),
                          np.where(np.isfinite(dist_ref), dist_ref, -1),
                          rtol=1e-5, atol=1e-5))
    sink.emit(tag, oracle_match=ok)
    assert ok, f"{tag}: engine diverged from Dijkstra oracle"


def table2_static_baseline(sink: C.CsvSink, small: bool) -> None:
    for ds in C.datasets(small):
        log = C.stream_for(ds, window_frac=1.0, delta=0.0, query_every=10**9)
        # static path (Galois analogue): convert -> solve
        solver = StaticSolver(ds.n)
        conv_s = solver.convert(log)
        rep = solver.solve(int(ds.sources[0]))
        # streaming path: ingest while maintaining the tree, then query
        eng = _engine(ds, ds.sources[0])
        t0 = time.perf_counter()
        res = eng.ingest_log(log)
        ingest_s = time.perf_counter() - t0
        q = eng.query()
        match = bool(np.allclose(
            np.where(np.isfinite(q.dist), q.dist, -1),
            np.where(np.isfinite(rep.dist), rep.dist, -1)))
        sink.emit("table2", dataset=ds.name, conv_s=f"{conv_s:.3f}",
                  static_sp_ms=f"{rep.solve_s * 1e3:.1f}",
                  ingest_s=f"{ingest_s:.3f}",
                  dyn_query_ms=f"{q.latency_s * 1e3:.3f}",
                  static_vs_dyn_match=match)


def fig1_query_latency(sink: C.CsvSink, small: bool) -> None:
    ds = C.datasets(small)[1]  # web-Google-like
    for wf in (0.1, 0.4):
        for delta in (0.1, 0.5):
            q_every = max(1, int(len(ds.src) * wf / 10))
            log = C.stream_for(ds, window_frac=wf, delta=delta,
                               query_every=q_every)
            eng = _engine(ds, ds.sources[0])
            ours = [r.latency_s for r in eng.ingest_log(log)]
            base = ReMoBaseline(ds.n, int(len(ds.src) * 1.3) + 64,
                                int(ds.sources[0]))
            theirs = [r.latency_s for r in base.ingest_log(log)]
            speedup = C.pctile(theirs, 50) / max(C.pctile(ours, 50), 1e-9)
            sink.emit("fig1", dataset=ds.name, window_frac=wf, delta=delta,
                      ours_p50_ms=f"{C.pctile(ours, 50)*1e3:.3f}",
                      base_p50_ms=f"{C.pctile(theirs, 50)*1e3:.3f}",
                      median_speedup=f"{speedup:.1f}x")
            _check_oracle(eng, sink, "fig1_oracle")


def fig2_latency_over_time(sink: C.CsvSink, small: bool) -> None:
    ds = C.datasets(small)[1]
    q_every = max(1, len(ds.src) // 12)
    log = C.stream_for(ds, window_frac=0.4, delta=0.5, query_every=q_every)
    eng = _engine(ds, ds.sources[0])
    ours = [r.latency_s for r in eng.ingest_log(log)]
    base = ReMoBaseline(ds.n, int(len(ds.src) * 1.3) + 64, int(ds.sources[0]))
    theirs = [r.latency_s for r in base.ingest_log(log)]
    for i, (a, b) in enumerate(zip(ours, theirs)):
        sink.emit("fig2", query_idx=i, ours_ms=f"{a*1e3:.3f}",
                  base_ms=f"{b*1e3:.3f}",
                  speedup=f"{b / max(a, 1e-9):.1f}x")


def fig3_source_selection(sink: C.CsvSink, small: bool) -> None:
    for ds in C.datasets(small):
        for rank, s in enumerate(ds.sources):
            q_every = max(1, len(ds.src) // 6)
            log = C.stream_for(ds, window_frac=0.3, delta=0.2,
                               query_every=q_every)
            eng = _engine(ds, s)
            ours = [r.latency_s for r in eng.ingest_log(log)]
            base = ReMoBaseline(ds.n, int(len(ds.src) * 1.3) + 64, int(s))
            theirs = [r.latency_s for r in base.ingest_log(log)]
            sink.emit("fig3", dataset=f"{ds.name}-{rank+1}",
                      ours_p25_ms=f"{C.pctile(ours,25)*1e3:.3f}",
                      ours_p50_ms=f"{C.pctile(ours,50)*1e3:.3f}",
                      ours_p75_ms=f"{C.pctile(ours,75)*1e3:.3f}",
                      base_p50_ms=f"{C.pctile(theirs,50)*1e3:.3f}")


def fig4_stability(sink: C.CsvSink, small: bool) -> None:
    """Paper §5.4: with UNIT weights (the paper's preprocessing for real
    graphs) many equally valid trees exist; the incremental engine keeps
    predecessors unless forced to change, while a from-scratch solver
    re-resolves every tie per query (randomize_ties models the async
    runtime's arbitrariness)."""
    ds0 = C.datasets(small)[0]
    import dataclasses as _dc
    ds = _dc.replace(ds0, w=np.ones_like(ds0.w))
    q_every = max(1, len(ds.src) // 10)
    log = C.stream_for(ds, window_frac=0.3, delta=0.3, query_every=q_every)
    eng = _engine(ds, ds.sources[0])
    base = ReMoBaseline(ds.n, int(len(ds.src) * 1.3) + 64, int(ds.sources[0]),
                        randomize_ties=True)
    ours_res = eng.ingest_log(log)
    base_res = base.ingest_log(log)
    for i, (a, b) in enumerate(zip(ours_res, base_res)):
        sa = eng.stability_vs_prev(a.parent)
        sb = base.stability_vs_prev(b.parent)
        sink.emit("fig4", query_idx=i,
                  ours_stability=f"{sa:.4f}", base_stability=f"{sb:.4f}",
                  ours_ms=f"{a.latency_s*1e3:.3f}",
                  base_ms=f"{b.latency_s*1e3:.3f}")
    _check_oracle(eng, sink, "fig4_oracle")


def fig5_throughput(sink: C.CsvSink, small: bool) -> None:
    """Paper Fig. 5 + a beyond-paper variant: the paper enforces one
    stop-the-world epoch PER deletion; ``batch_deletions=True`` coalesces a
    run of consecutive deletions into one invalidation+recompute epoch
    (correctness: Appendix A Case 2 covers the union of subtrees — see
    DESIGN.md §3), trading epoch count for throughput."""
    for ds in C.datasets(small):
        for delta in (0.01, 0.1, 0.5, 1.0):
            for batched in (False, True):
                log = C.stream_for(ds, window_frac=0.3, delta=delta,
                                   query_every=10**9)
                eng = _engine(ds, ds.sources[0], batch_deletions=batched)
                t0 = time.perf_counter()
                eng.ingest_log(log)
                dt = time.perf_counter() - t0
                _check_oracle(eng, sink, "fig5_oracle")
                sink.emit("fig5", dataset=ds.name, delta=delta,
                          mode="batched-del" if batched else "paper-faithful",
                          events=len(log), events_per_s=f"{len(log)/dt:.0f}",
                          epochs=eng.n_epochs, rounds=eng.n_rounds,
                          rounds_per_event=round(
                              int(eng.n_rounds) / len(log), 3))


def fig6_batch_bsp(sink: C.CsvSink, small: bool) -> None:
    ds = C.datasets(small)[1]
    base_log = C.stream_for(ds, window_frac=0.2, delta=0.1,
                            query_every=10**9)
    n_events = len(base_log)
    for n_queries in (4, 16, 64):
        batch = max(1, n_events // n_queries)
        # GraphBolt processing model: reconverge once per batch
        bsp = BatchedBSPEngine(ds.n, int(len(ds.src) * 1.3) + 64,
                               int(ds.sources[0]), batch)
        lat_bsp = []
        for i in range(0, n_events, batch):
            bsp.push(base_log[i:i + batch])
            dt = bsp.maybe_flush()
            if dt is not None:
                lat_bsp.append(dt)
        rest = bsp.force_flush()
        if rest:
            lat_bsp.append(rest)
        # our engine: ingest continuously, query at the same intervals
        log_q = ev.interleave_queries(base_log, batch)
        eng = _engine(ds, ds.sources[0])
        lat_ours = [r.latency_s for r in eng.ingest_log(log_q)]
        sink.emit("fig6", n_queries=n_queries, batch=batch,
                  bsp_p50_ms=f"{C.pctile(lat_bsp,50)*1e3:.2f}",
                  ours_p50_ms=f"{C.pctile(lat_ours,50)*1e3:.3f}",
                  reduction=f"{C.pctile(lat_bsp,50)/max(C.pctile(lat_ours,50),1e-9):.1f}x")


def backend_shootout(sink: C.CsvSink, small: bool) -> None:
    """Beyond-paper: segment (COO scatter-min) vs ellpack (dense gather +
    row-min over the incrementally maintained ELL block) on fig5-style
    dynamic ingest.  Bounded-degree streams — the regime the flat ELL layout
    targets; power-law hubs run the sliced/hybrid path instead (DESIGN.md
    §6, ``hub_shootout``).

    Emits events/s per backend plus query p50 — the acceptance gate for the
    ELL backend is events/s >= segment with <10% query-latency regression.
    """
    import jax
    from repro.graphs import generators as gen

    n, m = (1 << 11, 1 << 13) if small else (1 << 13, 1 << 15)
    nv, src, dst, w = gen.erdos_renyi(n, m, seed=13)
    source = int(gen.top_in_degree_sources(nv, dst, 1)[0])
    for delta in (0.1, 0.5):
        log = C.stream_for(
            C.Dataset("er", nv, src, dst, w,
                      gen.top_in_degree_sources(nv, dst)),
            window_frac=1 / 3, delta=delta, query_every=10**9)
        eps: dict[str, float] = {}
        engines: dict[str, SSSPDelEngine] = {}
        for backend in ("segment", "ellpack"):
            for _timed in (False, True):  # first pass warms every jit shape
                eng = SSSPDelEngine(EngineConfig(
                    num_vertices=nv, edge_capacity=m + 64, source=source,
                    relax_backend=backend))
                t0 = time.perf_counter()
                eng.ingest_log(log)
                jax.block_until_ready(eng.state.sssp.dist)
                ingest_s = time.perf_counter() - t0
            eps[backend] = len(log) / ingest_s
            engines[backend] = eng
        # query = device->host readback (µs scale): interleave the reps
        # across backends so clock/GC drift cancels, report p50
        q_lat: dict[str, list[float]] = {b: [] for b in engines}
        for _rep in range(105):
            for b, eng in engines.items():
                q_lat[b].append(eng.query().latency_s)
        for backend, eng in engines.items():
            _check_oracle(eng, sink, "backend_shootout_oracle")
            planner = getattr(eng.backend, "planner", None)
            sink.emit("backend_shootout", dataset="er", n=nv, edges=m,
                      delta=delta, backend=backend, events=len(log),
                      events_per_s=round(eps[backend], 1),
                      query_p50_ms=round(C.pctile(q_lat[backend][5:], 50) * 1e3, 4),
                      rounds=eng.n_rounds,
                      rounds_per_event=round(int(eng.n_rounds) / len(log), 3),
                      ell_rebuilds=getattr(planner, "rebuilds", 0),
                      ell_k=getattr(planner, "k", 0))
        sink.emit("backend_shootout_summary", delta=delta,
                  ell_speedup=round(eps["ellpack"] / eps["segment"], 3))


def hub_shootout(sink: C.CsvSink, small: bool) -> None:
    """Beyond-paper (DESIGN.md §6): the three relaxation backends on an
    in-degree power-law hub stream — the regime the sliced/hybrid layout
    exists for.  Dense ELL pads every row to the (huge) global max
    in-degree; the sliced backend pays per-slice K plus a COO overflow lane
    for hub surplus.  Emits ingest events/s, query p50, and the device
    32-bit value count of each layout (memory proxy) per backend.

    The acceptance gate (benchmarks/check_regression.py) is sliced ingest
    >= 0.8x segment on these streams with query p50 within noise and the
    sliced layout strictly smaller than dense ELL; the sliced-vs-ellpack
    ratio is the headline the layout was built for.
    """
    import jax
    from repro.graphs import generators as gen

    n = (1 << 10) if small else (1 << 12)
    m = 8 * n
    nv, src, dst, w = gen.power_law_hubs(n, m, n_hubs=4, seed=23,
                                         orientation="in")
    source = int(gen.top_in_degree_sources(nv, dst, 1)[0])
    max_indeg = int(np.bincount(dst, minlength=nv).max())
    backends = ("segment", "ellpack", "sliced")
    for delta in (0.1, 0.5):
        log = C.stream_for(
            C.Dataset("plaw", nv, src, dst, w,
                      gen.top_in_degree_sources(nv, dst)),
            window_frac=1 / 3, delta=delta, query_every=10**9)
        eps: dict[str, float] = {}
        engines: dict[str, SSSPDelEngine] = {}
        for backend in backends:
            # first pass warms every jit shape; every backend then takes
            # best-of-2 timed passes (one-sided noise on a shared runner
            # only ever slows a pass down — best-of is the stable ratio
            # estimator, and all ratios compare like for like)
            rates = []
            for timed in (False, True, True):
                eng = SSSPDelEngine(EngineConfig(
                    num_vertices=nv, edge_capacity=m + 64, source=source,
                    relax_backend=backend))
                t0 = time.perf_counter()
                eng.ingest_log(log)
                jax.block_until_ready(eng.state.sssp.dist)
                if timed:
                    rates.append(len(log) / (time.perf_counter() - t0))
            eps[backend] = max(rates)
            engines[backend] = eng
        q_lat: dict[str, list[float]] = {b: [] for b in engines}
        for _rep in range(55):
            for b, eng in engines.items():
                q_lat[b].append(eng.query().latency_s)
        # layout memory proxy in 32-bit VALUES, not cells: an ELL cell is
        # (idx, w) = 2, an overflow/pool entry (src, dst, w) = 3
        sell = engines["sliced"].backend.state
        cells = {
            "segment": 3 * (m + 64),
            "ellpack": 2 * int(engines["ellpack"].backend.state.nbr_w.size),
            "sliced": 2 * int(sell.flat_w.size) + 3 * int(sell.ow.size),
        }
        for backend, eng in engines.items():
            _check_oracle(eng, sink, "hub_shootout_oracle")
            planner = getattr(eng.backend, "planner", None)
            sink.emit("hub_shootout", dataset="plaw", n=nv, edges=m,
                      max_indeg=max_indeg, delta=delta, backend=backend,
                      events=len(log), events_per_s=round(eps[backend], 1),
                      query_p50_ms=round(
                          C.pctile(q_lat[backend][5:], 50) * 1e3, 4),
                      rounds=eng.n_rounds,
                      rounds_per_event=round(int(eng.n_rounds) / len(log), 3),
                      device_values=cells[backend],
                      spills=getattr(planner, "spills", 0),
                      rebuilds=getattr(planner, "rebuilds", 0))
        sink.emit("hub_shootout_summary", delta=delta,
                  sliced_vs_segment=round(eps["sliced"] / eps["segment"], 3),
                  sliced_vs_ellpack=round(eps["sliced"] / eps["ellpack"], 3),
                  cells_vs_ellpack=round(
                      cells["sliced"] / max(cells["ellpack"], 1), 4))


def bucket_shootout(sink: C.CsvSink, small: bool) -> None:
    """Beyond-paper (DESIGN.md §9): the lazy bucketed delta-stepping
    schedule vs the eager per-event rounds schedule, raced across all three
    relaxation backends on the two stress streams — the delta=0.5
    round-bound ER stream (half the events are deletions, so the eager
    schedule pays a full converge epoch per event: the "round tax") and the
    in-degree power-law hub stream.  The bucketed legs drain INSIDE the
    timed window, so the ratio measures deferred-and-coalesced settlement,
    not skipped work; final (dist, parent) bit-identity of every leg
    against the eager segment reference is asserted in-run.

    Second half: the fused Pallas sliced-ELL wave kernel (DESIGN.md §9.4)
    vs the unfused three-dispatch composition on the settled hub layout,
    interpret mode, wave-level best-of timing.  The gates
    (benchmarks/check_regression.py): buckets >= 2.0x rounds events/s on
    the delta=0.5 ER stream, fused >= 1.0x unfused wave on hubs.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.backends.sliced import sliced_relax_wave
    from repro.graphs import generators as gen

    n_er, m_er = (1 << 11, 1 << 13) if small else (1 << 13, 1 << 15)
    nv, esrc, edst, ew = gen.erdos_renyi(n_er, m_er, seed=13)
    er = C.Dataset("er", nv, esrc, edst, ew,
                   gen.top_in_degree_sources(nv, edst))
    n_h = (1 << 10) if small else (1 << 12)
    nh, hs, hd, hw = gen.power_law_hubs(n_h, 8 * n_h, n_hubs=4, seed=23,
                                        orientation="in")
    hub = C.Dataset("plaw", nh, hs, hd, hw,
                    gen.top_in_degree_sources(nh, hd))

    delta = 0.5
    backends = ("segment", "ellpack", "sliced")
    hub_engines: dict[tuple[str, str], SSSPDelEngine] = {}
    for ds in (er, hub):
        m = len(ds.src)
        source = int(ds.sources[0])
        log = C.stream_for(ds, window_frac=1 / 3, delta=delta,
                           query_every=10**9)
        eps: dict[tuple[str, str], float] = {}
        engines: dict[tuple[str, str], SSSPDelEngine] = {}
        for backend in backends:
            for sched in ("rounds", "buckets"):
                kw = ({"wave_schedule": "buckets", "bucket_width": 1.0}
                      if sched == "buckets" else {})
                for _timed in (False, True):  # warm pass covers every shape
                    eng = SSSPDelEngine(EngineConfig(
                        num_vertices=ds.n, edge_capacity=m + 64,
                        source=source, relax_backend=backend, **kw))
                    t0 = time.perf_counter()
                    eng.ingest_log(log)
                    eng.drain()   # settle ALL deferred work inside the clock
                    jax.block_until_ready(eng.state.sssp.dist)
                    dt = time.perf_counter() - t0
                eps[(backend, sched)] = len(log) / dt
                engines[(backend, sched)] = eng
                rounds = int(eng.n_rounds)
                sink.emit("bucket_shootout", dataset=ds.name, n=ds.n,
                          edges=m, delta=delta, backend=backend,
                          schedule=sched, events=len(log),
                          events_per_s=round(eps[(backend, sched)], 1),
                          rounds=rounds,
                          rounds_per_event=round(rounds / len(log), 3))
        # the correctness contract, asserted on the benchmark stream
        # (DESIGN.md §9.2): distances are bit-identical across every
        # (backend, schedule) leg; parents too on the ER stream (continuous
        # weights, unique shortest paths).  The hub stream has UNIT weights
        # — equal-cost paths abound, and the keep-parent-on-tie rule makes
        # the winner depend on epoch arrival order, so there the schedules
        # may settle different-but-equally-valid trees: each leg's parent
        # array is instead checked as a valid SSSP tree over the live edges.
        ref = engines[("segment", "rounds")].query()
        for eng in engines.values():
            q = eng.query()
            np.testing.assert_array_equal(ref.dist, q.dist)
            if ds is er:
                np.testing.assert_array_equal(ref.parent, q.parent)
            else:
                e = eng.state.edges
                act = np.asarray(e.active)
                oracle.check_tree(
                    ds.n, np.asarray(e.src)[act], np.asarray(e.dst)[act],
                    np.asarray(e.w)[act], source,
                    np.asarray(q.dist), np.asarray(q.parent))
        _check_oracle(engines[("segment", "buckets")], sink,
                      "bucket_shootout_oracle")
        for backend in backends:
            sink.emit("bucket_shootout_summary", dataset=ds.name,
                      delta=delta, backend=backend,
                      buckets_vs_rounds=round(
                          eps[(backend, "buckets")]
                          / eps[(backend, "rounds")], 3),
                      rounds_saved=round(
                          int(engines[(backend, "rounds")].n_rounds)
                          / max(int(engines[(backend, "buckets")].n_rounds),
                                1), 2),
                      identical=True)
        if ds is hub:
            hub_engines = engines

    # --- fused Pallas wave kernel vs the unfused three-dispatch composition
    # (DESIGN.md §9.4) on the settled hub-stream sliced layout, interpret
    # mode.  Wave-level timing: best-of batches so one-sided scheduler noise
    # cannot sink the parity gate.
    eng = hub_engines[("sliced", "buckets")]
    planner = eng.backend.planner
    # race on the COMPACTED live layout — the geometry the planner builds at
    # every rebuild (spill-doubling triggers them regularly), not the
    # end-of-stream churn state whose overflow lane is mostly tombstones
    lsrc, ldst, lw = eng.alloc.active_coo()
    planner.widths, planner.ocap = planner.required_geometry(ldst)
    st = planner.rebuild(lsrc, ldst, lw)
    dist, parent = eng.state.sssp.dist, eng.state.sssp.parent
    # engine waves are always frontier-masked (converge loops, bucket
    # drains) — race the two paths the way the engine actually calls them
    frontier = jnp.asarray(np.isfinite(np.asarray(dist)))
    kw = dict(widths=tuple(planner.widths), slice_rows=planner.sr,
              num_vertices=eng.cfg.num_vertices, frontier=frontier)
    reps = 20 if small else 40
    wave_us: dict[str, float] = {}
    variants = (("jnp", dict(use_kernel=False, use_fused=False)),
                ("pallas_unfused", dict(use_kernel=True, use_fused=False)),
                ("pallas_fused", dict(use_fused=True)))
    for name, v in variants:
        jax.block_until_ready(
            sliced_relax_wave(dist, parent, st, **v, **kw))
        best = float("inf")
        for _batch in range(5):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = sliced_relax_wave(dist, parent, st, **v, **kw)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / reps)
        wave_us[name] = best * 1e6
        sink.emit("bucket_shootout_fused", dataset="plaw", impl=name,
                  n=eng.cfg.num_vertices, overflow_cap=int(st.ow.size),
                  wave_us=round(wave_us[name], 1))
    outs = {name: sliced_relax_wave(dist, parent, st, **v, **kw)
            for name, v in variants}
    for name in ("pallas_unfused", "pallas_fused"):
        np.testing.assert_array_equal(np.asarray(outs["jnp"][0]),
                                      np.asarray(outs[name][0]))
        np.testing.assert_array_equal(np.asarray(outs["jnp"][1]),
                                      np.asarray(outs[name][1]))
    # the gate pairing (check_regression): the fused kernel must beat the
    # EXISTING Pallas sliced wave (that is what "interpret mode" is a
    # property of); the jnp three-dispatch path rides along as a loose
    # lower bound — it has no kernel-dispatch emulation cost to pay, so
    # parity-within-overhead (>= 0.8x) is the honest expectation there
    sink.emit("bucket_shootout_fused_summary",
              fused_vs_pallas=round(
                  wave_us["pallas_unfused"] / wave_us["pallas_fused"], 3),
              fused_vs_jnp=round(wave_us["jnp"] / wave_us["pallas_fused"],
                                 3),
              identical=True)


def dist_engine(sink: C.CsvSink, small: bool) -> None:
    """Beyond-paper (DESIGN.md §5): the sharded dynamic engine vs the
    single-device engine on the same mixed ADD/DEL stream — ingest
    throughput and query p50.  P = local device count (1 on the CI runner;
    8 when the process is started with forced host devices), so on one
    device this measures the pure sharding overhead: shard_map epochs plus
    per-partition host planning, with bit-identical results as the gate.

    Second half (DESIGN.md §7.2): the three relaxation backends ON the
    sharded engine, racing ingest over an in-degree power-law hub stream —
    sharded-sliced must hold >= 0.95x sharded-segment with the three-way
    parity record intact.
    """
    import jax
    from repro.core.dist_engine import (ShardedEngineConfig,
                                        ShardedSSSPDelEngine)
    from repro.graphs import generators as gen

    n, m = (1 << 11, 1 << 13) if small else (1 << 13, 1 << 15)
    nv, src, dst, w = gen.erdos_renyi(n, m, seed=17)
    source = int(gen.top_in_degree_sources(nv, dst, 1)[0])
    n_parts = len(jax.devices())

    def _mk_engine(name):
        if name == "single":
            return SSSPDelEngine(EngineConfig(
                num_vertices=nv, edge_capacity=m + 64, source=source))
        return ShardedSSSPDelEngine(ShardedEngineConfig(
            num_vertices=nv, edges_per_part=m + 64, source=source))

    for delta in (0.1, 0.5):
        log = C.stream_for(
            C.Dataset("er", nv, src, dst, w,
                      gen.top_in_degree_sources(nv, dst)),
            window_frac=1 / 3, delta=delta, query_every=10**9)
        eps: dict[str, float] = {}
        engines: dict[str, object] = {}
        for name in ("single", "sharded"):
            for _timed in (False, True):  # first pass warms every jit shape
                eng = _mk_engine(name)
                t0 = time.perf_counter()
                eng.ingest_log(log)
                jax.block_until_ready(
                    eng.state.sssp.dist if name == "single" else eng.dist)
                ingest_s = time.perf_counter() - t0
            eps[name] = len(log) / ingest_s
            engines[name] = eng
        q_lat: dict[str, list[float]] = {b: [] for b in engines}
        res: dict[str, object] = {}
        for _rep in range(55):
            for b, eng in engines.items():
                res[b] = eng.query()
                q_lat[b].append(res[b].latency_s)
        # the equivalence contract, checked on the benchmark stream too
        np.testing.assert_array_equal(res["single"].dist, res["sharded"].dist)
        np.testing.assert_array_equal(res["single"].parent,
                                      res["sharded"].parent)
        _check_oracle(engines["single"], sink, "dist_engine_oracle")
        for name, eng in engines.items():
            sink.emit("dist_engine", dataset="er", n=nv, edges=m,
                      parts=(1 if name == "single" else n_parts),
                      delta=delta, engine=name, events=len(log),
                      events_per_s=round(eps[name], 1),
                      query_p50_ms=round(
                          C.pctile(q_lat[name][5:], 50) * 1e3, 4),
                      rounds=eng.n_rounds,
                      rounds_per_event=round(int(eng.n_rounds) / len(log), 3))
        sink.emit("dist_engine_summary", delta=delta, parts=n_parts,
                  sharded_vs_single=round(eps["sharded"] / eps["single"], 3),
                  identical=True)

    # --- per-backend sharded ingest on an in-degree power-law hub stream
    # (DESIGN.md §7.2): the sliced layout's win must survive sharding.  The
    # gate (benchmarks/check_regression.py) is sharded-sliced ingest >=
    # 0.95x sharded-segment plus the three-way bit-parity record below.
    nh = (1 << 10) if small else (1 << 12)
    mh = 8 * nh
    nv, src, dst, w = gen.power_law_hubs(nh, mh, n_hubs=4, seed=23,
                                         orientation="in")
    source = int(gen.top_in_degree_sources(nv, dst, 1)[0])
    backends = ("segment", "ellpack", "sliced")
    for delta in (0.1, 0.5):
        log = C.stream_for(
            C.Dataset("plaw", nv, src, dst, w,
                      gen.top_in_degree_sources(nv, dst)),
            window_frac=1 / 3, delta=delta, query_every=10**9)
        eps = {}
        engines = {}
        for backend in backends:
            # best-of-2 timed passes after a warming pass (one-sided noise
            # only slows a pass down; best-of is the stable ratio estimator)
            rates = []
            for timed in (False, True, True):
                eng = ShardedSSSPDelEngine(ShardedEngineConfig(
                    num_vertices=nv, edges_per_part=mh + 64, source=source,
                    relax_backend=backend))
                t0 = time.perf_counter()
                eng.ingest_log(log)
                jax.block_until_ready(eng.dist)
                if timed:
                    rates.append(len(log) / (time.perf_counter() - t0))
            eps[backend] = max(rates)
            engines[backend] = eng
        res = {b: e.query() for b, e in engines.items()}
        # the three-way sharded parity record — asserted in-run, gated in
        # check_regression via the summary row
        for other in ("ellpack", "sliced"):
            np.testing.assert_array_equal(res["segment"].dist,
                                          res[other].dist)
            np.testing.assert_array_equal(res["segment"].parent,
                                          res[other].parent)
        # parity alone can't catch a bug shared by all three sharded
        # engines — anchor the trio against the Dijkstra oracle over the
        # live edge set (from the per-partition host mirrors)
        coo = [a.active_coo() for a in engines["segment"].allocs]
        e_src, e_dst, e_w = (np.concatenate([c[i] for c in coo])
                             for i in range(3))
        dist_ref, _ = oracle.dijkstra(nv, e_src, e_dst, e_w, source)
        ok = bool(np.allclose(
            np.where(np.isfinite(res["segment"].dist),
                     res["segment"].dist, -1),
            np.where(np.isfinite(dist_ref), dist_ref, -1),
            rtol=1e-5, atol=1e-5))
        sink.emit("dist_engine_backends_oracle", delta=delta, oracle_match=ok)
        assert ok, "sharded backends diverged from Dijkstra oracle"
        for backend, eng in engines.items():
            sink.emit("dist_engine", dataset="plaw", n=nv, edges=mh,
                      parts=n_parts, delta=delta,
                      engine=f"sharded-{backend}", events=len(log),
                      events_per_s=round(eps[backend], 1),
                      rounds=eng.n_rounds,
                      rounds_per_event=round(int(eng.n_rounds) / len(log), 3))
        sink.emit("dist_engine_backends_summary", delta=delta, parts=n_parts,
                  sliced_vs_segment=round(eps["sliced"] / eps["segment"], 3),
                  ellpack_vs_segment=round(eps["ellpack"] / eps["segment"], 3),
                  identical=True)


def serving(sink: C.CsvSink, small: bool) -> None:
    """Serving layer (DESIGN.md §8): batched multi-source trace replay on a
    power-law stream at S in {1, 4, 16} concurrent sources, measuring the
    paper's three serving metrics — per-query result latency (p50/p95/p99),
    solution stability (per-epoch dist/parent churn) and sustained
    topology-event throughput — via the repro.serving harness, plus the
    sequential baseline the regression gate compares against: 4 independent
    single-source engines replaying the same workload one after another.

    The gate (benchmarks/check_regression.py) is batched S=4 throughput
    >= 2.0x the 4-sequential-replay throughput — the batched [S, N] state's
    reason to exist: one shared graph layout, one fused epoch per batch
    instead of S.  Bit-parity of every batched lane against its
    single-source engine is asserted in-run (summary row ``identical``).
    """
    import jax
    from repro.graphs import generators as gen
    from repro.serving import TraceRecorder, replay_trace

    n = (1 << 10) if small else (1 << 11)
    m = 4 * n
    nv, src, dst, w = gen.power_law_hubs(n, m, n_hubs=4, seed=31,
                                         orientation="in")
    all_sources = [int(s) for s in gen.top_in_degree_sources(nv, dst, 16)]
    delta = 0.3
    log = C.stream_for(
        C.Dataset("plaw", nv, src, dst, w, np.asarray(all_sources[:3])),
        window_frac=1 / 3, delta=delta, query_every=10**9)

    def trace_for(sources):
        """The same topology stream with one query per served source at
        each of 8 evenly spaced collection points."""
        rec = TraceRecorder()
        step = max(1, len(log) // 8)
        for a in range(0, len(log), step):
            rec.extend_from_log(log[a:a + step])
            for s in sources:
                rec.query(source=s)
        return rec.trace()

    def best_of(n_timed, mk, trace):
        """Warm pass + best-of-n timed replays (fresh engine each pass so
        every pass replays the identical trace; one-sided scheduler noise
        only ever slows a pass down).  Returns the best report."""
        best = None
        for timed in (False,) + (True,) * n_timed:
            eng = mk()
            rep = replay_trace(eng, trace)
            jax.block_until_ready(
                eng.state.sssp.dist if hasattr(eng, "state") else eng.dist)
            if timed and (best is None
                          or rep.events_per_s > best[0].events_per_s):
                best = (rep, eng)
        return best

    def mk_batched(sources):
        return SSSPDelEngine(EngineConfig(
            num_vertices=nv, edge_capacity=m + 64, source=sources[0],
            sources=tuple(sources)))

    reports = {}
    engines = {}
    for S in (1, 4, 16):
        srcs = all_sources[:S]
        n_timed = 1 if S == 16 else 2   # S=16 is ungated — one timed pass
        reports[S], engines[S] = best_of(n_timed, lambda: mk_batched(srcs),
                                         trace_for(srcs))
        sink.emit("serving", dataset="plaw", n=nv, edges=m, delta=delta,
                  backend="segment", s=S, **reports[S].to_record())

    # sequential baseline: 4 single-source engines replay the same
    # workload back to back (each answering only its own queries)
    seq_sources = all_sources[:4]
    seq_traces = [trace_for([s]) for s in seq_sources]
    seq_engines = seq_reports = None
    best_seq = None
    for timed in (False, True, True):
        engs = [SSSPDelEngine(EngineConfig(
            num_vertices=nv, edge_capacity=m + 64, source=s))
            for s in seq_sources]
        t0 = time.perf_counter()
        reps = [replay_trace(e, t) for e, t in zip(engs, seq_traces)]
        for e in engs:
            jax.block_until_ready(e.state.sssp.dist)
        wall = time.perf_counter() - t0
        # keep wall, engines AND per-query reports from the SAME (best)
        # pass so the emitted record is internally consistent
        if timed and (best_seq is None or wall < best_seq):
            best_seq, seq_engines, seq_reports = wall, engs, reps
    n_topo = seq_traces[0].n_topology
    seq_eps = n_topo / best_seq
    seq_lat = [l for r in seq_reports for l in r.latencies]
    sink.emit("serving", dataset="plaw", n=nv, edges=m, delta=delta,
              backend="segment", s=4, engine="sequential/segment",
              n_sources=4, events=sum(len(t) for t in seq_traces),
              topology_events=n_topo, queries=sum(r.queries
                                                  for r in seq_reports),
              wall_s=round(best_seq, 4), events_per_s=round(seq_eps, 1),
              latency_p50_ms=round(C.pctile(seq_lat, 50) * 1e3, 4),
              latency_p95_ms=round(C.pctile(seq_lat, 95) * 1e3, 4),
              latency_p99_ms=round(C.pctile(seq_lat, 99) * 1e3, 4))

    # the serving equivalence contract, asserted on the benchmark stream:
    # every batched lane == its single-source engine, bit for bit
    qb = engines[4].query()
    for i, (s, eng) in enumerate(zip(seq_sources, seq_engines)):
        qs = eng.query()
        np.testing.assert_array_equal(qb.dist[i], qs.dist)
        np.testing.assert_array_equal(qb.parent[i], qs.parent)
    _check_oracle(seq_engines[0], sink, "serving_oracle")
    sink.emit("serving_summary", delta=delta, s=4,
              batched_vs_sequential=round(
                  reports[4].events_per_s / max(seq_eps, 1e-9), 3),
              batched16_vs_sequential=round(
                  reports[16].events_per_s / max(seq_eps, 1e-9), 3),
              identical=True)


def scale(sink: C.CsvSink, small: bool) -> None:
    """Paper-scale ingest trajectory (DESIGN.md §11): synthetic N-vertex /
    10N-edge ADD streams synthesized and ingested chunk-by-chunk, one
    FRESH subprocess per size so ``resource.getrusage`` peak RSS is an
    honest per-workload number (benchmarks/scale_worker.py documents the
    budget formula: pool-capacity + vertex + O(chunk) terms, never
    O(stream)).  Small mode runs N ∈ {64k, 256k}; the full run adds the
    acceptance point N=1M / E=10M.  The smallest size cross-checks the
    final tree against the Dijkstra oracle; the regression gate
    (check_regression.gate_scale) holds the events/s floor and the RSS
    ceiling from this PR onward.  On a TPU every size runs in this
    process (a child cannot reach the chip this process holds), so peak
    RSS is the bench process's high-water mark there."""
    import json
    import os
    import subprocess
    import sys

    from benchmarks import scale_worker
    from repro.kernels.relax import config as kernel_config

    sizes = [1 << 16, 1 << 18] + ([] if small else [1 << 20])
    for n in sizes:
        if kernel_config.on_tpu():
            rec = scale_worker.measure(n, 10 * n,
                                       check_oracle=n == sizes[0])
        else:
            cmd = [sys.executable, "-m", "benchmarks.scale_worker",
                   "--n", str(n), "--e", str(10 * n)]
            if n == sizes[0]:
                cmd.append("--check-oracle")
            env = dict(os.environ)
            env["PYTHONPATH"] = os.pathsep.join(
                p for p in ("src", env.get("PYTHONPATH", "")) if p)
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 env=env)
            assert out.returncode == 0, (
                f"scale worker n={n} failed:\n{out.stderr[-2000:]}")
            rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["rss_ok"], (
            f"scale n={n}: peak RSS {rec['peak_rss_mb']}MB over budget "
            f"{rec['rss_budget_mb']}MB")
        assert rec.get("oracle_match", True), f"scale n={n}: oracle mismatch"
        sink.emit("scale", **rec)


def sparse_frontier(sink: C.CsvSink, small: bool) -> None:
    """Frontier-compacted sparse epochs (DESIGN.md §12): pay for the
    affected region, not the graph.

    Two legs, both asserting bit-identity in-run (dist, parent, rounds,
    messages — the §12 contract) before emitting any timing:

      * **localized** — an N-vertex / 4N-edge base graph ingested untimed,
        then a timed phase of small ADD batches confined to a 1k-vertex
        window each: the regime the sparse path targets (a handful of
        affected vertices per epoch on a paper-scale graph).  Gate:
        sparse >= 3x dense events/s at the largest N
        (check_regression.gate_sparse_frontier).  Small mode runs
        N=256k; the full run adds the N=1M acceptance point.  Set
        ``REPRO_SCALE_DATASET=soc-livejournal1`` to source the base graph
        from the checksum-cached SNAP download instead of synthetic RMAT
        (graphs/datasets.fetch_dataset; CI stays synthetic).
      * **auto-high-occupancy** — a delta=0.5 sliding-window ER stream
        whose cascades blow past every ladder rung: ``frontier_mode=
        "auto"`` must route these epochs dense from the host-side
        occupancy bound and stay >= 0.95x the dense engine's throughput
        (the routing-overhead gate)."""
    import os

    import jax
    from repro.graphs import generators as gen

    rng = np.random.default_rng(7)

    def localized_base(n: int):
        name = os.environ.get("REPRO_SCALE_DATASET")
        if name:
            from repro.graphs import datasets as ds_mod
            path = ds_mod.fetch_dataset(name)
            s, d, w = ds_mod.parse_edge_list(path)
            _, s, d = ds_mod.compact_ids(s, d)
            keep = (s < n) & (d < n)
            return (s[keep].astype(np.int32), d[keep].astype(np.int32),
                    w[keep])
        _, s, d, w = gen.rmat(int(np.log2(n)), 4, seed=11)
        return s, d, w

    def run_localized(n: int, mode: str, batches: list) -> tuple:
        bs, bd, bw = localized_base(n)
        kw = {} if mode == "dense" else dict(frontier_mode=mode)
        eng = SSSPDelEngine(EngineConfig(
            num_vertices=n, edge_capacity=len(bs) + 8 * len(batches) + 64,
            source=0, **kw))
        eng.ingest_log(ev.adds(bs, bd, bw))          # untimed base build
        eng.ingest_log(batches[0])                   # warm the batch shape
        jax.block_until_ready(eng.state.sssp.dist)
        t0 = time.perf_counter()
        for b in batches[1:]:
            eng.ingest_log(b)
        jax.block_until_ready(eng.state.sssp.dist)
        return eng, time.perf_counter() - t0

    sizes = [1 << 18] + ([] if small else [1 << 20])
    n_batches = 48
    for n in sizes:
        # localized update batches: 8 fresh edges inside a random 1k window
        batches = []
        for _ in range(n_batches):
            ws = int(rng.integers(0, n - 1024))
            u = ws + rng.integers(0, 1024, 8)
            v = ws + rng.integers(0, 1024, 8)
            batches.append(ev.adds(u.astype(np.int64), v.astype(np.int64),
                                   rng.uniform(0.5, 1.5, 8)))
        runs = {}
        for mode in ("dense", "sparse"):
            eng, took = run_localized(n, mode, batches)
            runs[mode] = (eng, took)
        qd, qs = runs["dense"][0].query(), runs["sparse"][0].query()
        np.testing.assert_array_equal(qd.dist, qs.dist)
        np.testing.assert_array_equal(qd.parent, qs.parent)
        assert runs["dense"][0].n_rounds == runs["sparse"][0].n_rounds
        assert runs["dense"][0].n_messages == runs["sparse"][0].n_messages
        ev_count = 8 * (n_batches - 1)
        for mode, (eng, took) in runs.items():
            sink.emit("sparse_frontier", dataset="localized", n=n,
                      mode=mode, batches=n_batches - 1, batch_events=8,
                      ingest_s=round(took, 4),
                      events_per_s=round(ev_count / max(took, 1e-9), 1),
                      rounds=eng.n_rounds)
        sink.emit("sparse_frontier_summary", dataset="localized", n=n,
                  sparse_vs_dense=round(
                      runs["dense"][1] / max(runs["sparse"][1], 1e-9), 3),
                  identical=True)

    # ---- auto routing overhead on a high-occupancy stream ----
    n, m = 1 << 13, 1 << 15
    nv, src, dst, w = gen.erdos_renyi(n, m, seed=17)
    source = int(gen.top_in_degree_sources(nv, dst, 1)[0])
    log = C.stream_for(
        C.Dataset("er", nv, src, dst, w, gen.top_in_degree_sources(nv, dst)),
        window_frac=1 / 3, delta=0.5, query_every=10**9)
    times, engines = {}, {}
    for mode in ("dense", "auto"):
        kw = {} if mode == "dense" else dict(frontier_mode="auto")
        for _timed in (False, True):   # first pass warms every jit shape
            eng = SSSPDelEngine(EngineConfig(
                num_vertices=nv, edge_capacity=m + 64, source=source, **kw))
            t0 = time.perf_counter()
            eng.ingest_log(log)
            jax.block_until_ready(eng.state.sssp.dist)
            times[mode] = time.perf_counter() - t0
        engines[mode] = eng
    qd, qa = engines["dense"].query(), engines["auto"].query()
    np.testing.assert_array_equal(qd.dist, qa.dist)
    np.testing.assert_array_equal(qd.parent, qa.parent)
    assert engines["dense"].n_rounds == engines["auto"].n_rounds
    for mode, eng in engines.items():
        sink.emit("sparse_frontier", dataset="er-hot", n=nv, mode=mode,
                  events=len(log), ingest_s=round(times[mode], 4),
                  events_per_s=round(len(log) / max(times[mode], 1e-9), 1),
                  rounds=eng.n_rounds)
    sink.emit("sparse_frontier_summary", dataset="er-hot", n=nv,
              auto_vs_dense=round(times["dense"] / max(times["auto"], 1e-9),
                                  3),
              identical=True)


ALL = [table2_static_baseline, fig1_query_latency, fig2_latency_over_time,
       fig3_source_selection, fig4_stability, fig5_throughput,
       fig6_batch_bsp, backend_shootout, hub_shootout, bucket_shootout,
       dist_engine, serving, scale, sparse_frontier]
