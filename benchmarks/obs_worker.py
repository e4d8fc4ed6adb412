"""Sharded obs-overhead worker: the P=8 leg of the ``obs_overhead``
section, measured in a FRESH process.

On the CPU, benchmarks/bench_sssp.py runs it via ``python -m
benchmarks.obs_worker``: a subprocess because
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (which only the CPU
platform reads) must be set BEFORE jax initializes, and the parent bench
process has long since imported jax.  On a TPU the bench calls ``run`` in
its own process over the real devices: a child cannot reach the chip its
parent holds.

Same contract as the single-device leg (DESIGN.md §10.4), on the sharded
engine over an 8-device mesh: the identical power-law stream ingested
with telemetry off and on in interleaved passes (1 warm + best-of-2), a
default-threshold watchdog armed on the instrumented passes (it must stay
silent — §10.8), and in-run asserts pinning bit-identical (dist, parent,
rounds, messages), span==counter agreement, histogram-total==counter
consistency (§10.6) and per-partition attribution sums (§10.5).

Emits one ``OBSROW {json}`` line per bench record on stdout; the parent
re-emits them through its CsvSink so check_regression gates the sharded
on/off ratio exactly like the single-device one.  ``--trace-out PATH``
additionally saves the instrumented engine's Perfetto Chrome trace (the
CI build artifact).
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import json
import time

import numpy as np


def run(small: bool, trace_out: str | None = None) -> list[dict]:
    """The sharded leg over every visible device; returns the bench
    records (``{"bench": name, **fields}``)."""
    import jax

    from repro.core.dist_engine import ShardedEngineConfig, \
        ShardedSSSPDelEngine
    from repro.graphs import generators as gen
    from repro.graphs import window as win
    from repro.core import events as ev
    from repro.obs import WatchdogConfig

    P = len(jax.devices())
    n = (1 << 9) if small else (1 << 10)
    m = 4 * n
    nv, src, dst, w = gen.power_law_hubs(n, m, n_hubs=4, seed=31,
                                         orientation="in")
    source = int(gen.top_in_degree_sources(nv, dst)[0])
    log = ev.interleave_queries(
        win.sliding_window_stream(src, dst, w, window=len(src) // 3,
                                  delta=0.3, seed=0),
        max(1, len(src) // 12))

    def mk(obs_on):
        return ShardedSSSPDelEngine(ShardedEngineConfig(
            num_vertices=nv, edges_per_part=m, source=source,
            relax_backend="sliced", sliced_slice_rows=32, sliced_hub_k=4,
            sliced_init_k=2, observability=obs_on,
            # default thresholds: only multi-second stalls fire — the
            # gated bench asserts the watchdog stays silent (§10.8)
            obs_watchdog=WatchdogConfig() if obs_on else None))

    best = {False: 0.0, True: 0.0}
    final = {}
    for _ in range(3):                      # 1 warm + best-of-2 timed
        for obs_on in (False, True):        # interleaved passes
            eng = mk(obs_on)
            t0 = time.perf_counter()
            eng.ingest_log(log)
            jax.block_until_ready(eng.dist)
            eps = len(log) / (time.perf_counter() - t0)
            if eps > best[obs_on]:
                best[obs_on], final[obs_on] = eps, eng

    # §10 invariants: telemetry free of algorithmic effect, three views
    # of the same events in agreement, histogram totals == flat counters
    q_off, q_on = final[False].query(), final[True].query()
    np.testing.assert_array_equal(q_off.dist, q_on.dist)
    np.testing.assert_array_equal(q_off.parent, q_on.parent)
    on = final[True]
    snap = on.metrics_snapshot()
    assert int(snap["rounds"]) == int(on.n_rounds)
    assert int(final[False].n_rounds) == int(on.n_rounds)
    sp, ct = snap["spans"], snap["counters"]
    for kind, name in (("add_epoch", "add_epochs"),
                       ("del_epoch", "del_epochs"), ("query", "queries")):
        assert sp.get(kind, 0) == ct.get(name, 0), (kind, sp, ct)
    h = snap["histograms"]
    assert h["latency_us"]["count"] == ct["queries"]
    assert h["frontier_occupancy"]["count"] == ct["add_epochs"]
    assert h["waves_per_epoch"]["count"] == ct["add_epochs"] + ct["del_epochs"]
    att = snap["attribution"]["partition"]
    assert int(np.sum(att["adds_per_part"])) == on.n_adds
    assert int(np.sum(att["frontier_per_part"])) == ct["frontier"]
    assert int(np.sum(att["updates_per_part"])) >= 0
    # silent watchdog on the gated bench (§10.8)
    assert "watchdog_warnings" not in ct, ct.get("watchdog_warnings")

    from benchmarks import common as C
    records = []
    for obs_on in (False, True):
        eng = final[obs_on]
        s = eng.metrics_snapshot()
        records.append(dict(
            bench="obs_overhead", dataset="plaw", n=nv, edges=m,
            backend="sliced", engine="sharded", parts=P,
            observability=obs_on, events=len(log),
            events_per_s=round(best[obs_on], 1), epochs=eng.n_epochs,
            rounds=int(s["rounds"]), messages=int(s["messages"]),
            spans=sum(s["spans"].values()),
            **(C.hist_fields(s) if obs_on else {})))
    records.append(dict(
        bench="obs_overhead_summary", backend="sliced", engine="sharded",
        parts=P, on_vs_off=round(best[True] / max(best[False], 1e-9), 3),
        identical=True))

    if trace_out:
        on.obs.tracer.save_chrome(trace_out)
        print(f"chrome trace -> {trace_out}", file=sys.stderr)
    return records


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--trace-out", default=None)
    args = ap.parse_args()
    for rec in run(args.small, args.trace_out):
        print("OBSROW " + json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    # eight host devices for the P=8 leg; the flag is read by the CPU
    # platform only and must precede jax's initialization
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    raise SystemExit(main())
