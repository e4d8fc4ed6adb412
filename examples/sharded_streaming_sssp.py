"""Streaming SSSP over a sliding-window event stream, sharded across the
local device mesh (DESIGN.md §5, §7.2).

Run: PYTHONPATH=src python examples/sharded_streaming_sssp.py [--delta 0.3]

Multi-partition on one host (8 forced host devices):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/sharded_streaming_sssp.py

Pick a relaxation backend (one RelaxBackend protocol serves both engines —
the sharded engine runs one shard-local layout per partition and plugs its
wave into the shard_map epochs):

    # portable COO scatter-min (default)
    ... sharded_streaming_sssp.py --backend segment
    # incrementally maintained dense ELL block (DESIGN.md §2)
    ... sharded_streaming_sssp.py --backend ellpack
    # hub-aware sliced-ELL + overflow hybrid for power-law in-degree
    # graphs (DESIGN.md §6) — pair with --hubs for its target workload
    ... sharded_streaming_sssp.py --backend sliced --hubs

Replays an RMAT stream with windowed deletions through the sharded engine
(vertex partition = all local devices flattened), reports the paper's
metrics plus the per-partition edge-pool fill, and cross-checks the final
tree bit-for-bit against the single-device engine *running the same
backend*.  ``--balanced`` relabels vertices so shards own ~equal in-edge
mass (power-law hubs otherwise load a single shard).

Serving-layer trace flags (DESIGN.md §8): ``--record-trace PATH`` saves
the generated workload; ``--replay-trace PATH`` replays a recorded trace
through the sharded engine + metrics harness (missing/incompatible paths
exit with code 2).  ``--dataset PATH`` streams a real SNAP/Konect edge
list through the same pipeline (graphs/datasets.py; bad paths exit 2).
Engines are built through ``repro.make_engine`` (DESIGN.md §11.5).

Observability flags (DESIGN.md §10): ``--trace-out PATH`` writes the
engine's span trace as Chrome trace-event JSON (loads in Perfetto),
``--log-json PATH`` writes spans + the final ``metrics_snapshot`` as
JSONL; either enables the engine's counter registry / flight recorder,
and a nonexistent parent directory exits with code 2.  ``--buckets``
switches both engines to the bucketed delta-stepping wave schedule.
"""
import argparse
import time

import numpy as np

import jax

import repro
from repro.compile_cache import enable_compile_cache
from repro.core import events as ev
from repro.core.engine import RELAX_BACKENDS
from repro.graphs import generators as gen
from repro.graphs import partition as part_mod
from repro.graphs import window as win
from repro.obs import out_path_or_exit
from repro.serving import TraceRecorder, load_trace_or_exit, replay_trace

from streaming_sssp import add_obs_flags, dump_obs, obs_paths


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--scale", type=int, default=10)
    p.add_argument("--delta", type=float, default=0.3)
    p.add_argument("--window-frac", type=float, default=0.3)
    p.add_argument("--exchange", choices=("allgather", "delta"),
                   default="allgather")
    p.add_argument("--backend", choices=RELAX_BACKENDS, default="segment",
                   help="relaxation backend for BOTH engines "
                        "(core/backends/, DESIGN.md §7)")
    p.add_argument("--hubs", action="store_true",
                   help="in-degree power-law hub graph instead of RMAT "
                        "(the sliced backend's target workload)")
    p.add_argument("--balanced", action="store_true",
                   help="edge-balanced vertex relabeling "
                        "(graphs/partition.edge_balanced_relabeling)")
    p.add_argument("--dataset", metavar="PATH",
                   help="replay a real SNAP/Konect edge list (graphs/"
                        "datasets.py; bad paths exit 2)")
    p.add_argument("--record-trace", metavar="PATH",
                   help="save the generated workload as a serving trace "
                        "(repro/serving/trace.py, DESIGN.md §8.2)")
    p.add_argument("--replay-trace", metavar="PATH",
                   help="replay a recorded trace through the sharded "
                        "engine and report the serving metrics "
                        "(unknown paths exit 2)")
    p.add_argument("--buckets", action="store_true",
                   help="bucketed delta-stepping wave schedule "
                        "(core/buckets.py, DESIGN.md §9) on both engines")
    add_obs_flags(p)
    args = p.parse_args()
    enable_compile_cache()
    # fail fast on unwritable observability destinations (exit 2)
    for path in obs_paths(args):
        if path:
            out_path_or_exit(path)
    obs_on = any(obs_paths(args))
    schedule = "buckets" if args.buckets else "rounds"

    if args.dataset:
        n, trace = repro.load_dataset_or_exit(
            args.dataset, window_frac=args.window_frac, delta=args.delta)
        log = ev.interleave_queries(trace.to_log(),
                                    max(1, trace.n_topology // 10))
        trace = repro.ServingTrace.from_log(log)

    if args.replay_trace or args.dataset:
        if args.replay_trace:
            trace = load_trace_or_exit(args.replay_trace)
            topo = trace.kind != ev.QUERY
            n = int(max(trace.src[topo].max(initial=0),
                        trace.dst[topo].max(initial=0))) + 1
        parts = len(jax.devices())
        epp = int(trace.n_topology * 1.3) // max(parts // 2, 1) + 64
        source = int(gen.top_in_degree_sources(
            n, trace.dst[trace.kind == ev.ADD].astype(np.int64))[0])
        eng = repro.make_engine(
            num_vertices=n, edge_capacity=epp * parts, source=source,
            partitions=parts, exchange=args.exchange,
            relax_backend=args.backend, wave_schedule=schedule,
            observability=obs_on)
        report = replay_trace(eng, trace)
        print(f"trace: {args.replay_trace or args.dataset} "
              f"source={source} partitions={parts} schedule={schedule}")
        print(report.summary())
        dump_obs(eng, args)
        return

    if args.hubs:
        n, src, dst, w = gen.power_law_hubs(1 << args.scale,
                                            8 << args.scale, n_hubs=4,
                                            seed=7, orientation="in")
    else:
        n, src, dst, w = gen.rmat(args.scale, edge_factor=8, seed=7)
    source = int(gen.top_in_degree_sources(n, dst)[0])
    window = int(len(src) * args.window_frac)
    log = win.sliding_window_stream(src, dst, w, window=window,
                                    delta=args.delta, seed=0)
    log = ev.interleave_queries(log, window // 10)
    parts = len(jax.devices())
    print(f"graph: n={n} stream={len(log)} events (delta={args.delta}) "
          f"source={source} partitions={parts} backend={args.backend}")

    if args.record_trace:
        rec = TraceRecorder()
        rec.extend_from_log(log)
        rec.trace().save(args.record_trace)
        print(f"recorded trace: {args.record_trace} ({len(log)} events)")

    relabel = None
    if args.balanced:
        relabel = part_mod.edge_balanced_relabeling(n, dst, parts)

    epp = int(len(src) * 1.3) // max(parts // 2, 1) + 64
    eng = repro.make_engine(
        num_vertices=n, edge_capacity=epp * parts, source=source,
        partitions=parts, exchange=args.exchange,
        relax_backend=args.backend, wave_schedule=schedule,
        observability=obs_on, relabel=relabel)
    lat, stab = [], []
    t0 = time.perf_counter()

    def on_query(r):
        lat.append(r.latency_s)
        stab.append(eng.stability_vs_prev(r.parent, source=r.source))

    eng.ingest_log(log, on_query=on_query)
    wall = time.perf_counter() - t0

    fill = eng.partition_fill()
    print(f"queries: {len(lat)}  latency p50 {np.median(lat)*1e3:.3f}ms")
    print(f"stability (predecessor overlap): p50 {np.median(stab):.4f}")
    print(f"ingestion: {len(log)/wall:.0f} events/s "
          f"({eng.n_epochs} epochs, {eng.n_rounds} message waves)")
    print(f"partition fill (live edges/shard): min={fill.min()} "
          f"max={fill.max()} imbalance={fill.max()/max(fill.mean(), 1):.2f}x")

    dump_obs(eng, args)

    # cross-check: the sharded run must equal the single-device engine
    # running the same relaxation backend
    ref = repro.make_engine(num_vertices=n,
                            edge_capacity=int(len(src) * 1.3) + 64,
                            source=source, relax_backend=args.backend,
                            wave_schedule=schedule)
    ref.ingest_log(log)
    q_ref, q = ref.query(), eng.query()
    np.testing.assert_array_equal(q_ref.dist, q.dist)
    if relabel is None:
        np.testing.assert_array_equal(q_ref.parent, q.parent)
    print("single-device equivalence: OK (bit-identical dist"
          f"{', parent' if relabel is None else ''})")


if __name__ == "__main__":
    main()
