"""Scale-bench worker: one (N, E) ingest measured in a FRESH process.

Run by the ``scale`` section of benchmarks/bench_sssp.py via
``python -m benchmarks.scale_worker --n ... --e ...``; a fresh process
per size makes ``resource.getrusage(RUSAGE_SELF).ru_maxrss`` an honest
peak for exactly this workload (no residue from earlier sections).

The workload is the paper-scale ingest path end to end (DESIGN.md §11):
a synthetic E-event ADD stream is SYNTHESIZED chunk-by-chunk (a seeded
rng per chunk — no full-stream materialization anywhere in the process)
and fed through ``StreamEngineBase.ingest_log``'s chunked-iterable path
into an engine on the bucketed wave schedule, which defers convergence
work so ingest cost stays per-batch; one drain at the final query
settles the tree.  Random (u, v) pairs collide on ~E²/2 / (N² ) slots
(≈ 50 rows at every bench size) — duplicates are dropped by the
allocator, exercising its collision path without meaningfully changing
E.

Peak RSS is read BEFORE the optional oracle check (the pure-Python
Dijkstra would dominate the high-water mark) and compared against the
documented budget:

    budget_mb = BASE_MB + EDGE_BYTES * capacity / 1e6
                        + VERTEX_BYTES * n / 1e6 + CHUNK_MB

  BASE_MB     interpreter + numpy + jax/XLA CPU runtime floor
  EDGE_BYTES  per pool slot: host mirror (13 B) + columnar index
              (12 B/cell at ≤ 2x pow2 slack, + the doubling-rebuild
              transient) + free stack (4 B) + the device pool and its
              functional-update double buffer (2 x 13 B)
  VERTEX_BYTES dist/parent/pending + bucket bookkeeping, a few copies
  CHUNK_MB    transient per-chunk arrays + pow2-padded jit batches

The point of the bound: it scales with POOL CAPACITY and CHUNK size
only — a control plane or replay path that held O(stream) Python
objects (the pre-§11 dict planner at E ≥ 10M) blows straight past it.

On a TPU the bench calls ``measure`` in its own process instead (a child
cannot reach the chip its parent holds), so peak RSS there is the whole
bench process's high-water mark.

Emits one JSON line on stdout; benchmarks/bench_sssp.py turns it into a
``scale`` record gated by check_regression (events/s floor, RSS
ceiling, oracle parity at the smallest size).
"""
from __future__ import annotations

import argparse
import json
import resource
import sys
import time

import numpy as np

BASE_MB = 900.0
EDGE_BYTES = 120.0
VERTEX_BYTES = 80.0
CHUNK_MB = 96.0


def rss_budget_mb(n: int, capacity: int) -> float:
    return (BASE_MB + EDGE_BYTES * capacity / 1e6
            + VERTEX_BYTES * n / 1e6 + CHUNK_MB)


def peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def measure(n: int, e: int, *, chunk: int = 1 << 16, seed: int = 0,
            alloc_impl: str = "columnar", check_oracle: bool = False
            ) -> dict:
    """Ingest one synthetic (n, e) stream; returns the ``scale`` record."""
    import repro
    from repro.core import events as ev

    cap = e + 64
    eng = repro.make_engine(
        num_vertices=n, edge_capacity=cap, source=0,
        wave_schedule="buckets", bucket_width=float("inf"),
        alloc_impl=alloc_impl)

    def synth_chunks():
        done, i = 0, 0
        while done < e:
            m = min(chunk, e - done)
            rng = np.random.default_rng((seed << 20) + i)
            src = rng.integers(0, n, m, dtype=np.int64)
            dst = rng.integers(0, n, m, dtype=np.int64)
            w = rng.uniform(0.1, 1.0, m).astype(np.float32)
            yield ev.adds(src, dst, w)
            done += m
            i += 1

    t0 = time.perf_counter()
    eng.ingest_log(synth_chunks())
    ingest_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    res = eng.query()          # one drain settles the deferred waves
    query_s = time.perf_counter() - t1
    peak_mb = peak_rss_mb()    # read BEFORE any oracle bookkeeping
    budget_mb = rss_budget_mb(n, cap)

    oracle_match = None
    if check_oracle:
        from repro.core import oracle
        lsrc, ldst, lw = eng.alloc.active_coo()
        dist_ref, _ = oracle.dijkstra(n, lsrc, ldst, lw, 0)
        dist = np.asarray(res.dist)
        oracle_match = bool(np.allclose(
            np.where(np.isfinite(dist), dist, -1),
            np.where(np.isfinite(dist_ref), dist_ref, -1),
            rtol=1e-5, atol=1e-5))

    rec = {
        "n": n, "e": e, "chunk": chunk, "alloc_impl": alloc_impl,
        "live_edges": int(eng.alloc.mactive.sum()),
        "events_per_s": round(e / max(ingest_s, 1e-9), 1),
        "ingest_s": round(ingest_s, 3),
        "query_s": round(query_s, 3),
        "waves": int(eng.n_rounds),
        "epochs": int(eng.n_epochs),
        "peak_rss_mb": round(peak_mb, 1),
        "rss_budget_mb": round(budget_mb, 1),
        "rss_ok": bool(peak_mb <= budget_mb),
    }
    if oracle_match is not None:
        rec["oracle_match"] = oracle_match
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, required=True)
    ap.add_argument("--e", type=int, required=True)
    ap.add_argument("--chunk", type=int, default=1 << 16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--alloc-impl", default="columnar")
    ap.add_argument("--check-oracle", action="store_true")
    args = ap.parse_args()
    print(json.dumps(measure(args.n, args.e, chunk=args.chunk,
                             seed=args.seed, alloc_impl=args.alloc_impl,
                             check_oracle=args.check_oracle)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
