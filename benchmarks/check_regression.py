"""Perf/parity regression gates over BENCH_sssp.json (the CI artifact).

Replaces the inline heredoc that used to live in ci.yml: one gate function
per benchmark section, stdlib-only, exit 1 on any violation so the workflow
step fails.  Thresholds are deliberately loose (CI runners are noisy); the
sharp correctness gates — oracle cross-checks and sharded-vs-single
bit-identity — are asserted *inside* the benchmark run itself, and this
script additionally refuses to pass if those parity records are missing.

Run: ``python -m benchmarks.check_regression [--json BENCH_sssp.json]
[--sections backend_shootout,dist_engine,hub_shootout,serving]``

Gates (per delta value found in the section):
  * backend_shootout — ellpack ingest >= 0.95x segment; ellpack query p50
    <= 1.5x segment.
  * hub_shootout — sliced ingest >= 0.8x segment on the power-law stream
    (the floor is deliberately loose: the legs run minutes apart and
    shared-CPU drift swings the ratio ±20%; a real regression reads ~0.2x);
    sliced query p50 <= 1.5x segment; sliced device cells < ellpack's
    (the layout's reason to exist).
  * dist_engine — the summary row must report ``identical=True``
    (sharded == single bit-parity was asserted in-run); at P=1 the sharded
    ingest must hold >= 0.9x single-device (pure sharding overhead bound).
    The per-backend half gates sharded-sliced ingest >= 0.95x
    sharded-segment on the power-law hub stream and requires the three-way
    sharded parity record (``dist_engine_backends_summary``) to be present
    and true.
  * serving — batched S=4 multi-source replay throughput >= 2.0x the
    4-sequential-single-source-replay throughput (DESIGN.md §8: one shared
    layout, one fused epoch per batch instead of S), with the per-lane
    bit-parity record (``serving_summary.identical``) present and true and
    the latency/stability metric fields present on every batched row.
  * bucket_shootout — the lazy bucketed schedule must hold >= 2.0x the
    eager rounds schedule's events/s on the delta=0.5 ER stream for every
    backend (DESIGN.md §9: the round tax), with the final-state parity
    record present and true; the fused Pallas wave (§9.4) must beat the
    existing Pallas sliced wave (>= 1.0x) and stay within dispatch-overhead
    parity of the jnp three-dispatch path (>= 0.8x) on the power-law hub
    layout.
  * sparse_frontier — the frontier-compacted sparse path (DESIGN.md §12)
    must hold >= 3.0x the dense engine's throughput on the localized-update
    stream at the largest N present (the pay-for-the-affected-region win),
    and ``frontier_mode="auto"`` must hold >= 0.95x dense on the
    high-occupancy delta=0.5 ER stream (the routing-overhead bound); both
    summaries must carry the in-run bit-identity record.
  * scale — every paper-scale ingest row (DESIGN.md §11) must hold the
    chunked-ingest events/s floor (absolute, deliberately loose for CI
    hosts) AND stay under its own documented RSS budget
    (benchmarks/scale_worker.py: pool capacity + vertex + O(chunk) terms,
    never O(stream)); the smallest size must carry a passing oracle-parity
    record.
"""
from __future__ import annotations

import argparse
import json
import sys

DEFAULT_SECTIONS = ("backend_shootout", "dist_engine", "hub_shootout",
                    "bucket_shootout", "serving", "scale", "sparse_frontier")

# absolute floor for the scale section's chunked ingest (events/s): local
# runs measure 150k-350k across N=64k..1M; CI's shared 2-core runners are
# ~5-10x slower, a real O(batch)->O(stream) control-plane regression is
# >100x at the top size
SCALE_EVENTS_PER_S_FLOOR = 10_000.0


def _rows(records: list[dict], bench: str) -> list[dict]:
    return [r for r in records if r.get("bench") == bench]


def _by(rows: list[dict], *keys: str) -> dict[tuple, dict]:
    return {tuple(r[k] for k in keys): r for r in rows}


def _ratio_gate(errors: list[str], name: str, num: float, den: float,
                floor: float | None = None, ceil: float | None = None
                ) -> float:
    ratio = num / max(den, 1e-9)
    if floor is not None and ratio < floor:
        errors.append(f"{name}: {ratio:.3f}x < required {floor}x")
    if ceil is not None and ratio > ceil:
        errors.append(f"{name}: {ratio:.3f}x > allowed {ceil}x")
    return ratio


def gate_backend_shootout(records: list[dict]) -> list[str]:
    errors: list[str] = []
    rows = _rows(records, "backend_shootout")
    if not rows:
        return ["backend_shootout: no records found"]
    by = _by(rows, "delta", "backend")
    for d in sorted({r["delta"] for r in rows}):
        ing = _ratio_gate(errors, f"backend_shootout d={d} ell/seg ingest",
                          float(by[(d, "ellpack")]["events_per_s"]),
                          float(by[(d, "segment")]["events_per_s"]),
                          floor=0.95)
        q = _ratio_gate(errors, f"backend_shootout d={d} ell/seg query",
                        float(by[(d, "ellpack")]["query_p50_ms"]),
                        float(by[(d, "segment")]["query_p50_ms"]),
                        ceil=1.5)
        print(f"backend_shootout delta={d}: ell/seg ingest {ing:.2f}x, "
              f"query {q:.2f}x")
    return errors


def gate_hub_shootout(records: list[dict]) -> list[str]:
    errors: list[str] = []
    rows = _rows(records, "hub_shootout")
    if not rows:
        return ["hub_shootout: no records found"]
    by = _by(rows, "delta", "backend")
    for d in sorted({r["delta"] for r in rows}):
        # floor 0.8, not 0.95: the two legs run minutes apart and shared-CPU
        # drift between them swings the ratio ±20% run-to-run (interleaved
        # per-epoch microbenches show parity); a real sliced regression
        # shows up as ~0.2x (dense-ELL territory), far below this floor
        ing = _ratio_gate(errors, f"hub_shootout d={d} sliced/seg ingest",
                          float(by[(d, "sliced")]["events_per_s"]),
                          float(by[(d, "segment")]["events_per_s"]),
                          floor=0.8)
        q = _ratio_gate(errors, f"hub_shootout d={d} sliced/seg query",
                        float(by[(d, "sliced")]["query_p50_ms"]),
                        float(by[(d, "segment")]["query_p50_ms"]),
                        ceil=1.5)
        cells = _ratio_gate(errors, f"hub_shootout d={d} sliced/ell values",
                            float(by[(d, "sliced")]["device_values"]),
                            float(by[(d, "ellpack")]["device_values"]),
                            ceil=1.0)
        print(f"hub_shootout delta={d}: sliced/seg ingest {ing:.2f}x, "
              f"query {q:.2f}x, cells vs ellpack {cells:.3f}x")
    return errors


def gate_dist_engine(records: list[dict]) -> list[str]:
    errors: list[str] = []
    rows = _rows(records, "dist_engine")
    summaries = _rows(records, "dist_engine_summary")
    if not rows or not summaries:
        return ["dist_engine: no records found"]
    by = _by(rows, "delta", "engine")
    for s in summaries:
        d = s["delta"]
        if str(s.get("identical")) != "True":
            errors.append(f"dist_engine d={d}: sharded/single parity record "
                          f"missing or false: identical={s.get('identical')}")
        ratio = float(by[(d, "sharded")]["events_per_s"]) \
            / max(float(by[(d, "single")]["events_per_s"]), 1e-9)
        parts = int(s.get("parts", 0))
        if parts == 1 and ratio < 0.9:
            errors.append(f"dist_engine d={d}: sharded P=1 ingest {ratio:.3f}x "
                          f"single < required 0.9x")
        print(f"dist_engine delta={d} P={parts}: sharded/single ingest "
              f"{ratio:.2f}x, identical={s.get('identical')}")
    # per-backend sharded ingest on the power-law hub stream (DESIGN.md
    # §7.2): the three-way parity record must be present and true, and
    # sharded-sliced must hold the hub-stream ingest floor vs
    # sharded-segment
    bk_summaries = _rows(records, "dist_engine_backends_summary")
    if not bk_summaries:
        return errors + ["dist_engine: no sharded per-backend records found "
                         "(dist_engine_backends_summary)"]
    for s in bk_summaries:
        d = s["delta"]
        if str(s.get("identical")) != "True":
            errors.append(f"dist_engine backends d={d}: three-way sharded "
                          f"parity record missing or false: "
                          f"identical={s.get('identical')}")
        ing = _ratio_gate(errors,
                          f"dist_engine backends d={d} sliced/seg ingest",
                          float(by[(d, "sharded-sliced")]["events_per_s"]),
                          float(by[(d, "sharded-segment")]["events_per_s"]),
                          floor=0.95)
        print(f"dist_engine backends delta={d}: sharded sliced/segment "
              f"ingest {ing:.2f}x, identical={s.get('identical')}")
    return errors


def gate_serving(records: list[dict]) -> list[str]:
    errors: list[str] = []
    rows = _rows(records, "serving")
    summaries = _rows(records, "serving_summary")
    if not rows or not summaries:
        return ["serving: no records found"]
    # every batched row must carry the three serving metrics (DESIGN.md §8)
    metric_keys = ("events_per_s", "latency_p50_ms", "latency_p95_ms",
                   "latency_p99_ms", "churn_mean", "stability_parent")
    for r in rows:
        if str(r.get("engine", "")).startswith("sequential"):
            continue
        missing = [k for k in metric_keys if k not in r]
        if missing:
            errors.append(f"serving s={r.get('s')}: metric field(s) "
                          f"missing from record: {missing}")
    for s in summaries:
        if str(s.get("identical")) != "True":
            errors.append(f"serving: batched-lane parity record missing or "
                          f"false: identical={s.get('identical')}")
    # the throughput gate is per-artifact, not per-summary
    by = _by(rows, "engine", "s")
    ratio = _ratio_gate(
        errors, "serving batched-S=4 / 4-sequential ingest",
        float(by[("single/segment", 4)]["events_per_s"]),
        float(by[("sequential/segment", 4)]["events_per_s"]),
        floor=2.0)
    print(f"serving: batched S=4 vs 4x sequential ingest {ratio:.2f}x, "
          f"identical={[str(s.get('identical')) for s in summaries]}")
    return errors


def gate_bucket_shootout(records: list[dict]) -> list[str]:
    errors: list[str] = []
    rows = _rows(records, "bucket_shootout")
    summaries = _rows(records, "bucket_shootout_summary")
    if not rows or not summaries:
        return ["bucket_shootout: no records found"]
    by = _by(rows, "dataset", "backend", "schedule")
    for s in summaries:
        if str(s.get("identical")) != "True":
            errors.append(f"bucket_shootout {s.get('dataset')}/"
                          f"{s.get('backend')}: final-state parity record "
                          f"missing or false: identical={s.get('identical')}")
    # the round-tax gate runs on the ER stream only (the ISSUE's
    # round-bound regime); the hub-stream ratios are informational
    for backend in sorted({r["backend"] for r in rows}):
        ratio = _ratio_gate(
            errors, f"bucket_shootout er {backend} buckets/rounds ingest",
            float(by[("er", backend, "buckets")]["events_per_s"]),
            float(by[("er", backend, "rounds")]["events_per_s"]),
            floor=2.0)
        print(f"bucket_shootout er {backend}: buckets/rounds ingest "
              f"{ratio:.2f}x")
    fused = _rows(records, "bucket_shootout_fused_summary")
    if not fused:
        return errors + ["bucket_shootout: no fused-wave records found "
                         "(bucket_shootout_fused_summary)"]
    for s in fused:
        if str(s.get("identical")) != "True":
            errors.append("bucket_shootout fused: wave parity record "
                          f"missing or false: identical={s.get('identical')}")
        vp = float(s.get("fused_vs_pallas", 0.0))
        vj = float(s.get("fused_vs_jnp", 0.0))
        if vp < 1.0:
            errors.append(f"bucket_shootout fused: {vp:.3f}x < required "
                          f"1.0x vs the existing Pallas sliced wave")
        # loose floor: the jnp path pays no pallas_call overhead, and in
        # interpret mode the fused kernel carries ~35-50us of fixed per-call
        # emulation cost plus the same ±20% shared-CPU drift as the hub
        # gate — the binding requirement is the >= 1.0x vs-Pallas gate above
        if vj < 0.8:
            errors.append(f"bucket_shootout fused: {vj:.3f}x < required "
                          f"0.8x vs the jnp three-dispatch path")
        print(f"bucket_shootout fused: vs pallas {vp:.2f}x, "
              f"vs jnp {vj:.2f}x")
    return errors


def gate_scale(records: list[dict]) -> list[str]:
    errors: list[str] = []
    rows = _rows(records, "scale")
    if not rows:
        return ["scale: no records found"]
    smallest = min(rows, key=lambda r: int(r["n"]))
    if str(smallest.get("oracle_match")) != "True":
        errors.append(f"scale n={smallest['n']}: oracle parity record "
                      f"missing or false: "
                      f"oracle_match={smallest.get('oracle_match')}")
    for r in sorted(rows, key=lambda r: int(r["n"])):
        n, eps = int(r["n"]), float(r["events_per_s"])
        peak = float(r["peak_rss_mb"])
        budget = float(r["rss_budget_mb"])
        if eps < SCALE_EVENTS_PER_S_FLOOR:
            errors.append(f"scale n={n}: ingest {eps:.0f} events/s < "
                          f"required {SCALE_EVENTS_PER_S_FLOOR:.0f}")
        if peak > budget:
            errors.append(f"scale n={n}: peak RSS {peak:.0f}MB > budget "
                          f"{budget:.0f}MB (O(stream) host state?)")
        print(f"scale n={n}: {eps:.0f} events/s, peak RSS {peak:.0f}MB / "
              f"budget {budget:.0f}MB, waves={r.get('waves')}")
    return errors


def gate_sparse_frontier(records: list[dict]) -> list[str]:
    errors: list[str] = []
    summaries = _rows(records, "sparse_frontier_summary")
    if not summaries:
        return ["sparse_frontier: no records found"]
    for s in summaries:
        if str(s.get("identical")) != "True":
            errors.append(f"sparse_frontier {s.get('dataset')}: bit-identity "
                          f"record missing or false: "
                          f"identical={s.get('identical')}")
    loc = [s for s in summaries if s.get("dataset") == "localized"]
    if not loc:
        errors.append("sparse_frontier: no localized-stream summary found")
    else:
        # the acceptance point is the largest N the run produced (small mode
        # runs 256k; the full run adds N=1M)
        top = max(loc, key=lambda r: int(r["n"]))
        ratio = float(top.get("sparse_vs_dense", 0.0))
        if ratio < 3.0:
            errors.append(f"sparse_frontier localized n={top['n']}: sparse "
                          f"{ratio:.2f}x dense < required 3.0x")
        print(f"sparse_frontier localized n={top['n']}: sparse/dense "
              f"{ratio:.2f}x, identical={top.get('identical')}")
    hot = [s for s in summaries if s.get("dataset") == "er-hot"]
    if not hot:
        errors.append("sparse_frontier: no high-occupancy auto summary found")
    else:
        ratio = float(hot[0].get("auto_vs_dense", 0.0))
        if ratio < 0.95:
            errors.append(f"sparse_frontier er-hot: auto {ratio:.3f}x dense "
                          f"< required 0.95x (routing overhead)")
        print(f"sparse_frontier er-hot: auto/dense {ratio:.2f}x, "
              f"identical={hot[0].get('identical')}")
    return errors


GATES = {
    "backend_shootout": gate_backend_shootout,
    "sparse_frontier": gate_sparse_frontier,
    "scale": gate_scale,
    "bucket_shootout": gate_bucket_shootout,
    "dist_engine": gate_dist_engine,
    "hub_shootout": gate_hub_shootout,
    "serving": gate_serving,
}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--json", default="BENCH_sssp.json")
    p.add_argument("--sections", default=",".join(DEFAULT_SECTIONS),
                   help="comma-separated gate names (default: all)")
    args = p.parse_args()
    sections = [s for s in args.sections.split(",") if s]
    unknown = [s for s in sections if s not in GATES]
    if unknown:
        print(f"error: unknown gate section(s): {','.join(unknown)} "
              f"(known: {','.join(GATES)})", file=sys.stderr)
        return 2
    with open(args.json) as f:
        records = json.load(f)["records"]
    errors: list[str] = []
    for s in sections:
        errors += GATES[s](records)
    for e in errors:
        print(f"REGRESSION: {e}", file=sys.stderr)
    if not errors:
        print(f"all gates passed: {','.join(sections)}")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
