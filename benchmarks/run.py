"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Sections:
  * SSSP-Del paper tables/figures (benchmarks/bench_sssp.py) with Dijkstra
    oracle cross-checks — one function per paper table/figure — plus the
    beyond-paper sections: backend_shootout, hub_shootout, dist_engine,
    ``serving`` (batched multi-source trace replay with the
    latency/stability/throughput record, DESIGN.md §8) and
    ``obs_overhead`` (the §10.4 observability overhead contract:
    instrumented vs uninstrumented ingest on the same stream);
  * kernel micro-benchmarks (Pallas interpret-mode vs jnp reference);
  * roofline table distilled from the dry-run reports (if reports/ exists).

``--small`` shrinks graphs for CI-speed runs; ``--only <prefix>`` filters
(unknown names are an error — exit 2); ``--list`` prints the sections.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import common as C


def section_names() -> list[str]:
    from benchmarks import bench_sssp
    return [fn.__name__ for fn in bench_sssp.ALL]


def _token_matches(tok: str, name: str) -> bool:
    """THE --only matching rule (substring), shared by the pre-run
    validation and the section filter so the two can never drift."""
    return bool(tok) and tok in name


def check_only(only: str | None) -> list[str]:
    """Validate --only tokens against the section list; returns the unknown
    tokens (each token must match at least one section)."""
    names = section_names()
    return [tok for tok in (only.split(",") if only else [])
            if not any(_token_matches(tok, name) for name in names)]


def run_sssp(sink: C.CsvSink, small: bool, only: str | None) -> None:
    from benchmarks import bench_sssp
    wanted = only.split(",") if only else None
    for fn in bench_sssp.ALL:
        if wanted and not any(_token_matches(tok, fn.__name__)
                              for tok in wanted):
            continue
        t0 = time.perf_counter()
        fn(sink, small)
        sink.emit("section_done", name=fn.__name__,
                  wall_s=f"{time.perf_counter() - t0:.1f}")


def run_kernels(sink: C.CsvSink, small: bool) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.kernels.relax import ops as relax_ops
    from repro.kernels.spmm import ops as spmm_ops
    from repro.kernels.embed_bag import ops as eb_ops
    rng = np.random.default_rng(0)

    n, k = (256, 16) if small else (1024, 32)
    nbr = jnp.asarray(rng.integers(0, n, (n, k)), jnp.int32)
    w = jnp.asarray(rng.random((n, k)).astype(np.float32))
    dist = jnp.asarray(rng.random(n).astype(np.float32))
    parent = jnp.full((n,), -1, jnp.int32)
    for name, use_kernel in (("pallas_interp", True), ("jnp_ref", False)):
        t0 = time.perf_counter()
        out = relax_ops.relax_wave(dist, parent, nbr, w,
                                   use_kernel=use_kernel)
        jax.block_until_ready(out)
        sink.emit("kernel_relax", impl=name, n=n, k=k,
                  ms=f"{(time.perf_counter()-t0)*1e3:.1f}")

    feats = jnp.asarray(rng.random((n, 64)).astype(np.float32))
    msk = jnp.asarray(rng.random((n, k)) < 0.8)
    for name, use_kernel in (("pallas_interp", True), ("jnp_ref", False)):
        t0 = time.perf_counter()
        jax.block_until_ready(spmm_ops.neighbor_reduce(
            feats, nbr, msk, agg="sum", use_kernel=use_kernel))
        sink.emit("kernel_spmm", impl=name, n=n, k=k,
                  ms=f"{(time.perf_counter()-t0)*1e3:.1f}")

    table = jnp.asarray(rng.random((4096, 32)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 4096, (n, 8)), jnp.int32)
    for name, use_kernel in (("pallas_interp", True), ("jnp_ref", False)):
        t0 = time.perf_counter()
        jax.block_until_ready(eb_ops.bag_lookup(table, idx, agg="sum",
                                                use_kernel=use_kernel))
        sink.emit("kernel_embed_bag", impl=name, bags=n,
                  ms=f"{(time.perf_counter()-t0)*1e3:.1f}")


def run_roofline_table(sink: C.CsvSink) -> None:
    shown = 0
    for base, variant in (("reports/dryrun", "baseline"),
                          ("reports/perf/flash_vjp", "flash_vjp"),
                          ("reports/perf/opt", "opt")):
        if not os.path.isdir(base):
            continue
        for mesh in sorted(os.listdir(base)):
            d = os.path.join(base, mesh)
            if not os.path.isdir(d):
                continue
            for f in sorted(os.listdir(d)):
                if not f.endswith(".json"):
                    continue
                rec = json.load(open(os.path.join(d, f)))
                if not rec.get("ok"):
                    continue
                r = rec["roofline"]
                sink.emit("roofline", variant=variant, mesh=mesh,
                          cell=f[:-5], dominant=r["dominant"],
                          compute_s=f"{r['compute_s']:.3e}",
                          memory_s=f"{r['memory_s']:.3e}",
                          collective_s=f"{r['collective_s']:.3e}",
                          peak_gb=f"{rec['memory']['peak_per_device_gb']:.2f}")
                shown += 1
    if not shown:
        sink.emit("roofline", note="no reports found; run "
                  "PYTHONPATH=src python -m repro.launch.dryrun --all first")


def write_bench_json(sink: C.CsvSink, args, wall_s: float,
                     path: str = "BENCH_sssp.json") -> None:
    """Machine-readable artifact so the perf trajectory is tracked across
    PRs (CI runs ``--small`` and archives this file)."""
    import platform

    import jax

    payload = {
        "schema": 1,
        "suite": "sssp_del",
        "small": bool(args.small),
        "only": args.only,
        "wall_s": round(wall_s, 2),
        "env": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "python": platform.python_version(),
        },
        "records": sink.records,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    print(f"wrote {path} ({len(sink.records)} records)", flush=True)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--small", action="store_true")
    p.add_argument("--only", help="comma-separated name substrings, e.g. "
                                  "'backend_shootout,dist_engine'")
    p.add_argument("--skip-kernels", action="store_true")
    p.add_argument("--json", default="BENCH_sssp.json",
                   help="machine-readable output path ('' disables)")
    p.add_argument("--list", action="store_true",
                   help="print available section names and exit")
    args = p.parse_args()
    if args.list:
        for name in section_names():
            print(name)
        return 0
    unknown = check_only(args.only)
    if unknown:
        print(f"error: unknown --only section(s): {','.join(unknown)}; "
              f"--list prints the available names", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sink = C.CsvSink()
    t0 = time.perf_counter()
    run_sssp(sink, args.small, args.only)
    if not args.skip_kernels and not args.only:
        run_kernels(sink, args.small)
    if not args.only:
        run_roofline_table(sink)
    wall = time.perf_counter() - t0
    sink.emit("all_done", wall_s=f"{wall:.1f}", rows=len(sink.rows))
    if args.json:
        write_bench_json(sink, args, wall, args.json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
