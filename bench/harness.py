"""One run of one cell: set-up, the timed window, the check, the result.

Everything is found by name from ``BENCHMARK.json``:

    configs[].file                  the deployment (graph, engine settings)
    bench/traffic/<traffic>.json    the traffic mix (``traffic.py``)
    bench/cells/<workload>.json     the cell's nominal seconds per window
    bench/metrics/<metric>.py       one reader per metric, ``read(rec)``

so a later cell, mix or metric is new files plus new entries.

The timed path is the program's public one: ``repro.make_engine(...)`` with
the configuration's engine settings, then per window ``ingest_log`` of the
window's DEL and ADD batches and ``query()``.  A run of ``seconds`` times a
fixed count of whole windows, ``round(seconds / window_s)`` and at least
one, where ``window_s`` is the cell's nominal window length: every run of a
cell does the same work, and the window starts at the first event of the
first timed window and ends at the last answer.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import graphgen, reference, traffic

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = Path(".bench_cache") / "jax"
GRAPH_DIR = Path(".bench_cache") / "graphs"
TRACE_DIR = Path(".bench_cache") / "trace"
SAMPLED_ANSWERS = 3
_SAMPLE_STREAM = 7


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ spec --
def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str, root: Path = ROOT
            ) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic mix) of a cell name."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise ValueError(f"no cell {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic.check_mix(mix)


def timed_windows(workload: str, seconds: float, root: Path = ROOT) -> int:
    """Whole windows a run of ``seconds`` times: ``seconds`` over the
    cell's nominal ``window_s`` (``bench/cells/<workload>.json``), rounded,
    and at least one."""
    cell = json.loads(
        (root / "bench" / "cells" / f"{workload}.json").read_text())
    return max(1, round(seconds / cell["window_s"]))


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: end-to-end ones untraced, per-layer
    ones traced; an entry with ``workloads`` only in those cells."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------- device --
def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def enable_cache(root: Path) -> Path:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it, however fast it compiled."""
    import jax
    path = root / CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs obtained (compiled, or read from the persistent cache) and
    real compiles, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.programs = 0
        self.seconds = 0.0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


# ------------------------------------------------------------------- run --
class Reservoir:
    """A seeded uniform sample of ``k`` answers of a stream of unknown
    length (the same seed and count keep the same answers)."""

    def __init__(self, k: int, seed: int):
        self.k, self.seen, self.kept = k, 0, []
        self.rng = graphgen.rng_for(seed, _SAMPLE_STREAM)

    def offer(self, item) -> None:
        if len(self.kept) < self.k:
            self.kept.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.kept[j] = item
        self.seen += 1


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, compiles: CompileCounter, root: Path = ROOT,
             make_engine=None, require_tpu: bool = True) -> dict:
    """Set up, measure, check.  Returns the run's record: every reading
    the metric readers and the result line take."""
    spec = load_spec(root)
    cell, cfg, mix = resolve(spec, workload, root)
    devs = devices(cell["chips"], require_tpu)
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core import events as ev
    if make_engine is None:
        from repro import make_engine
    enable_cache(root)
    parts = {}
    programs0, seconds0, misses0 = (compiles.programs, compiles.seconds,
                                    compiles.misses)

    t = time.perf_counter()
    graph = graphgen.generate(cfg, seed, mix["fresh_edges"],
                              root / GRAPH_DIR)
    stream = traffic.Stream(graph, mix)
    pool = traffic.pool_arcs(cfg, mix)
    parts["generate_s"] = time.perf_counter() - t
    log(f"{cfg['generator']} scale {cfg['scale']}: n={graph.n} "
        f"edges={graph.e0} (+{len(graph.u) - graph.e0} fresh) "
        f"arcs={2 * graph.e0} pool={pool} source={graph.source}")

    t = time.perf_counter()
    engine = make_engine(num_vertices=graph.n, edge_capacity=pool,
                         source=graph.source, **cfg["engine"])
    engine.ingest_log(stream.load_log(ev))
    parts["load_host_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine.query()
    parts["load_device_s"] = time.perf_counter() - t
    peaks = {"load": peak_bytes(devs)}

    def window(k: int) -> tuple[float, float, float, int, object]:
        log_k = stream.updates_log(ev, k)
        t0 = time.perf_counter()
        with TraceAnnotation("bench.ingest_log"):
            engine.ingest_log(log_k)
        t1 = time.perf_counter()
        with TraceAnnotation("bench.query"):
            res = engine.query()
        return t0, t1, time.perf_counter(), len(log_k), res

    t = time.perf_counter()
    for k in range(mix["warmup_windows"]):
        window(k)
    parts["warmup_s"] = time.perf_counter() - t
    peaks["warmup"] = peak_bytes(devs)
    parts["compile_s"] = compiles.seconds - seconds0
    parts["programs"] = compiles.programs - programs0
    parts["compiles"] = compiles.misses - misses0

    rounds0 = int(np.sum(engine.n_rounds))
    programs0, misses0 = compiles.programs, compiles.misses
    trace_dir = root / TRACE_DIR / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=_profile_options())
    sample = Reservoir(SAMPLED_ANSWERS, seed)
    windows = []
    first = mix["warmup_windows"]
    t_start = time.perf_counter()
    for k in range(first, first + timed_windows(workload, seconds, root)):
        t0, t1, t2, events, res = window(k)
        windows.append({"start": t0, "dispatched": t1, "answered": t2,
                        "events": events})
        sample.offer((k, res.dist, res.parent))
    if trace:
        jax.profiler.stop_trace()
    peaks["window"] = peak_bytes(devs)
    rec = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "setup_s": t_start - t_process, "setup_parts": parts,
        "t_start": t_start, "t_end": windows[-1]["answered"],
        "windows": windows,
        "rounds": int(np.sum(engine.n_rounds)) - rounds0,
        "programs_in_window": compiles.programs - programs0,
        "compiles_in_window": compiles.misses - misses0,
        "peak_bytes": peaks["window"], "peak_by_phase": peaks,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
    }
    del engine, res
    gc.collect()
    if trace:
        from bench import xplane
        rec["trace"] = xplane.reduce_file(xplane.find_xplane(trace_dir))
    t = time.perf_counter()
    rec["checks"] = check(graph, stream, sample.kept)
    rec["check_s"] = time.perf_counter() - t
    rec["answers_checked"] = len(sample.kept)
    return rec


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # host spans only, no Python calls
    opts.enable_hlo_proto = False
    return opts


def check(graph, stream, answers) -> dict:
    """The worst count of each comparison over the sampled answers, and how
    many answers failed."""
    worst = dict.fromkeys(reference.LIMITS, 0)
    failed = 0
    for k, dist, parent in answers:
        ids = stream.live(k)
        g = reference.LiveGraph.of_edges(graph.n, graph.u[ids], graph.v[ids],
                                         graph.w[ids])
        got = reference.compare(g, graph.source, g.sssp(graph.source), dist,
                                parent)
        failed += any(got[n] > reference.LIMITS[n] for n in got)
        for n in got:
            worst[n] = max(worst[n], got[n])
    return {"worst": worst, "failed": failed}
