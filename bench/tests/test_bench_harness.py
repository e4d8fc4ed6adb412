"""The harness end to end on the CPU at a small size: it refuses to run
without a TPU, takes a new cell as new files plus entries, and ``correct``
comes out false under the control and under each fault the cells can have.
"""
import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from bench import control, harness, reference
from bench import run as bench_run

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def jax_cache_config():
    """The harness turns JAX's persistent cache on at its root; put the
    settings back so that no other test reads or writes that cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def small_root(tmp_path: Path, scale: int = 10, edges: int = 32) -> Path:
    """A copy of the benchmark with every configuration at ``scale`` and
    every window of ``edges`` edges each way."""
    root = tmp_path / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for f in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        f.write_text(json.dumps(dict(cfg, scale=scale)))
    for f in (root / "bench" / "traffic").glob("*.json"):
        mix = json.loads(f.read_text())
        f.write_text(json.dumps(dict(mix, del_edges=edges, add_edges=edges,
                                     fresh_edges=512)))
    return root


def run(root: Path, workload: str, seed: int = 2**31 + 11,
        make_engine=None, seconds: float = 0.3) -> dict:
    rec = harness.run_cell(workload, seed, seconds, False,
                           time.perf_counter(), harness.CompileCounter(),
                           root=root, make_engine=make_engine,
                           require_tpu=False)
    return bench_run.result(rec, harness.load_spec(root), False, root)


def test_a_run_times_a_fixed_count_of_whole_windows(tmp_path):
    (tmp_path / "bench" / "cells").mkdir(parents=True)
    (tmp_path / "bench" / "cells" / "c.json").write_text(
        json.dumps({"window_s": 6.4}))
    counts = [harness.timed_windows("c", s, tmp_path)
              for s in (0.1, 10, 51, 51.0, 60)]
    assert counts == [1, 2, 8, 8, 9]


def test_refuses_to_run_without_a_tpu(capsys):
    assert bench_run.main(["--workload", "gap-kron.window", "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "TPU" in out.err


def test_every_metric_and_cell_resolves_to_files():
    spec = harness.load_spec(REPO)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.reader(m["name"], REPO))
    for cell in spec["workloads"]:
        _, cfg, mix = harness.resolve(spec, cell["name"], REPO)
        assert cfg["name"] == cell["config"]
        assert mix["add_edges"] >= 1
        assert harness.timed_windows(cell["name"], spec["run_seconds"],
                                     REPO) > 1
    assert len(json.dumps(spec).encode()) < 64 * 1024


def test_a_new_cell_is_new_files_and_entries(tmp_path, jax_cache_config):
    """A configuration, a traffic mix and a metric added as files beside
    the others, and named only in BENCHMARK.json."""
    root = small_root(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "gap-kron.json").read_text())
    (b / "configs" / "tiny-urand.json").write_text(json.dumps(
        dict(cfg, name="tiny-urand", generator="urand", scale=9)))
    (b / "traffic" / "burst.json").write_text(json.dumps(
        {"del_edges": 100, "add_edges": 100, "warmup_windows": 1,
         "fresh_edges": 400}))
    (b / "metrics" / "windows_answered.py").write_text(
        "def read(rec):\n    return len(rec['windows'])\n")
    (b / "cells" / "tiny-urand.burst.json").write_text(json.dumps(
        {"window_s": 0.1}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-urand", "source": "test",
                            "file": "bench/configs/tiny-urand.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "tiny-urand.burst",
                              "config": "tiny-urand", "traffic": "burst",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "windows_answered", "unit": "windows",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["tiny-urand.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run(root, "tiny-urand.burst")
    assert out["correct"], out
    assert out["metrics"]["windows_answered"]["value"] == out["attempted"]
    assert out["attempted"] == 3, "0.3 s over a nominal 0.1 s per window"
    assert set(out["metrics"]) == {"events_per_s", "setup_s",
                                   "windows_answered"}
    assert list(out)[-1] == "checks"


def test_program_is_correct_and_trickle_reports_its_tail(tmp_path,
                                                          jax_cache_config):
    out = run(small_root(tmp_path), "gap-kron.trickle")
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"events_per_s", "answer_latency_p95_ms",
                                   "setup_s"}
    assert out["checks"]["dist_mismatch"] == {"value": 0, "limit": 0}


def test_reference_in_the_programs_place_is_correct(tmp_path,
                                                     jax_cache_config):
    out = run(small_root(tmp_path), "gap-kron.trickle",
              make_engine=control.ReferenceEngine)
    assert out["correct"], out


@pytest.mark.parametrize("workload", ["gap-kron.trickle", "gap-urand.window"])
def test_control_is_not_correct(tmp_path, jax_cache_config, workload):
    """The reference with the DELs never applied."""
    out = run(small_root(tmp_path), workload,
              make_engine=control.control_engine)
    assert not out["correct"]
    assert out["checks"]["parent_bad"]["value"] > 0


# ------------------------------------------------------------- faults --
class _Fault:
    """The program, broken underneath the harness."""

    def __init__(self, **settings):
        import repro
        self.engine = repro.make_engine(**settings)
        self.calls = 0

    @property
    def n_rounds(self):
        return self.engine.n_rounds

    def ingest_log(self, log):
        self.calls += 1
        return self.engine.ingest_log(self.alter_log(log))

    def alter_log(self, log):
        return log

    def query(self):
        return self.engine.query()


class StateUnchanged(_Fault):
    """After the load, a window's events leave the state as it was."""

    def alter_log(self, log):
        return log if self.calls == 1 else log[:0]


class HalfBatch(_Fault):
    """After the load, every other event of each window is left out."""

    def alter_log(self, log):
        return log if self.calls == 1 else log[::2]


class AnswerAltered(_Fault):
    """One reached vertex's distance, altered where it is produced."""

    def query(self):
        res = self.engine.query()
        res.dist = res.dist.copy()
        reached = np.flatnonzero(np.isfinite(res.dist))
        res.dist[reached[len(reached) // 2]] += 1.0
        return res


@pytest.mark.parametrize("fault", [StateUnchanged, HalfBatch, AnswerAltered])
def test_a_fault_in_the_timed_path_is_not_correct(tmp_path, jax_cache_config,
                                                   fault):
    out = run(small_root(tmp_path, edges=64), "gap-kron.window",
              make_engine=fault)
    assert not out["correct"], out
    assert out["failed"] >= 1


def test_compare_counts_each_kind_of_wrong_answer():
    # a path 0 -1- 1 -2- 2, and an isolated vertex 3
    u, v, w = np.array([0, 1]), np.array([1, 2]), np.array([1.0, 2.0])
    g = reference.LiveGraph.of_edges(4, u, v, w)
    ref = g.sssp(0)
    assert list(ref) == [0, 1, 3, np.inf]
    good = (np.array([0, 1, 3, np.inf]), np.array([-1, 0, 1, -1]))
    assert reference.compare(g, 0, ref, *good) == {"dist_mismatch": 0,
                                                   "parent_bad": 0}
    cases = [
        (np.array([0, 1, 2, np.inf]), good[1], (1, 0)),   # distance off
        (good[0], np.array([-1, 0, 0, -1]), (0, 1)),      # no arc 0 -> 2
        (good[0], np.array([-1, 2, 1, -1]), (0, 1)),      # arc not tight
        (good[0], np.array([-1, 0, 1, 2]), (0, 1)),       # unreached, parent
        (good[0], np.array([1, 0, 1, -1]), (0, 1)),       # source, parent
    ]
    for dist, parent, (dm, pb) in cases:
        assert reference.compare(g, 0, ref, dist, parent) == {
            "dist_mismatch": dm, "parent_bad": pb}
