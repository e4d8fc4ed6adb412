"""The platform rules of the bring-up: kernel requests Mosaic refuses raise
on a TPU, the compile-cache helper's directory choice, and chip_smoke.py's
refusal to report a result without a TPU (plus its certificate, exercised
at a tiny size on the CPU)."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import repro
from repro import compile_cache
from repro.kernels.relax import config as kernel_config

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


@pytest.fixture
def fake_tpu(monkeypatch):
    """Steer the one platform decision to "TPU" without a chip."""
    monkeypatch.setattr(kernel_config, "_ON_TPU", True)


@pytest.mark.parametrize("knobs,reason", [
    (dict(relax_backend="sliced", sliced_fused=True),
     "Only 2D gather is supported"),
    (dict(frontier_mode="sparse", frontier_kernel=True), "scatter-min"),
])
def test_refused_kernel_request_raises_on_tpu(fake_tpu, knobs, reason):
    with pytest.raises(ValueError, match=reason):
        repro.make_engine(num_vertices=64, edge_capacity=128, source=0,
                          **knobs)


def test_compiling_kernel_request_is_allowed_on_tpu(fake_tpu):
    eng = repro.make_engine(num_vertices=64, edge_capacity=128, source=0,
                            relax_backend="ellpack", ell_use_kernel=True)
    assert eng.backend.use_kernel and not eng.backend.interpret


def test_kernel_requests_run_interpreted_off_tpu():
    assert not kernel_config.on_tpu()
    eng = repro.make_engine(num_vertices=64, edge_capacity=128, source=0,
                            relax_backend="sliced", sliced_fused=True)
    assert eng.backend.use_fused and eng.backend.interpret


def test_default_engines_use_no_kernel(fake_tpu):
    for backend in ("segment", "ellpack", "sliced", "auto"):
        eng = repro.make_engine(num_vertices=64, edge_capacity=128,
                                source=0, relax_backend=backend)
        assert not eng._use_kernel
        assert not getattr(eng.backend, "use_fused", False)


def test_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_checkout_path():
    assert compile_cache.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def _run_smoke(cwd: Path, *args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chip_smoke.py", "--scale", "6", "--churn", "16",
         "--query-every", "8", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_without_tpu():
    out = _run_smoke(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stdout
    assert '"ok"' not in out.stdout


def test_chip_smoke_fails_alone(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run_smoke(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.fixture(scope="module")
def tiny_workload():
    return chip_smoke.make_workload(9, 16, 128, 64, seed=0)


def test_chip_smoke_phases_certify_on_cpu(tiny_workload, monkeypatch,
                                          capsys):
    """Both single-chip phases end to end at scale 9: every query passes
    the certificate and the last one matches Dijkstra."""
    monkeypatch.setattr(chip_smoke, "LOAD_CHUNK", 2048)
    chip_smoke.run_one_chip(tiny_workload, jax.devices())
    out = capsys.readouterr().out
    assert out.count("certificate ok") == 2 * 5
    assert "final query matches Dijkstra" in out


def test_certificate_rejects_a_wrong_tree(tiny_workload):
    wl = tiny_workload
    eng = repro.make_engine(num_vertices=wl.n, edge_capacity=1 << 14,
                            source=wl.source)
    res = eng.ingest_log(chip_smoke.load_chunks(wl))[-1]
    live = wl.live(0)
    assert chip_smoke.certify(wl.n, *live, wl.source, res.dist,
                              res.parent) > 1
    v = int(np.nonzero(np.isfinite(res.dist)
                       & (np.arange(wl.n) != wl.source))[0][0])
    for dist, parent in (
            (np.where(np.arange(wl.n) == v, res.dist * 2, res.dist),
             res.parent),                                  # not tight
            (res.dist, np.where(np.arange(wl.n) == v, v,
                                res.parent)),              # edge not live
            (np.where(np.arange(wl.n) == v, np.inf, res.dist),
             res.parent)):                                 # can relax
        with pytest.raises(AssertionError):
            chip_smoke.certify(wl.n, *live, wl.source, dist, parent)
