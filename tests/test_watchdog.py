"""Stall / divergence watchdog (DESIGN.md §10.8).

Contracts under test:

  * synchronous checks — a finished epoch slower than
    ``max_epoch_wall_s`` or an ADD frontier above ``max_frontier`` emits
    one structured warning (FlightRecorder record + counter + stderr);
  * stall sampling — an armed region older than ``stall_timeout_s``
    fires ONCE from the sampler thread, bumps ``watchdog_stalls`` and
    triggers the one-shot flight-recorder dump while the "engine thread"
    is still blocked;
  * divergence review — a waves-per-epoch histogram whose top occupied
    bucket reaches ``max_drain_waves`` is flagged at most once;
  * a default-config watchdog stays silent on a healthy engine run.
"""
import time

import numpy as np
import pytest

from repro.core.engine import EngineConfig, SSSPDelEngine
from repro.graphs import generators, window
from repro.obs import EngineObs, WatchdogConfig
from repro.obs import hist
from repro.obs.watchdog import Watchdog


def _obs(cfg: WatchdogConfig) -> EngineObs:
    return EngineObs(enabled=True, watchdog=cfg)


# -------------------------------------------------------- synchronous checks
def test_slow_epoch_warns_once_per_offender(capsys):
    obs = _obs(WatchdogConfig(stall_timeout_s=0.0, max_epoch_wall_s=1e-9))
    with obs.epoch("add_epoch"):
        pass
    err = capsys.readouterr().err
    assert "slow_epoch" in err
    assert obs.watchdog.warnings == 1
    snap = obs.counters.snapshot()
    assert snap["watchdog_warnings"] == 1
    assert "watchdog_stalls" not in snap
    kinds = [r["kind"] for r in obs.recorder.records()]
    assert "watchdog" in kinds


def test_frontier_blowup_threshold():
    obs = _obs(WatchdogConfig(stall_timeout_s=0.0, max_frontier=10))
    obs.watchdog.observe("add_epoch", 0.0, {"frontier": 5})
    assert obs.watchdog.warnings == 0
    obs.watchdog.observe("add_epoch", 0.0, {"frontier": 11})
    assert obs.watchdog.warnings == 1


# ----------------------------------------------------------------- stalls --
def test_stall_fires_once_and_dumps_recorder(capsys):
    obs = _obs(WatchdogConfig(stall_timeout_s=0.05, poll_interval_s=0.01))
    wd = obs.watchdog
    obs.recorder.record("add_epoch", wall_ms=1.0)
    wd.arm("add_epoch")
    try:
        deadline = time.perf_counter() + 5.0
        while wd.warnings == 0 and time.perf_counter() < deadline:
            time.sleep(0.01)
        # hold the region armed past several polls: one firing only
        time.sleep(0.1)
    finally:
        wd.disarm()
        wd.stop()
    assert wd.warnings == 1
    assert obs._dumped
    snap = obs.counters.snapshot()
    assert snap["watchdog_stalls"] == 1
    err = capsys.readouterr().err
    assert "stall" in err and "flight recorder postmortem" in err
    assert "add_epoch" in err


def test_stall_in_engine_epoch_region(capsys):
    """End-to-end through a real engine: a patched backend sleep inside
    the dispatched epoch trips the sampler while the engine thread is
    still inside ``obs.epoch``."""
    n, src, dst, w = generators.erdos_renyi(48, 160, seed=5)
    eng = SSSPDelEngine(EngineConfig(
        n, len(src) + 32, 0, observability=True,
        obs_watchdog=WatchdogConfig(stall_timeout_s=0.05,
                                    poll_interval_s=0.01)))
    stage = eng.backend.apply_adds

    def slow_stage(*a, **kw):
        time.sleep(0.3)
        return stage(*a, **kw)

    eng.backend.apply_adds = slow_stage
    log = window.sliding_window_stream(src, dst, w, window=80, delta=0.5,
                                       seed=5)
    batch = next(iter(log.runs()))
    eng._ingest_adds(batch)
    eng.obs.watchdog.stop()
    snap = eng.metrics_snapshot()
    assert snap["counters"]["watchdog_stalls"] >= 1
    err = capsys.readouterr().err
    assert "stall" in err and "flight recorder postmortem" in err
    # the stall dump did NOT break the run: the epoch completed
    assert snap["counters"]["add_epochs"] == 1


def test_no_stall_when_epochs_are_fast():
    obs = _obs(WatchdogConfig(stall_timeout_s=0.2, poll_interval_s=0.01))
    for _ in range(20):
        with obs.epoch("add_epoch"):
            pass
    time.sleep(0.1)
    obs.watchdog.stop()
    assert obs.watchdog.warnings == 0
    assert "watchdog_warnings" not in obs.counters.snapshot()


# ------------------------------------------------------- divergence review --
def test_review_flags_wave_divergence_once(capsys):
    obs = _obs(WatchdogConfig(stall_timeout_s=0.0, max_drain_waves=64))
    counts = hist.zeros_np()
    counts[3] = 5                                  # top bucket lo = 4 < 64
    obs.watchdog.review({"hist_waves_per_epoch": counts})
    assert obs.watchdog.warnings == 0
    counts[8] = 1                                  # top bucket lo = 128 >= 64
    obs.watchdog.review({"hist_waves_per_epoch": counts})
    assert obs.watchdog.warnings == 1
    obs.watchdog.review({"hist_waves_per_epoch": counts})  # once only
    assert obs.watchdog.warnings == 1
    assert "wave_divergence" in capsys.readouterr().err


def test_review_ignores_missing_or_empty_histogram():
    obs = _obs(WatchdogConfig(stall_timeout_s=0.0, max_drain_waves=4))
    obs.watchdog.review({})
    obs.watchdog.review({"hist_waves_per_epoch": hist.zeros_np()})
    assert obs.watchdog.warnings == 0


# ------------------------------------------------------------ healthy runs --
def test_default_config_watchdog_is_silent_on_healthy_run(capsys):
    n, src, dst, w = generators.erdos_renyi(64, 256, seed=7)
    log = window.sliding_window_stream(src, dst, w, window=128, delta=0.5,
                                       seed=7, query_every=128)
    eng = SSSPDelEngine(EngineConfig(
        n, len(src) + 64, 0, observability=True,
        obs_watchdog=WatchdogConfig()))
    eng.ingest_log(log)
    eng.query()
    snap = eng.metrics_snapshot()
    assert "watchdog_warnings" not in snap["counters"]
    assert eng.obs.watchdog.warnings == 0
    assert "[repro.obs.watchdog]" not in capsys.readouterr().err


def test_watchdog_absent_unless_configured():
    n, src, dst, w = generators.erdos_renyi(48, 128, seed=3)
    eng = SSSPDelEngine(EngineConfig(n, len(src) + 32, 0,
                                     observability=True))
    assert eng.obs.watchdog is None
    off = SSSPDelEngine(EngineConfig(n, len(src) + 32, 0,
                                     obs_watchdog=WatchdogConfig()))
    assert off.obs.watchdog is None      # obs disabled wins


def test_stop_is_idempotent_and_joins_thread():
    obs = _obs(WatchdogConfig(stall_timeout_s=0.05, poll_interval_s=0.01))
    wd = obs.watchdog
    wd.arm("add_epoch")
    wd.disarm()
    assert wd._thread is not None
    wd.stop()
    assert wd._thread is None
    wd.stop()
