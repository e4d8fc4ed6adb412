"""Engine telemetry layer (DESIGN.md §10): counter registry, span tracer,
flight recorder, and their wiring through both engines.

The load-bearing contracts:

  * telemetry never changes the computation — an observability-enabled
    engine is bit-identical (dist, parent, rounds, messages) to its
    uninstrumented twin on any stream, for every backend and schedule;
  * span counts, engine counters and the exported Chrome trace are three
    views of the same events and must always agree;
  * instrumented ingest obeys the §2.4 no-host-sync rule — the device
    counters accumulate lazily and drain only at ``snapshot()`` /
    ``metrics_snapshot()`` (the device_get trap test, across the backend
    x engine grid);
  * the flight recorder is a bounded ring and dumps once on a dispatch
    exception.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.core import events as ev
from repro.core.dist_engine import ShardedEngineConfig, ShardedSSSPDelEngine
from repro.core.engine import EngineConfig, SSSPDelEngine
from repro.graphs import generators, window
from repro.obs import (SPAN_NAMES, CounterRegistry, EngineObs,
                       FlightRecorder, SpanTracer, load_chrome_trace,
                       out_path_or_exit, span_counts_of, write_log_jsonl)
from repro.serving import TraceRecorder, replay_trace

HERE = os.path.dirname(__file__)
# tiny layout knobs so rebuild/spill paths run under instrumentation too
BACKEND_KW = {
    "segment": {},
    "ellpack": dict(ell_init_k=2),
    "sliced": dict(sliced_slice_rows=32, sliced_hub_k=4, sliced_init_k=1),
}


def _dynamic_stream(seed: int, *, n=72, m=320, delta=0.5):
    n, src, dst, w = generators.erdos_renyi(n, m, seed=seed)
    log = window.sliding_window_stream(src, dst, w, window=m // 3,
                                       delta=delta, seed=seed,
                                       query_every=m // 2)
    return n, len(src), log


def _mk(engine: str, backend: str, n: int, cap: int, source: int, **kw):
    if engine == "single":
        return SSSPDelEngine(EngineConfig(
            n, cap, source, relax_backend=backend,
            **BACKEND_KW[backend], **kw))
    return ShardedSSSPDelEngine(ShardedEngineConfig(
        n, cap, source, relax_backend=backend, **BACKEND_KW[backend], **kw))


# --------------------------------------------------------- counter registry --
def test_counter_registry_device_and_host():
    import jax.numpy as jnp
    reg = CounterRegistry(enabled=True)
    reg.add("frontier", jnp.int32(3))          # device scalar, lazy
    reg.add("frontier", jnp.int32(4))
    reg.add("waves", jnp.asarray([1, 2, 3]))   # [S] vector, lazy
    reg.add("waves", jnp.asarray([1, 0, 1]))
    reg.peak("hw", jnp.int32(5))
    reg.peak("hw", jnp.int32(2))
    reg.inc("epochs")                          # host int
    reg.inc("epochs", 4)
    reg.inc("per_part", np.array([1, 0]))      # host [P] tally
    reg.inc("per_part", np.array([0, 2]))
    snap = reg.snapshot()
    assert snap["frontier"] == 7 and isinstance(snap["frontier"], int)
    np.testing.assert_array_equal(snap["waves"], [2, 2, 4])
    assert snap["hw"] == 5
    assert snap["epochs"] == 5
    np.testing.assert_array_equal(snap["per_part"], [1, 2])
    assert reg.names() == sorted(["frontier", "waves", "hw", "epochs",
                                  "per_part"])


def test_counter_registry_merges_host_and_device_same_name():
    import jax.numpy as jnp
    reg = CounterRegistry(enabled=True)
    reg.inc("rebuilds", 2)
    reg.add("rebuilds", jnp.int32(3))
    assert reg.snapshot()["rebuilds"] == 5


def test_counter_registry_disabled_noops():
    reg = CounterRegistry(enabled=False)
    reg.add("a", 1)
    reg.inc("b")
    reg.peak("c", 9)
    assert reg.snapshot() == {} and reg.names() == []


# --------------------------------------------------------------- span tracer --
def test_span_nesting_roundtrips_through_chrome_trace(tmp_path):
    tr = SpanTracer(enabled=True)
    with tr.span("outer", events=2):
        with tr.span("inner"):
            pass
        tr.instant("rebuild")
        with tr.span("inner"):
            pass
    path = str(tmp_path / "trace.json")
    tr.save_chrome(path)
    events = load_chrome_trace(path)
    assert span_counts_of(events) == tr.span_counts() == \
        {"outer": 1, "inner": 2, "rebuild": 1}
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    outer, = by_name["outer"]
    assert outer["ph"] == "X" and outer["args"]["depth"] == 0
    assert outer["args"]["events"] == 2
    for inner in by_name["inner"]:
        assert inner["args"]["depth"] == 1
        # nesting: every inner interval sits inside the outer interval
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    reb, = by_name["rebuild"]
    assert reb["ph"] == "i" and reb["s"] == "t" and "dur" not in reb
    assert outer["ts"] <= reb["ts"] <= outer["ts"] + outer["dur"]


def test_span_jsonl_and_load_errors(tmp_path):
    tr = SpanTracer(enabled=True)
    with tr.span("epoch", kindof="add"):
        tr.instant("mark")
    path = str(tmp_path / "spans.jsonl")
    tr.save_jsonl(path)
    lines = [json.loads(line) for line in
             Path(path).read_text().splitlines()]
    assert [ln["name"] for ln in lines] == ["mark", "epoch"]
    assert lines[1]["args"] == {"kindof": "add"}
    assert all(ln["dur_us"] >= 0 and ln["ts_us"] >= 0 for ln in lines)
    bad = tmp_path / "not_chrome.json"
    bad.write_text(json.dumps([1, 2, 3]))
    with pytest.raises(ValueError, match="traceEvents"):
        load_chrome_trace(str(bad))


def test_disabled_tracer_records_nothing():
    tr = SpanTracer(enabled=False)
    with tr.span("epoch"):
        tr.instant("mark")
    assert tr.spans == [] and tr.span_counts() == {}


@pytest.mark.parametrize("enabled", [False, True])
def test_span_totals_fold_count_total_and_self_time(enabled, monkeypatch):
    """The always-on aggregate: per name, count, total ns, and self ns —
    the duration less what the span's children cover (grandchildren are
    inside the children, so they are not subtracted twice)."""
    import types

    import repro.obs.spans as spans_mod
    clock = iter(range(0, 10_000, 10))      # each reading 10 ns later
    monkeypatch.setattr(spans_mod, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: next(clock)))
    tr = SpanTracer(enabled=enabled, annotate=False)   # reads 0
    with tr.span("query"):                  # 10 .. 80
        with tr.span("settle"):             # 20 .. 50
            with tr.span("drain"):          # 30 .. 40
                pass
        with tr.span("readback"):           # 60 .. 70
            pass
    with tr.span("readback"):               # 90 .. 100
        pass
    assert tr.totals() == {
        "drain": {"count": 1, "total_ns": 10, "self_ns": 10},
        "settle": {"count": 1, "total_ns": 30, "self_ns": 20},
        "readback": {"count": 2, "total_ns": 20, "self_ns": 20},
        "query": {"count": 1, "total_ns": 70, "self_ns": 30},
    }
    assert len(tr.spans) == (5 if enabled else 0)
    assert tr.span_counts() == (
        {"query": 1, "settle": 1, "drain": 1, "readback": 2}
        if enabled else {})


def test_span_totals_count_a_span_left_by_an_exception():
    tr = SpanTracer(enabled=False)
    with pytest.raises(RuntimeError):
        with tr.span("del_epoch"):
            raise RuntimeError("boom")
    with tr.span("add_epoch"):
        pass
    assert {k: v["count"] for k, v in tr.totals().items()} == \
        {"del_epoch": 1, "add_epoch": 1}
    # the failed span left no open frame behind: add_epoch is top-level
    assert tr.totals()["add_epoch"]["self_ns"] == \
        tr.totals()["add_epoch"]["total_ns"]


# ----------------------------------------------------------- flight recorder --
def test_flight_recorder_ring_wraps_at_capacity():
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("add_epoch", events=i)
    assert fr.total == 20 and fr.capacity == 8
    recs = fr.records()
    assert len(recs) == 8
    assert [r["seq"] for r in recs] == list(range(12, 20))
    assert recs[-1]["events"] == 19
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_flight_recorder_dump_format(capsys):
    fr = FlightRecorder(capacity=4)
    fr.record("drain", wall_ms=1.25)
    text = fr.dump(header="postmortem")
    err = capsys.readouterr().err
    assert text in err and err.startswith("# postmortem")
    assert json.loads(text.splitlines()[1])["kind"] == "drain"


# ----------------------------------------------------------------- EngineObs --
def test_engine_obs_epoch_dumps_flight_recorder_once(capsys):
    obs = EngineObs(enabled=True, flight_capacity=4)
    with obs.epoch("add_epoch", events=3):
        pass
    with pytest.raises(RuntimeError, match="boom"):
        with obs.epoch("del_epoch", events=1):
            raise RuntimeError("boom")
    err = capsys.readouterr().err
    assert "flight recorder" in err and "boom" in err
    snap = obs.counters.snapshot()
    assert snap["add_epochs"] == 1                       # failure not counted
    # the successful epoch also folded one wall-time histogram sample;
    # the failed one folded none
    assert int(np.sum(snap["hist_add_epoch_wall_us"])) == 1
    assert "hist_del_epoch_wall_us" not in snap
    assert set(snap) == {"add_epochs", "hist_add_epoch_wall_us"}
    assert obs.tracer.span_counts() == {"add_epoch": 1, "del_epoch": 1}
    assert [r["kind"] for r in obs.recorder.records()] == \
        ["add_epoch", "del_epoch"]
    assert obs.recorder.records()[-1]["error"].startswith("RuntimeError")
    # one-shot: a second failure must not dump again
    with pytest.raises(RuntimeError):
        with obs.epoch("drain"):
            raise RuntimeError("again")
    assert "flight recorder" not in capsys.readouterr().err


def test_engine_obs_disabled_keeps_only_span_totals():
    obs = EngineObs(enabled=False)
    with obs.epoch("add_epoch"):
        with obs.tracer.span("plan_adds"):
            pass
    assert obs.tracer.spans == [] and obs.recorder.total == 0
    assert obs.counters.snapshot() == {}
    assert {k: v["count"] for k, v in obs.tracer.totals().items()} == \
        {"add_epoch": 1, "plan_adds": 1}


def test_engine_obs_disabled_is_inert():
    obs = EngineObs(enabled=False)
    with obs.epoch("add_epoch"):
        pass
    obs.note_layout({"rebuilds": 3})
    assert obs.counters.snapshot() == {}
    assert obs.tracer.span_counts() == {}
    assert obs.recorder.total == 0


def test_note_layout_deltas_and_rebuild_instants():
    obs = EngineObs(enabled=True)
    obs.note_layout({"rebuilds": 2, "overflow_hits": 5})
    obs.note_layout({"rebuilds": 2, "overflow_hits": 9})
    obs.note_layout({"rebuilds": 3, "overflow_hits": 0})  # reset clamps to 0
    snap = obs.counters.snapshot()
    assert snap == {"rebuilds": 3, "overflow_hits": 9}
    # one instant per rebuild delta — spans and counters can never disagree
    assert obs.tracer.span_counts() == {"rebuild": 3}


# --------------------------------------------------------- engine integration --
@pytest.mark.parametrize("backend", ["segment", "ellpack", "sliced"])
@pytest.mark.parametrize("schedule", ["rounds", "buckets"])
def test_single_engine_obs_bit_identical_and_consistent(backend, schedule):
    """Instrumentation is algorithmically free: the obs-enabled engine
    matches its uninstrumented twin bit for bit, and every telemetry view
    (spans, counters, metrics_snapshot) agrees with the engine's own
    stats."""
    n, m, log = _dynamic_stream(seed=11)
    kw = dict(wave_schedule=schedule)
    plain = _mk("single", backend, n, m + 64, 3, **kw)
    inst = _mk("single", backend, n, m + 64, 3, observability=True, **kw)
    res_p = plain.ingest_log(log) + [plain.query()]
    res_i = inst.ingest_log(log) + [inst.query()]
    for a, b in zip(res_p, res_i):
        np.testing.assert_array_equal(a.dist, b.dist)
        np.testing.assert_array_equal(a.parent, b.parent)
    assert plain.n_rounds == inst.n_rounds
    assert plain.n_messages == inst.n_messages

    snap = inst.metrics_snapshot()
    assert snap["rounds"] == inst.n_rounds
    assert snap["messages"] == inst.n_messages
    sp, ct = snap["spans"], snap["counters"]
    assert sp["add_epoch"] == ct["add_epochs"]
    assert sp["del_epoch"] == ct["del_epochs"]
    assert sp["add_epoch"] + sp["del_epoch"] == inst.n_epochs
    assert sp["query"] == ct["queries"] == len(res_i)
    assert sp.get("rebuild", 0) == ct.get("rebuilds", 0)
    if backend == "ellpack":
        assert ct["rebuilds"] == inst.backend.planner.rebuilds >= 1
    if backend == "sliced":
        assert ct["overflow_hits"] == inst.backend.planner.spills >= 1
    assert ct["frontier"] > 0            # lazy device counter drained here
    if schedule == "buckets":
        assert sp.get("drain", 0) == ct.get("drains", 0) > 0
        assert ct["drain_waves"] > 0
    # the plain twin carries no telemetry state at all
    assert plain.metrics_snapshot()["counters"] == {}
    assert plain.metrics_snapshot()["spans"] == {}


@pytest.mark.parametrize("backend", ["segment", "sliced"])
def test_sharded_engine_obs_bit_identical_and_consistent(backend):
    n, m, log = _dynamic_stream(seed=17)
    plain = _mk("sharded", backend, n, m + 64, 3)
    inst = _mk("sharded", backend, n, m + 64, 3, observability=True)
    res_p = plain.ingest_log(log) + [plain.query()]
    res_i = inst.ingest_log(log) + [inst.query()]
    for a, b in zip(res_p, res_i):
        np.testing.assert_array_equal(a.dist, b.dist)
        np.testing.assert_array_equal(a.parent, b.parent)
    assert plain.n_rounds == inst.n_rounds
    assert plain.n_messages == inst.n_messages
    snap = inst.metrics_snapshot()
    assert snap["rounds"] == inst.n_rounds
    sp, ct = snap["spans"], snap["counters"]
    assert sp["add_epoch"] == ct["add_epochs"]
    assert sp["del_epoch"] == ct["del_epochs"]
    assert sp["add_epoch"] + sp["del_epoch"] == inst.n_epochs
    assert sp.get("rebuild", 0) == ct.get("rebuilds", 0)
    # per-partition tallies come back as [P] vectors summing to the totals
    P = inst.P
    assert np.asarray(ct["adds_per_part"]).shape == (P,)
    assert int(np.sum(ct["adds_per_part"])) == inst.n_adds
    assert int(np.sum(ct["dels_per_part"])) == inst.n_dels


def test_batched_sources_snapshot_is_per_lane():
    n, m, log = _dynamic_stream(seed=23)
    srcs = (3, 17, 40)
    eng = SSSPDelEngine(EngineConfig(n, m + 64, srcs[0], sources=srcs,
                                     observability=True))
    eng.ingest_log(log)
    snap = eng.metrics_snapshot()
    np.testing.assert_array_equal(snap["rounds"], eng.n_rounds)
    np.testing.assert_array_equal(snap["messages"], eng.n_messages)
    assert np.asarray(snap["rounds"]).shape == (len(srcs),)
    ck = eng.checkpoint()
    assert ck is not None
    snap = eng.metrics_snapshot()
    assert snap["spans"]["checkpoint"] == snap["counters"]["checkpoints"] == 1


def test_replay_report_carries_engine_metrics():
    n, m, log = _dynamic_stream(seed=29)
    rec = TraceRecorder()
    rec.extend_from_log(log)
    eng = SSSPDelEngine(EngineConfig(n, m + 64, 3, observability=True))
    rep = replay_trace(eng, rec.trace())
    assert rep.engine_metrics["rounds"] == eng.n_rounds
    assert rep.engine_metrics["messages"] == eng.n_messages
    r = rep.to_record()
    assert r["rounds"] == eng.n_rounds and r["messages"] == eng.n_messages


def test_dump_flight_recorder_postmortem():
    n, m, log = _dynamic_stream(seed=31)
    eng = SSSPDelEngine(EngineConfig(n, m + 64, 3, observability=True,
                                     obs_flight_capacity=6))
    eng.ingest_log(log)
    text = eng.dump_flight_recorder()
    recs = [json.loads(line) for line in text.splitlines()
            if not line.startswith("#")]
    assert 0 < len(recs) <= 6
    assert {r["kind"] for r in recs} <= \
        {"add_epoch", "del_epoch", "drain", "query", "checkpoint"}
    with pytest.raises(ValueError, match="obs_flight_capacity"):
        EngineConfig(n, m + 64, 3, obs_flight_capacity=0)


# ------------------------------------------------ always-on spans, rounds --
@pytest.mark.parametrize("engine", ["single", "sharded"])
@pytest.mark.parametrize("schedule", ["rounds", "buckets"])
def test_spans_stay_on_with_observability_off(engine, schedule):
    """With observability off the tracer keeps no Span list, but its
    aggregate holds every span the path opened, counted once per epoch,
    planning call, deletion group and query."""
    n, m, log = _dynamic_stream(seed=41)
    eng = _mk(engine, "segment", n, m + 64, 3, wave_schedule=schedule)
    res = eng.ingest_log(log)
    assert eng.obs.tracer.spans == []
    tot = eng.obs.tracer.totals()
    count = {k: v["count"] for k, v in tot.items()}
    opened = {"plan_adds", "plan_dels", "add_epoch", "del_epoch", "query",
              "settle", "readback"} | ({"drain"} if schedule == "buckets"
                                       else set())
    assert set(count) == opened and opened <= set(SPAN_NAMES)
    runs = list(log.runs())
    assert count["plan_adds"] == sum(b.kind == ev.ADD for b in runs)
    assert count["plan_dels"] == sum(
        len(eng._deletion_groups(b)) for b in runs if b.kind == ev.DEL)
    assert count["add_epoch"] + count["del_epoch"] == eng.n_epochs
    assert count["query"] == count["settle"] == count["readback"] \
        == len(res)
    if schedule == "buckets":
        assert count["drain"] == len(res)
    for name, t in tot.items():
        assert 0 <= t["self_ns"] <= t["total_ns"], name
    # the drain, settle and readback are children of the query
    q = tot["query"]
    assert q["total_ns"] - q["self_ns"] >= \
        tot["settle"]["total_ns"] + tot["readback"]["total_ns"]
    snap = eng.metrics_snapshot()
    assert snap["spans"] == {} and snap["span_totals"] == tot


def test_span_totals_do_not_grow_with_the_stream():
    n, m, log = _dynamic_stream(seed=43)
    eng = _mk("single", "segment", n, m + 64, 3)
    eng.ingest_log(log)
    first = eng.obs.tracer.totals()
    for _ in range(2):
        eng.ingest_log(log)
    again = eng.obs.tracer.totals()
    assert eng.obs.tracer.spans == [] and eng.obs.tracer._child_ns == []
    assert set(again) == set(first)
    # a replayed ADD is a duplicate (planned, never dispatched): planning
    # and query spans triple, epoch spans need not
    for name in ("plan_adds", "plan_dels", "query", "settle", "readback"):
        assert again[name]["count"] == 3 * first[name]["count"]
    assert eng.obs.recorder.total == 0


@pytest.mark.parametrize("backend", ["segment", "ellpack", "sliced"])
@pytest.mark.parametrize("schedule", ["rounds", "buckets"])
def test_rounds_by_kind_sum_to_n_rounds_in_both_engines(backend, schedule):
    """DEL rounds apart from the rest, summing to ``n_rounds`` exactly, and
    the same split in the single-device and the sharded engine."""
    n, m, log = _dynamic_stream(seed=47)
    got = {}
    for engine in ("single", "sharded"):
        eng = _mk(engine, backend, n, m + 64, 3, wave_schedule=schedule)
        eng.ingest_log(log)
        eng.query()
        kinds = eng.rounds_by_kind
        assert kinds["add"] + kinds["del"] == eng.n_rounds
        assert kinds["add"] > 0 and kinds["del"] > 0
        assert eng.metrics_snapshot()["rounds_by_kind"] == kinds
        got[engine] = kinds
    assert got["single"] == got["sharded"]


def test_rounds_by_kind_are_per_lane_when_batched():
    n, m, log = _dynamic_stream(seed=53)
    eng = SSSPDelEngine(EngineConfig(n, m + 64, 3, sources=(3, 17, 40)))
    eng.ingest_log(log)
    kinds = eng.rounds_by_kind
    assert kinds["add"].shape == kinds["del"].shape == (3,)
    np.testing.assert_array_equal(kinds["add"] + kinds["del"], eng.n_rounds)


# -------------------------------------------------- §2.4 no-host-sync rule --
@pytest.mark.parametrize("observability", [False, True])
@pytest.mark.parametrize("engine", ["single", "sharded"])
@pytest.mark.parametrize("backend", ["segment", "ellpack", "sliced"])
def test_instrumented_ingest_never_reads_device_values(engine, backend,
                                                       observability,
                                                       monkeypatch):
    """Satellite: the device_get trap holds with the always-on spans and
    the DEL-round counter, and WITH observability enabled — the counter
    registry accumulates lazily, spans are pure host bookkeeping, so
    ADD/DEL ingest still never syncs nor waits for the device."""
    n, m, log = _dynamic_stream(seed=13)
    eng = _mk(engine, backend, n, m + 64, 0, observability=observability)
    topo = log[np.asarray(log.kind) != ev.QUERY]

    def trap(*a, **k):
        raise AssertionError("device_get during instrumented ingest")

    monkeypatch.setattr(jax, "device_get", trap)
    monkeypatch.setattr(jax, "block_until_ready", trap)
    eng.ingest_log(topo)  # only ADD/DEL runs: must not sync
    monkeypatch.undo()
    q = eng.query()
    assert np.isfinite(np.asarray(q.dist)).any()
    snap = eng.metrics_snapshot()   # the sanctioned read-back point
    assert snap["span_totals"]["add_epoch"]["count"] > 0
    if observability:
        assert snap["counters"]["add_epochs"] > 0


# ----------------------------------------------------------- CLI / examples --
def _example_env():
    root = Path(HERE).resolve().parent
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(root / "src")
                         + (":" + env["PYTHONPATH"]
                            if env.get("PYTHONPATH") else ""))
    return root, env


def test_out_path_or_exit_contract(tmp_path, capsys):
    ok = str(tmp_path / "trace.json")
    assert out_path_or_exit(ok) == ok
    with pytest.raises(SystemExit) as ei:
        out_path_or_exit(str(tmp_path / "no_such_dir" / "trace.json"))
    assert ei.value.code == 2
    assert "error:" in capsys.readouterr().err


@pytest.mark.parametrize("flag", ["--trace-out", "--metrics-out"])
@pytest.mark.parametrize("example", ["streaming_sssp.py",
                                     "sharded_streaming_sssp.py"])
def test_examples_exit_2_on_bad_obs_out_dir(example, flag, tmp_path):
    root, env = _example_env()
    proc = subprocess.run(
        [sys.executable, str(root / "examples" / example),
         flag, str(tmp_path / "missing_dir" / "out.json")],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 2, proc.stderr
    assert "error:" in proc.stderr


def test_example_replay_writes_trace_and_jsonl(tmp_path):
    """End-to-end CLI pass: replay a tiny recorded trace with --trace-out
    and --log-json; both artifacts must exist and parse, and the JSONL's
    final metrics_snapshot line must agree with the Chrome trace's span
    counts."""
    n, m, log = _dynamic_stream(seed=37)
    rec = TraceRecorder()
    rec.extend_from_log(log)
    trace_path = str(tmp_path / "stream.trace")
    rec.trace().save(trace_path)
    out_json = str(tmp_path / "spans.chrome.json")
    out_jsonl = str(tmp_path / "spans.jsonl")
    out_prom = str(tmp_path / "metrics.prom")
    root, env = _example_env()
    proc = subprocess.run(
        [sys.executable, str(root / "examples" / "streaming_sssp.py"),
         "--replay-trace", trace_path, "--trace-out", out_json,
         "--log-json", out_jsonl, "--metrics-out", out_prom],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    events = load_chrome_trace(out_json)
    counts = span_counts_of(events)
    assert counts.get("add_epoch", 0) > 0
    lines = Path(out_jsonl).read_text().splitlines()
    final = json.loads(lines[-1])
    assert final["kind"] == "metrics_snapshot"
    assert final["spans"] == counts
    assert final["counters"]["add_epochs"] == counts["add_epoch"]
    # the Prometheus artifact agrees with both other views (§10.7)
    from repro.obs.export import parse_prometheus_text
    parsed = parse_prometheus_text(Path(out_prom).read_text())
    assert parsed["repro_add_epochs"][()] == counts["add_epoch"]
    assert parsed["repro_hist_latency_us_count"][()] == \
        final["counters"]["queries"]


# ------------------------------------------------------- P=8 acceptance run --
def test_obs_p8_acceptance_subprocess(tmp_path):
    """The ISSUE's acceptance scenario: a sharded (P=8 forced devices)
    bucketed replay of the power-law trace with a Chrome trace out; the
    worker asserts span counts == engine counters and metrics_snapshot
    bit-identity, the parent re-validates the exported artifact."""
    out = str(tmp_path / "p8.chrome.json")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_obs_worker.py"), out],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert proc.stdout.strip().startswith("OK"), proc.stdout
    events = load_chrome_trace(out)
    counts = span_counts_of(events)
    assert counts.get("add_epoch", 0) > 0 and counts.get("drain", 0) > 0
    assert counts.get("rebuild", 0) > 0


def test_obs_p8_crash_dumps_flight_recorder_subprocess():
    """Satellite scenario: a failing epoch on the SHARDED (P=8) path must
    dump the flight recorder postmortem to stderr exactly once, carrying
    the injected error and the healthy epochs recorded before it."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_obs_crash_worker.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert proc.stdout.strip().startswith("OK"), proc.stdout
    err = proc.stderr
    assert err.count("flight recorder postmortem") == 1, err[-2000:]
    assert "RuntimeError('injected epoch failure')" in err, err[-2000:]
    # the dump carries the healthy epochs recorded BEFORE the failure
    lines = [ln for ln in err.splitlines() if ln.startswith("{")]
    assert any('"error"' in ln for ln in lines), err[-2000:]
    assert any('"wall_ms"' in ln and "add_epoch" in ln
               for ln in lines), err[-2000:]
