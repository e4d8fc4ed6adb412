"""The program-span readers: spans found by name in the traced window,
totals and self times, and nothing read where there is nothing to read."""
import shutil
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import harness, spans, xplane

DATA = Path(__file__).resolve().parent / "data"
NEW_READERS = ("plan_ms_per_window", "dispatch_ms_per_window", "readback_ms")


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def profile():
    """Two windows on the main thread, in ns: ``bench.ingest_log`` holding
    ``plan_dels``, ``del_epoch``, ``plan_adds`` and ``add_epoch`` (the last
    with a nested ``plan_adds``, to test self time), then ``bench.query``
    holding ``query`` > ``settle``, ``readback``.  A runtime event and a
    span before the window are not read; another thread's ``add_epoch``
    is not a child of the main thread's."""
    main = NS(name="python3", events=[
        ev("plan_adds", -50, 10),                      # before the window
        ev("bench.ingest_log", 0, 100),
        ev("plan_dels", 5, 10), ev("del_epoch", 15, 25),
        ev("plan_adds", 40, 10), ev("add_epoch", 50, 40),
        ev("plan_adds", 60, 5),                        # inside add_epoch
        ev("PjitFunction(f)", 70, 5),                  # the runtime's own
        ev("bench.query", 100, 100),
        ev("query", 100, 95), ev("settle", 102, 48), ev("readback", 150, 40),
        ev("bench.ingest_log", 200, 50),
        ev("plan_dels", 200, 10), ev("del_epoch", 210, 20),
        ev("bench.query", 250, 50),
        ev("query", 250, 45), ev("settle", 251, 20), ev("readback", 271, 20),
    ])
    other = NS(name="worker", events=[ev("add_epoch", 55, 10)])
    return NS(planes=[NS(name="/host:CPU", lines=[main, other])])


def record(workload="gap-kron.trickle", windows=2):
    return {"workload": workload, "windows": [{}] * windows,
            "trace": {"busy_s": 1.0, "window_s": 1.0, "idle_share": 0.0}}


def test_spans_in_the_window_by_name():
    found = spans.in_window(profile(), spans.names())
    names = [f[0] for f in found]
    assert names == ["plan_dels", "del_epoch", "plan_adds", "add_epoch",
                     "add_epoch", "plan_adds", "query", "settle", "readback",
                     "plan_dels", "del_epoch", "query", "settle", "readback"]
    assert {f[1] for f in found if f[0] != "add_epoch"} == {0}
    assert sorted(f[1] for f in found if f[0] == "add_epoch") == [0, 1]


def test_total_and_self_time():
    found = spans.in_window(profile(), spans.names())
    assert sum(spans.durations_ns(found, ("plan_adds", "plan_dels"))) == \
        10 + 10 + 5 + 10
    # add_epoch 40 less its nested plan_adds 5, the other thread's 10;
    # del_epochs 25 and 20 hold nothing
    assert spans.self_ns(found, ("add_epoch", "del_epoch")) == \
        35 + 10 + 25 + 20
    assert spans.self_ns(found, ("query",)) == (95 - 88) + (45 - 40)
    assert spans.durations_ns(found, ("readback",)) == [40, 20]


@pytest.fixture
def synthetic(monkeypatch):
    monkeypatch.setattr(spans, "of", lambda rec: spans.in_window(
        profile(), spans.names()) if "trace" in rec else None)


@pytest.mark.parametrize("name, want", [
    ("plan_ms_per_window", 1e-6 * 35 / 2),
    ("dispatch_ms_per_window", 1e-6 * 90 / 2),
    ("readback_ms", 1e-6 * 30),
])
def test_metric_readers(synthetic, name, want):
    assert harness.reader(name)(record()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW_READERS)
def test_untraced_run_reads_no_span_metric(synthetic, name):
    rec = record()
    del rec["trace"]
    assert harness.reader(name)(rec) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_program_without_span_names_reads_nothing(monkeypatch, name):
    """The parent of this change names no spans: its traced run reads
    nothing and raises nothing."""
    monkeypatch.setattr(spans, "names", lambda: ())
    assert harness.reader(name)(record()) is None


def _trace_at(monkeypatch, tmp_path, data: Path, workload: str) -> None:
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    dest = tmp_path / harness.TRACE_DIR / workload
    dest.mkdir(parents=True)
    shutil.copy(data, dest / data.name)
    spans._read.cache_clear()


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_trace_without_program_spans_reads_nothing(monkeypatch, tmp_path,
                                                     name):
    """``small.xplane.pb`` holds only the benchmark's spans."""
    _trace_at(monkeypatch, tmp_path, DATA / "small.xplane.pb", "c")
    assert harness.reader(name)(record("c")) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_missing_trace_reads_nothing(monkeypatch, tmp_path, name):
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    assert harness.reader(name)(record("c")) is None


def test_readers_of_a_recorded_tpu_trace(monkeypatch, tmp_path):
    """``data/spans.xplane.pb``: ``record_span_trace.py`` on one TPU v5
    lite; three traced windows of ``gap-kron.trickle`` at scale 10."""
    _trace_at(monkeypatch, tmp_path, DATA / "spans.xplane.pb",
              "gap-kron.trickle")
    rec = record("gap-kron.trickle", windows=3)
    got = {name: harness.reader(name)(rec) for name in NEW_READERS}
    assert got == pytest.approx({"plan_ms_per_window": 0.7198863333333333,
                                 "dispatch_ms_per_window": 18.410025,
                                 "readback_ms": 0.9948866666666666})


def test_program_spans_nest_in_the_benchmarks_spans():
    """Every span of a window opens inside the benchmark span of its
    phase, and the query's settle ends before its readback starts."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(DATA / "spans.xplane.pb"))
    found = spans.in_window(pd, spans.names())
    bench = xplane.host_spans(pd, "bench.")
    assert [b[0] for b in bench] == ["bench.ingest_log", "bench.query"] * 3

    def phase(s, e):
        inside = [b[0] for b in bench if b[1] <= s and e <= b[2]]
        assert len(inside) == 1
        return inside[0]

    for name, _, s, e in found:
        assert phase(s, e) == ("bench.query" if name in
                               ("query", "settle", "readback")
                               else "bench.ingest_log"), name
    settles = [f for f in found if f[0] == "settle"]
    readbacks = [f for f in found if f[0] == "readback"]
    assert len(settles) == len(readbacks) == 3
    for st, rb in zip(settles, readbacks):
        assert st[3] <= rb[2]
    # planning and dispatch fit in the benchmark's span around ingest_log
    ingest_ns = sum(e - s for n, s, e in bench if n == "bench.ingest_log")
    spent = sum(spans.durations_ns(found, ("plan_adds", "plan_dels"))) \
        + spans.self_ns(found, ("add_epoch", "del_epoch"))
    assert 0 < spent <= ingest_ns
