"""Percentile, rate and metric arithmetic on hand-made timestamps."""
import math

import pytest

from bench import harness, peaks, stats


def test_pctile_interpolates_between_order_statistics():
    xs = [4.0, 1.0, 3.0, 2.0, 5.0]
    assert stats.pctile(xs, 50) == 3.0
    assert stats.pctile(xs, 95) == pytest.approx(4.8)
    assert stats.pctile(xs, 100) == 5.0
    assert stats.pctile([7.0], 95) == 7.0
    assert math.isnan(stats.pctile([], 50))


def test_rate():
    assert stats.rate(300, 10.0, 12.5) == 120.0
    with pytest.raises(ValueError):
        stats.rate(1, 2.0, 2.0)


def record():
    """Three windows: started at 0, 1 and 3 s; dispatched 0.1 s later;
    answered at 0.5, 2.0 and 3.9 s."""
    starts, answers = [0.0, 1.0, 3.0], [0.5, 2.0, 3.9]
    windows = [{"start": s, "dispatched": s + 0.1, "answered": a,
                "events": 128} for s, a in zip(starts, answers)]
    return {"windows": windows, "t_start": 0.0, "t_end": 3.9, "rounds": 30,
            "setup_s": 42.0, "peak_bytes": 1000,
            "trace": {"busy_s": 3.0, "window_s": 3.9,
                      "idle_share": 0.9 / 3.9}}


@pytest.mark.parametrize("name, want", [
    ("events_per_s", 3 * 128 / 3.9),
    # latencies 0.5, 1.0, 0.9 s: the 95th percentile lies 0.9 of the way
    # from the second (0.9) to the third (1.0) of them
    ("answer_latency_p95_ms", 1e3 * (0.9 + 0.9 * 0.1)),
    ("setup_s", 42.0),
    ("host_ms_per_window", 100.0),
    ("rounds_per_window", 10.0),
    ("device_ms_per_round", 100.0),
    ("device_idle_share", 100 * 0.9 / 3.9),
    ("query_wait_ms.trickle", 1e3 * (0.4 + 0.9 + 0.8) / 3),
])
def test_metric_readers(name, want):
    assert harness.reader(name)(record()) == pytest.approx(want)


def test_untraced_run_reads_no_trace_metric():
    rec = record()
    del rec["trace"]
    assert harness.reader("device_idle_share")(rec) is None
    assert harness.reader("device_ms_per_round")(rec) is None


def test_peaks_are_known_only_for_listed_chips():
    v5e = peaks.of("TPU v5 lite")
    assert v5e["hbm_bytes"] == 16e9 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.of("TPU v9 imaginary")
