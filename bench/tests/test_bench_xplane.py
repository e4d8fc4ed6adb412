"""The trace reduction gives known busy, idle and per-operation figures."""
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import xplane

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def profile():
    """Host spans [0, 100) and [100, 150) ns, then a gap with no span, then
    [200, 300).  The device runs module m1 over [10, 60): a loop holding
    ops a and b; and m2 over [220, 250): op a."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.ingest_log", 0, 100), ev("bench.query", 100, 50),
        ev("other", 150, 50), ev("bench.query", 200, 100)])])
    tpu = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_m1(1)", 10, 50),
                                       ev("jit_m2(2)", 220, 30)]),
        NS(name="XLA Ops", events=[
            ev("%while.1 = (f32[8]) while(...)", 10, 50),
            ev("%a = f32[8] fusion(...)", 20, 20),
            ev("%b = f32[8] fusion(...)", 40, 15),
            ev("%a = f32[8] fusion(...)", 220, 30)]),
    ])
    idle_tpu = NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[])])
    return NS(planes=[host, tpu, idle_tpu, NS(name="/host:metadata",
                                              lines=[])])


def test_union_and_clip():
    iv = np.array([[5, 9], [0, 2], [1, 3], [9, 10]], np.float64)
    assert xplane.union(iv).tolist() == [[0, 3], [5, 10]]
    assert xplane.clip(iv, 1, 6).tolist() == [[5, 6], [1, 2], [1, 3]]
    assert len(xplane.union(np.zeros((0, 2)))) == 0


def test_reduction_of_a_known_profile():
    r = xplane.reduce_profile(profile())
    assert r["window_s"] == pytest.approx(300e-9)
    # busy: [10, 60) and [220, 250); the chip that ran nothing is left out
    assert r["chips"] == 1
    assert r["busy_s"] == pytest.approx(80e-9)
    assert r["idle_share"] == pytest.approx(1 - 80 / 300)
    ops = dict((k, v) for k, v in r["device_ops"])
    # leaf operations only, named without fingerprints and shapes
    assert ops == pytest.approx({"jit_m1/a": 20e-9, "jit_m1/b": 15e-9,
                                 "jit_m2/a": 30e-9})
    # gaps [0,10) ingest, [60,220) mid-point 140 in the query span,
    # [250,300) query
    names, secs = zip(*r["idle_gaps"])
    assert names == ("bench.query", "bench.query", "bench.ingest_log")
    assert secs == pytest.approx((160e-9, 50e-9, 10e-9))
    assert r["idle_by_span"] == pytest.approx(
        {"bench.query": 210e-9, "bench.ingest_log": 10e-9})


def test_reduction_needs_spans_and_device_work():
    p = profile()
    p.planes[0].lines[0].events = []
    with pytest.raises(ValueError):
        xplane.reduce_profile(p)
    p = profile()
    p.planes[1].lines[1].events = []
    with pytest.raises(ValueError):
        xplane.reduce_profile(p)


def test_reduction_of_a_recorded_tpu_trace():
    """``data/small.xplane.pb``: ``record_trace.py`` on one TPU v5 lite;
    three calls of a 2048x2048 matmul program, each followed by a 20 ms
    host wait inside a ``bench.query`` span."""
    r = xplane.reduce_file(DATA)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.065422465)
    assert r["busy_s"] == pytest.approx(0.000228105)
    assert r["idle_share"] == pytest.approx(1 - 0.000228105 / 0.065422465)
    ops = dict((k, v) for k, v in r["device_ops"])
    assert ops == pytest.approx({"jit_step/fusion": 0.000182988,
                                 "jit_step/copy-done": 4.509e-05,
                                 "jit_step/copy-start": 2.7e-08})
    assert r["idle_by_span"] == pytest.approx(
        {"bench.query": 0.065422465 - 0.000228105})
    names, secs = zip(*r["idle_gaps"][:3])
    assert names == ("bench.query",) * 3
    assert all(0.02 < s < 0.025 for s in secs)
