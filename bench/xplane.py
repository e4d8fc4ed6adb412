"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
idle share, time per device operation, and idle gaps named by the
benchmark's own host span that was open at the time.

Layout of a TPU v5e trace as JAX 0.9 writes it (read by hand): one plane
per chip, named ``/device:TPU:<i>``, whose line ``XLA Modules`` holds one
event per executed program (``jit_<name>(<fingerprint>)``) and whose line
``XLA Ops`` holds one event per operation of those programs, named by its
whole HLO instruction (``%fusion.22 = f32[...] fusion(...)``); a ``while``
op spans the operations of its loop.  Host threads sit on the plane
``/host:CPU``, where ``jax.profiler.TraceAnnotation`` puts the benchmark's
spans on the thread ``python3``.  All events share one clock, in ns.

Busy time is the union of the ``XLA Ops`` intervals inside the window, per
chip, averaged over the chips that ran anything.  The window runs from the
first benchmark span's start to the last one's end.  Time per operation
counts leaf operations only (not a ``while`` around them), named
``<program>/<op>`` without fingerprints or shapes.
"""
from __future__ import annotations

import collections
import re
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(iv: np.ndarray) -> np.ndarray:
    """Merge ``[start, end)`` rows of ``iv`` (any order) into disjoint
    sorted intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]] if len(iv) else iv


def _op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _leaves(ops: list) -> list:
    """The operations that contain no other (``ops`` sorted by start)."""
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= op[2]]


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def host_spans(pd, prefix: str) -> list[tuple[str, float, float]]:
    spans = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans += [ev for ev in _events(line) if ev[0].startswith(prefix)]
    return sorted(spans, key=lambda s: s[1])


def reduce_profile(pd, span_prefix: str = "bench.") -> dict:
    """Busy, idle and per-operation figures of one trace, in seconds."""
    spans = host_spans(pd, span_prefix)
    if not spans:
        raise ValueError(f"no host span named {span_prefix}* in the trace")
    lo, hi = spans[0][1], max(s[2] for s in spans)
    busy, op_time, gaps = [], collections.Counter(), []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: _events(line) for line in plane.lines}
        ops = sorted((ev for ev in lines.get(OPS_LINE, [])
                      if ev[2] > lo and ev[1] < hi),
                     key=lambda ev: (ev[1], -ev[2]))
        if not ops:
            continue
        mods = sorted(lines.get(MODULES_LINE, []), key=lambda m: m[1])
        mod_start = np.asarray([m[1] for m in mods])
        for name, s, e in _leaves(ops):
            i = int(np.searchsorted(mod_start, s, side="right")) - 1
            mod = _module_name(mods[i][0]) if i >= 0 and mods[i][2] >= s \
                else "?"
            op_time[f"{mod}/{_op_name(name)}"] += (min(e, hi)
                                                   - max(s, lo)) * 1e-9
        merged = union(clip(np.asarray([ev[1:] for ev in ops], np.float64),
                            lo, hi))
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9)
        edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        gaps += [(s, e) for s, e in edges if e > s]
    if not busy:
        raise ValueError("no device operation inside the traced window")
    span_start = np.asarray([s[1] for s in spans])

    def span_at(t: float) -> str:
        i = int(np.searchsorted(span_start, t, side="right")) - 1
        return spans[i][0] if i >= 0 and spans[i][2] >= t else "between"

    named = [(span_at((s + e) / 2), (e - s) * 1e-9) for s, e in gaps]
    idle_by_span = collections.Counter()
    for name, sec in named:
        idle_by_span[name] += sec / len(busy)
    window_s = (hi - lo) * 1e-9
    busy_s = float(np.mean(busy))
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "chips": len(busy),
        "device_ops": [[k, v / len(busy)] for k, v in
                       op_time.most_common(TOP)],
        "idle_gaps": sorted(named, key=lambda g: -g[1])[:TOP],
        "idle_by_span": dict(idle_by_span),
    }


def reduce_file(path: Path, span_prefix: str = "bench.") -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)), span_prefix)
