"""The program's own spans in a traced run.

The engines open a ``jax.profiler.TraceAnnotation`` under each name in
``repro.obs.SPAN_NAMES``, so the profiler records them on the host plane
beside the benchmark's ``bench.*`` spans, on the device planes' clock.
``of(rec)`` reads them back from the run's trace, inside the window that
the benchmark's spans bound (``xplane.reduce_profile``'s window), and
gives None where there is nothing to read: an untraced run, or a program
that names no spans.
"""
from __future__ import annotations

import functools
from pathlib import Path

import numpy as np

from bench import harness, xplane


def names() -> tuple[str, ...]:
    """The program's span names; none where it exports no such list."""
    try:
        from repro.obs import SPAN_NAMES
    except ImportError:
        return ()
    return tuple(SPAN_NAMES)


def in_window(pd, wanted, prefix: str = "bench.") -> list[tuple]:
    """``(name, line, start_ns, end_ns)`` of each host span named in
    ``wanted`` that lies inside the window, by start; ``line`` tells the
    host threads apart."""
    bench = xplane.host_spans(pd, prefix)
    if not bench:
        return []
    lo, hi = bench[0][1], max(s[2] for s in bench)
    found, line_no = [], 0
    for plane in pd.planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            found += [(name, line_no, s, e) for name, s, e
                      in xplane._events(line)
                      if name in wanted and s >= lo and e <= hi]
            line_no += 1
    return sorted(found, key=lambda sp: sp[2])


@functools.lru_cache(maxsize=2)
def _read(path: str, wanted: tuple[str, ...]) -> tuple:
    from jax.profiler import ProfileData
    return tuple(in_window(ProfileData.from_file(path), wanted))


def of(rec: dict) -> list[tuple] | None:
    """The program spans of a traced run's window; None where the run was
    not traced, or its trace holds no program span."""
    wanted = names()
    if "trace" not in rec or not wanted:
        return None
    try:
        path = xplane.find_xplane(
            harness.ROOT / harness.TRACE_DIR / rec["workload"])
    except FileNotFoundError:
        return None
    return list(_read(str(Path(path).resolve()), wanted)) or None


def durations_ns(spans: list[tuple], which) -> list[float]:
    return [e - s for name, _, s, e in spans if name in which]


def self_ns(spans: list[tuple], which) -> float:
    """Summed self time of the spans named in ``which``: each one's
    duration less the part of it that the program spans nested in it, on
    its own thread, cover."""
    total = 0.0
    for i, (name, line, s, e) in enumerate(spans):
        if name not in which:
            continue
        inner = np.asarray([(s2, e2) for j, (_, l2, s2, e2)
                            in enumerate(spans)
                            if j != i and l2 == line and s <= s2
                            and e2 <= e], np.float64).reshape(-1, 2)
        covered = xplane.union(inner)
        total += (e - s) - float(np.sum(covered[:, 1] - covered[:, 0]))
    return total
