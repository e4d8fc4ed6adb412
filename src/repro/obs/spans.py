"""Span tracer (DESIGN.md §10.2): monotonic host-side spans over the
engines' layer boundaries, exportable as Chrome trace-event JSON (loads
directly in Perfetto / chrome://tracing) and as JSONL.

A span wraps one host-side region — slot planning, add/del epoch
dispatch, drain, checkpoint, query and its settle and readback (the
names in ``SPAN_NAMES``) — with ``time.perf_counter_ns`` stamps.  Every
span, enabled or not, opens a ``jax.profiler.TraceAnnotation`` under its
own name, so it lands in profiler traces on the device planes' clock,
and folds its duration into a fixed per-name aggregate (``totals()``:
count, total ns, self ns).  Only an enabled tracer also keeps the
``Span`` list the exports read, so a disabled one holds memory that does
not grow with the stream.  Instant events mark point occurrences
(layout rebuilds).  Nothing here touches device values: the tracer is
pure host bookkeeping, so it obeys the §2.4 no-host-sync rule by
construction (the device work inside a span stays async; a span around
a dispatch measures dispatch wall time, the quantity the ingest loop
actually spends).
"""
from __future__ import annotations

import dataclasses
import json
import time
from contextlib import contextmanager
from typing import Any, Iterator

try:  # TraceAnnotation exists across our supported jax range; stay soft
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover - profiler missing from a slim build
    _TraceAnnotation = None

__all__ = ["SPAN_NAMES", "Span", "SpanTracer", "load_chrome_trace",
           "span_counts_of"]

# every span the engines open, so a reader of a profiler trace can tell
# program spans from the runtime's own host events
SPAN_NAMES = ("plan_adds", "plan_dels", "add_epoch", "del_epoch", "drain",
              "query", "settle", "readback", "checkpoint")


@dataclasses.dataclass
class Span:
    name: str
    t0_ns: int      # perf_counter_ns at entry (exit for instants)
    dur_ns: int     # 0 for instant events
    depth: int      # nesting depth at entry (0 = top-level)
    phase: str      # "X" complete span | "i" instant
    args: dict[str, Any] = dataclasses.field(default_factory=dict)


class SpanTracer:
    def __init__(self, enabled: bool = True, annotate: bool | None = None):
        self.enabled = enabled
        self._annotate = (_TraceAnnotation is not None if annotate is None
                          else bool(annotate) and _TraceAnnotation is not None)
        self._base_ns = time.perf_counter_ns()
        # per open span, the ns its finished child spans covered
        self._child_ns: list[int] = []
        # name -> [count, total ns, self ns]; one entry per span name
        self._totals: dict[str, list[int]] = {}
        self.spans: list[Span] = []   # completion order; enabled only

    @contextmanager
    def span(self, name: str, **args) -> Iterator[None]:
        depth = len(self._child_ns)
        self._child_ns.append(0)
        ann = _TraceAnnotation(name) if self._annotate else None
        t0 = time.perf_counter_ns()
        try:
            if ann is not None:
                with ann:
                    yield
            else:
                yield
        finally:
            dur = time.perf_counter_ns() - t0
            child = self._child_ns.pop()
            if self._child_ns:
                self._child_ns[-1] += dur
            agg = self._totals.setdefault(name, [0, 0, 0])
            agg[0] += 1
            agg[1] += dur
            agg[2] += dur - child
            if self.enabled:
                self.spans.append(Span(name, t0, dur, depth, "X", args))

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        self.spans.append(Span(name, time.perf_counter_ns(), 0,
                               len(self._child_ns), "i", args))

    # --------------------------------------------------------------- readout
    def span_counts(self) -> dict[str, int]:
        """Completed spans + instants by name (the figure the acceptance
        check matches against the engine's epoch/drain/rebuild counters)."""
        counts: dict[str, int] = {}
        for s in self.spans:
            counts[s.name] = counts.get(s.name, 0) + 1
        return counts

    def totals(self) -> dict[str, dict[str, int]]:
        """Completed spans by name, enabled or not: ``count``,
        ``total_ns`` and ``self_ns`` (duration less the part its child
        spans cover)."""
        return {name: {"count": c, "total_ns": t, "self_ns": s}
                for name, (c, t, s) in self._totals.items()}

    # --------------------------------------------------------------- exports
    def to_chrome(self) -> dict[str, Any]:
        """Chrome trace-event JSON object ({"traceEvents": [...]}, ts/dur
        in microseconds) — loads as-is in Perfetto."""
        events = []
        for s in self.spans:
            e: dict[str, Any] = {
                "name": s.name, "cat": "engine", "ph": s.phase,
                "ts": (s.t0_ns - self._base_ns) / 1e3,
                "pid": 0, "tid": 0,
                "args": {"depth": s.depth, **s.args},
            }
            if s.phase == "X":
                e["dur"] = s.dur_ns / 1e3
            else:
                e["s"] = "t"
            events.append(e)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)

    def jsonl_lines(self) -> list[str]:
        return [json.dumps({
            "name": s.name, "ph": s.phase, "depth": s.depth,
            "ts_us": (s.t0_ns - self._base_ns) / 1e3,
            "dur_us": s.dur_ns / 1e3, **({"args": s.args} if s.args else {}),
        }) for s in self.spans]

    def save_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            f.write("\n".join(self.jsonl_lines()) + "\n")


def load_chrome_trace(path: str) -> list[dict[str, Any]]:
    """Load a Chrome trace-event file back to its event list (round-trip
    validation for ``save_chrome`` outputs)."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError(f"{path} is not a Chrome trace-event file "
                         f"(no 'traceEvents' key)")
    return doc["traceEvents"]


def span_counts_of(events: list[dict[str, Any]]) -> dict[str, int]:
    """Event counts by name over a loaded Chrome trace (complete spans and
    instants; metadata events are ignored)."""
    counts: dict[str, int] = {}
    for e in events:
        if e.get("ph") in ("X", "i"):
            counts[e["name"]] = counts.get(e["name"], 0) + 1
    return counts
