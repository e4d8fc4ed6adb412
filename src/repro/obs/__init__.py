"""Engine observability layer (DESIGN.md §10): device-side counter
registry, span tracing with Perfetto export, and a per-epoch flight
recorder — shared by both engines via ``StreamEngineBase``.

``EngineObs`` bundles the three pieces behind one facade the engines
drive.  Two parts of it are always on, enabled or not: named spans
(``SPAN_NAMES``), each a ``jax.profiler.TraceAnnotation`` on the
profiler's clock plus a fixed per-name aggregate (``tracer.totals()``:
count, total ns, self ns).  Everything else below is the opt-in record
(``enabled=True``):

  * ``with obs.epoch(kind, **attrs):`` wraps one dispatched epoch — it
    opens a tracer span, and when enabled bumps the matching host
    counter (``add_epoch`` -> ``add_epochs``), appends a flight-recorder
    record with the dispatch wall time, and on an escaping exception
    dumps the flight recorder ONCE before re-raising.
  * ``with obs.tracer.span(name):`` wraps a region that is not an epoch
    (slot planning, a query's settle and readback): a span only.
  * ``obs.note_layout(totals)`` diffs the backend's monotone layout
    totals (``RelaxBackend.layout_counters()``: rebuilds, overflow-lane
    hits) against the last observation, folding the deltas into counters
    and emitting one ``rebuild`` instant event per rebuild — so the span
    stream and the counter registry can never disagree (they are derived
    from the same deltas).  Totals may reset when the "auto" backend
    swaps layouts; negative deltas clamp to zero.
  * ``obs.counters`` / ``obs.tracer`` / ``obs.recorder`` for direct use
    (device-value accumulation, instants, extra records).
  * ``obs.hist_device(name, value)`` / ``obs.hist_cumulative(name, value)``
    / ``obs.hist_host(name, value)`` record histogram samples (§10.6).
    The device variants are ZERO-dispatch on the hot path: they append
    the device value (a per-epoch sample, or the engine's cumulative
    counter whose consecutive diffs are the samples) to a host-side
    list; ``flush_histograms()`` — called by ``metrics_snapshot()`` —
    materializes each list in a few stacked one-hot folds that ride the
    registry's lazy ``+`` and its single ``snapshot()`` device_get.
    Host samples (query latency) fold as numpy vectors immediately;
    device and host counts merge under the same ``hist_*`` name.
  * an optional :class:`~repro.obs.watchdog.Watchdog` (§10.8) armed
    around every ``epoch()`` region: stalls fire a structured warning +
    the one-shot dump from a sampler thread, slow-epoch/frontier
    thresholds are checked synchronously after each epoch.

Disabled (the default) every hook but the spans no-ops.  What the spans
and the opt-in record cost is measured on the chip, end to end, by the
benchmark in ``bench/`` (DESIGN.md §10.4).
"""
from __future__ import annotations

import os
import sys
import time
from contextlib import contextmanager
from typing import Any, Iterator

from repro.obs import hist as hist_mod
from repro.obs.counters import CounterRegistry
from repro.obs.recorder import FlightRecorder
from repro.obs.spans import (SPAN_NAMES, Span, SpanTracer,
                             load_chrome_trace, span_counts_of)
from repro.obs.watchdog import Watchdog, WatchdogConfig

__all__ = [
    "SPAN_NAMES", "CounterRegistry", "EngineObs", "FlightRecorder", "Span", "SpanTracer",
    "Watchdog", "WatchdogConfig", "load_chrome_trace", "out_path_or_exit",
    "span_counts_of", "write_log_jsonl",
]

# span kind -> counter name: every epoch span bumps its counter from the
# SAME code path, which is what makes span counts and counters bit-consistent
_PLURAL = {
    "add_epoch": "add_epochs",
    "del_epoch": "del_epochs",
    "drain": "drains",
    "query": "queries",
    "checkpoint": "checkpoints",
}


class EngineObs:
    def __init__(self, enabled: bool = False, flight_capacity: int = 128,
                 watchdog: WatchdogConfig | None = None):
        self.enabled = bool(enabled)
        self.counters = CounterRegistry(self.enabled)
        self.tracer = SpanTracer(self.enabled)
        self.recorder = FlightRecorder(flight_capacity)
        self.watchdog = (Watchdog(watchdog, self)
                         if (self.enabled and watchdog is not None) else None)
        self._layout_last: dict[str, int] = {}
        # pending device histogram samples (§10.6): plain host lists of
        # device values — appending costs no device dispatch; materialized
        # by flush_histograms() at snapshot time
        self._hist_samples: dict[str, list] = {}
        self._hist_cum: dict[str, list] = {}
        self._hist_base: dict[str, Any] = {}
        self._dumped = False

    @contextmanager
    def epoch(self, kind: str, **attrs) -> Iterator[None]:
        if not self.enabled:
            with self.tracer.span(kind):
                yield
            return
        wd = self.watchdog
        t0 = time.perf_counter()
        if wd is not None:
            wd.arm(kind)
        try:
            with self.tracer.span(kind, **attrs):
                yield
        except BaseException as exc:
            self.recorder.record(kind, error=repr(exc), **attrs)
            self.dump_on_error(exc)
            raise
        finally:
            if wd is not None:
                wd.disarm()
        wall = time.perf_counter() - t0
        self.counters.inc(_PLURAL.get(kind, kind + "s"))
        self.recorder.record(kind, wall_ms=round(wall * 1e3, 3), **attrs)
        # per-kind dispatch wall-time histogram (§10.6): sample count per
        # kind equals the kind's counter by construction
        self.hist_host(f"hist_{kind}_wall_us", wall * 1e6)
        if wd is not None:
            wd.observe(kind, wall, attrs)

    # ------------------------------------------------------------- histograms
    def hist_device(self, name: str, value) -> None:
        """Record one device histogram sample (scalar, or [S] vector -> S
        samples) for counter ``name`` — a host-side list append, zero
        device dispatches on the hot path (§10.6/§10.4); the one-hot folds
        happen in flush_histograms()."""
        if self.enabled:
            self._hist_samples.setdefault(name, []).append(value)

    def hist_cumulative(self, name: str, value) -> None:
        """Record the engine's CUMULATIVE device counter after an epoch;
        consecutive diffs of the recorded series are the per-epoch samples
        (materialized at flush).  For engines whose epochs return updated
        cumulative counters rather than per-epoch stats — appending the
        returned array reference costs nothing."""
        if self.enabled:
            self._hist_cum.setdefault(name, []).append(value)

    def flush_histograms(self) -> None:
        """Materialize the pending sample lists into ``hist_*`` counters:
        a few stacked one-hot scatters per histogram (chunked so a long
        uninspected run cannot build an unboundedly wide stack op), folded
        through the registry's lazy ``+`` — no device_get here; the
        read-back stays ``snapshot()``'s single one."""
        if not self.enabled or not (self._hist_samples or self._hist_cum):
            return
        import jax.numpy as jnp
        CHUNK = 512
        for name, samples in self._hist_samples.items():
            for i in range(0, len(samples), CHUNK):
                vals = jnp.stack(
                    [jnp.asarray(s) for s in samples[i:i + CHUNK]])
                self.counters.add(name, hist_mod.one_hot(vals))
        self._hist_samples.clear()
        for name, series in self._hist_cum.items():
            if not series:
                continue
            base = self._hist_base.get(name)
            if base is None:
                base = jnp.zeros_like(jnp.asarray(series[0]))
            full = [base] + series
            for i in range(0, len(series), CHUNK):
                seg = jnp.stack(
                    [jnp.asarray(s) for s in full[i:i + CHUNK + 1]])
                self.counters.add(name, hist_mod.one_hot(seg[1:] - seg[:-1]))
            self._hist_base[name] = series[-1]
            series.clear()

    def hist_host(self, name: str, value: float) -> None:
        """Fold one host-born histogram sample (e.g. wall-clock latency in
        microseconds) into counter ``name`` as a numpy one-hot vector."""
        if self.enabled:
            self.counters.inc(name, hist_mod.one_hot_np(value))

    def note_layout(self, totals: dict[str, int]) -> None:
        """Fold the backend's monotone layout totals (rebuilds,
        overflow_hits, ...) into counters by delta; one ``rebuild``
        instant event per rebuild delta."""
        if not self.enabled:
            return
        for name, total in totals.items():
            delta = max(0, int(total) - self._layout_last.get(name, 0))
            self._layout_last[name] = int(total)
            if delta == 0:
                continue
            self.counters.inc(name, delta)
            if name == "rebuilds":
                for _ in range(delta):
                    self.tracer.instant("rebuild")

    def dump_on_error(self, exc: BaseException) -> None:
        """One-shot flight-recorder postmortem (nested epochs dump once)."""
        if self._dumped:
            return
        self._dumped = True
        self.recorder.dump(
            header=f"flight recorder postmortem "
                   f"({self.recorder.total} records total): {exc!r}")


# ----------------------------------------------------------- CLI plumbing --
def out_path_or_exit(path: str) -> str:
    """Validate a --trace-out / --log-json destination up front: a missing
    parent directory exits 2 (usage error) before any engine work runs."""
    parent = os.path.dirname(os.path.abspath(path))
    if not os.path.isdir(parent):
        print(f"error: output parent directory does not exist: {parent}",
              file=sys.stderr)
        raise SystemExit(2)
    return path


def _jsonable(v: Any) -> Any:
    import numpy as np
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, np.generic):
        return v.item()
    return v


def write_log_jsonl(engine, path: str) -> None:
    """JSONL export (--log-json): every span line followed by one final
    ``metrics_snapshot`` line — the machine-readable twin of --trace-out."""
    import json
    lines = engine.obs.tracer.jsonl_lines()
    lines.append(json.dumps(
        {"kind": "metrics_snapshot", **_jsonable(engine.metrics_snapshot())}))
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
