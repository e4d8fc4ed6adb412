"""Shared event-stream plumbing for the dynamic engines.

Both the single-device ``SSSPDelEngine`` (core/engine.py) and the sharded
``ShardedSSSPDelEngine`` (core/dist_engine.py) are host orchestrators over
jitted device epochs that consume the same ``EventLog`` stream.  Everything
that is *stream* logic rather than *epoch* logic lives here:

  * the driver loop (``ingest_log``) that coalesces the log into runs and
    dispatches ADD/DEL batches and QUERY markers;
  * the ``QueryResult`` record returned at every QUERY marker, with its
    wall-clock ``latency_s`` timed HERE (the template ``query()`` settles
    the dispatched epochs, then reads back the engine's ``_device_pair``)
    so both engines measure result latency identically — the serving
    harness's latency metric (DESIGN.md §8);
  * multi-source lane routing (DESIGN.md §8): engines constructed with
    ``sources=(s0, s1, ...)`` maintain stacked ``[S, N]`` dist/parent state;
    ``query(source=s)`` reads back ONE lane, ``query()`` the full stack,
    and QUERY stream markers carry their requested source;
  * lazy device-scalar stats counters (DESIGN.md §2.4: the ingest loop never
    blocks on a device value — rounds/messages accumulate on device and are
    only read back inside ``query()``; in batched mode they are ``[S]``
    device vectors, one independent counter per source), with the DEL
    epochs' share of the rounds kept apart (``rounds_by_kind``);
  * the paper's §5.4 predecessor-stability metric.

Subclasses implement ``_ingest_adds`` / ``_ingest_dels`` / ``_device_pair``
(and ``_host_view`` where host ids differ) and keep ``_dev_rounds`` /
``_dev_del_rounds`` / ``_dev_messages`` as device scalars (or ``[S]``
vectors).  Layout-specific work lives one layer down, behind the
``RelaxBackend`` protocol (core/backends/, DESIGN.md §7): the single-device
engine folds its epochs' stats into these counters in one jitted dispatch
per epoch; the sharded engine threads the same counters through its
shard_map epochs as replicated device scalars.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as ev
from repro.obs import EngineObs, WatchdogConfig
from repro.obs import hist as hist_mod


@dataclasses.dataclass
class QueryResult:
    dist: np.ndarray      # f32[N] (lane or single-source) or f32[S, N]
    parent: np.ndarray    # i32 of the same shape
    # wall-clock query latency (timed in query()): the wait for the
    # dispatched epochs (any pending drain included), then the readback
    latency_s: float
    epoch_stats: dict[str, Any]
    source: int | None = None   # the lane's source for a routed query


class StreamEngineBase:
    """Host-side driver over jitted device epochs; subclasses own the state."""

    def __init__(self, sources: tuple[int, ...] | None = None, *,
                 observability: bool = False,
                 flight_capacity: int = 128,
                 watchdog: "WatchdogConfig | None" = None) -> None:
        # observability layer (DESIGN.md §10): counter registry + span
        # tracer + flight recorder + optional stall watchdog; every hook
        # no-ops when disabled
        self.obs = EngineObs(enabled=observability,
                             flight_capacity=flight_capacity,
                             watchdog=watchdog)
        # Batched multi-source serving mode (DESIGN.md §8): ``sources`` is
        # the static tuple of maintained sources; None = classic
        # single-source engine.  ``_lane_of`` routes query sources to rows
        # of the stacked [S, N] state.
        self.sources = tuple(int(s) for s in sources) if sources else None
        if self.sources is not None:
            if len(set(self.sources)) != len(self.sources):
                raise ValueError(f"duplicate sources: {self.sources}")
            self._lane_of = {s: i for i, s in enumerate(self.sources)}
        else:
            self._lane_of = {}
        # batch counters (host-side; no device source)
        self.n_epochs = 0
        self.n_adds = 0
        self.n_dels = 0
        # round/message counters live ON DEVICE; read back lazily at query()
        # (batched engines keep one independent [S] counter per source);
        # _dev_del_rounds is the DEL epochs' share of _dev_rounds
        if self.sources is not None:
            self._dev_rounds = jnp.zeros((len(self.sources),), jnp.int32)
            self._dev_messages = jnp.zeros((len(self.sources),), jnp.int32)
        else:
            self._dev_rounds = jnp.int32(0)
            self._dev_messages = jnp.int32(0)
        self._dev_del_rounds = self._dev_rounds
        # previous parent snapshot per stability scope (None = full state,
        # a source id = that routed lane) — two routed [N] snapshots from
        # DIFFERENT lanes must never be compared against each other
        self._last_parent: dict[int | None, np.ndarray] = {}

    # --------------------------------------------------------- lazy counters
    @staticmethod
    def _counter(x) -> int | np.ndarray:
        got = jax.device_get(x)
        return int(got) if np.ndim(got) == 0 else np.asarray(got)

    @property
    def n_rounds(self) -> int | np.ndarray:
        """BSP rounds so far — an int, or i32[S] per source when batched."""
        return self._counter(self._dev_rounds)

    @property
    def n_messages(self) -> int | np.ndarray:
        return self._counter(self._dev_messages)

    @property
    def rounds_by_kind(self) -> dict[str, int | np.ndarray]:
        """``n_rounds`` split by epoch kind, summing to it exactly:
        ``del`` counts the invalidation and recompute rounds of DEL
        epochs, ``add`` every other round (ADD relax epochs, and the
        drains of the bucketed schedule, which also settle what its
        deletions left pending).  Per source when batched."""
        total, dels = jax.device_get((self._dev_rounds, self._dev_del_rounds))
        return {"add": self._counter(total - dels),
                "del": self._counter(dels)}

    def _stream_stats(self) -> dict[str, Any]:
        return {
            "epochs": self.n_epochs, "rounds": self.n_rounds,
            "messages": self.n_messages, "adds": self.n_adds,
            "dels": self.n_dels,
        }

    # ------------------------------------------------------------- interface
    def _deletion_groups(self, batch: ev.EventBatch
                         ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Paper-faithful: one stop-the-world epoch PER deletion;
        ``batch_deletions=True`` coalesces the whole run into one epoch
        (union of affected subtrees — DESIGN.md §3).  Both engines must
        group identically or the equivalence contract breaks."""
        if self.cfg.batch_deletions:
            return [(batch.src, batch.dst)]
        return [(batch.src[i:i + 1], batch.dst[i:i + 1])
                for i in range(len(batch.src))]

    def _ingest_adds(self, batch: ev.EventBatch) -> None:
        raise NotImplementedError

    def _ingest_dels(self, batch: ev.EventBatch) -> None:
        raise NotImplementedError

    def _device_pair(self, lane: int | None) -> tuple[jax.Array, jax.Array]:
        """The (dist, parent) device arrays a query returns — one lane of
        the stacked state when ``lane`` is given, everything otherwise —
        after settling any deferred work (the bucketed drain)."""
        raise NotImplementedError

    def _host_view(self, dist: np.ndarray, parent: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """The read-back pair in the caller's vertex ids (identity here)."""
        return dist, parent

    def _obs_pre_snapshot(self) -> None:
        """Engine-specific lazy folds right before the registry snapshot
        (metrics_snapshot only) — e.g. the sharded engine's per-partition
        touched-vertex attribution: per-READOUT device work, never
        per-epoch (§10.4)."""

    # ----------------------------------------------------------------- query
    def serves(self, source: int) -> bool:
        """Whether a routed ``query(source=...)`` would be answered from a
        dedicated lane/tree of this engine."""
        if self.sources is not None:
            return source in self._lane_of
        return int(source) == int(self.cfg.source)

    def route_of(self, query_source: int) -> int | None:
        """THE stream-marker routing policy, shared by ``ingest_log`` and
        the trace replayer (repro/serving/replay.py) so the two can never
        drift: a marker's source routes to its lane on a batched engine
        that serves it; everything else (``-1``, unserved sources,
        single-source engines) reads the full state."""
        if (query_source >= 0 and self.sources is not None
                and self.serves(query_source)):
            return query_source
        return None

    def lane_of(self, source: int) -> int:
        """Row of the stacked [S, N] state serving ``source``."""
        if self.sources is None:
            raise ValueError("lane_of() on a single-source engine; construct "
                             "with sources=(...) for batched serving")
        if source not in self._lane_of:
            raise ValueError(f"source {source} is not served by this engine "
                             f"(sources={self.sources})")
        return self._lane_of[source]

    def query(self, source: int | None = None) -> QueryResult:
        """State collection (paper §3): every batch was dispatched to run
        to convergence, so the query waits for the dispatched epochs (the
        ``settle`` span; a bucketed engine drains first), then reads the
        pair back (``readback``) — the whole timed here as the result
        latency (DESIGN.md §8).

        ``source`` routes the query to one maintained tree of a batched
        engine (only that lane is read back); a single-source engine
        accepts its own source or None.
        """
        lane: int | None = None
        if source is not None:
            if self.sources is not None:
                lane = self.lane_of(int(source))
            elif int(source) != int(self.cfg.source):
                raise ValueError(
                    f"source {source} is not served by this engine "
                    f"(source={self.cfg.source})")
        t0 = time.perf_counter()
        # the query span NESTS any drain span _device_pair dispatches — the
        # bucketed engines settle pending work inside the query (§10.2)
        with self.obs.epoch("query", lane=lane):
            dist, parent = self._device_pair(lane)
            with self.obs.tracer.span("settle"):
                jax.block_until_ready((dist, parent))
            with self.obs.tracer.span("readback"):
                dist = np.asarray(jax.device_get(dist))
                parent = np.asarray(jax.device_get(parent))
            dist, parent = self._host_view(dist, parent)
        dt = time.perf_counter() - t0
        if self.obs.enabled:
            # result-latency histogram in microseconds (§10.6): total
            # sample count == the ``queries`` counter by construction
            us = dt * 1e6
            self.obs.hist_host("hist_latency_us", us)
            if lane is not None:
                # per-lane attribution (§10.5): routed queries tally the
                # lane and fold the sample into an [S, B] per-lane row
                S = len(self.sources)
                one = np.zeros(S, np.int64)
                one[lane] = 1
                self.obs.counters.inc("queries_per_lane", one, dim="lane")
                row = np.zeros((S, hist_mod.NUM_BUCKETS), np.int64)
                row[lane, hist_mod.bucket_index_np(us)] = 1
                self.obs.counters.inc("hist_latency_us_per_lane", row,
                                      dim="lane")
        return QueryResult(dist=dist, parent=parent, latency_s=dt,
                           epoch_stats=self._stream_stats(),
                           source=None if source is None else int(source))

    # ---------------------------------------------------------------- stream
    def ingest_log(self, log: "ev.EventLog | Iterable[ev.EventLog]",
                   on_query: Callable[[QueryResult], None] | None = None
                   ) -> list[QueryResult]:
        """Drive the engine over an event log; returns query results.

        ``log`` may be a single ``EventLog`` or any iterable of them (e.g.
        a generator lowering ``TraceReader.chunks()``): chunks are ingested
        in order with only the current chunk resident, so paper-scale
        streams cost O(chunk) host memory here (DESIGN.md §11).  A run
        split across a chunk boundary ingests as two batches — converged
        results are identical, epoch counters may differ.

        QUERY markers carrying a source (events.query_marker(source=s)) are
        routed to that lane on a batched engine; markers with ``-1`` (and
        every marker on a single-source engine) read the full state.
        """
        chunks = [log] if isinstance(log, ev.EventLog) else log
        results: list[QueryResult] = []
        for chunk in chunks:
            for batch in chunk.runs():
                if batch.kind == ev.ADD:
                    self._ingest_adds(batch)
                elif batch.kind == ev.DEL:
                    self._ingest_dels(batch)
                else:
                    res = self.query(source=self.route_of(batch.query_source))
                    results.append(res)
                    if on_query is not None:
                        on_query(res)
        return results

    # ---------------------------------------------------------- observability
    def metrics_snapshot(self) -> dict[str, Any]:
        """One-stop observable state (DESIGN.md §10): the stream counters,
        rounds/messages drained from the SAME ``_dev_rounds`` /
        ``_dev_messages`` device scalars as ``n_rounds`` / ``n_messages``
        (bit-identical by construction), the counter registry's snapshot
        (its only device_get), histogram summaries + dimension attribution
        derived from that SAME snapshot (§10.5/§10.6 — no second
        device_get), span counts, and flight-recorder occupancy.  Consumed
        by ``ServingReport``, both examples, the exporters (§10.7) and the
        benches.  An armed watchdog reviews the snapshot for divergence;
        its findings land in the *next* snapshot's counters (§10.8).
        ``span_totals`` is the tracer's always-on per-name aggregate,
        and ``rounds_by_kind`` splits ``rounds`` by epoch kind."""
        if self.obs.enabled:
            self._obs_pre_snapshot()
            self.obs.flush_histograms()
        counters = self.obs.counters.snapshot()
        snap = {
            "epochs": self.n_epochs, "adds": self.n_adds,
            "dels": self.n_dels, "rounds": self.n_rounds,
            "rounds_by_kind": self.rounds_by_kind,
            "messages": self.n_messages,
            "counters": counters,
            "histograms": hist_mod.summarize(counters),
            "attribution": self.obs.counters.attribution(counters),
            "spans": self.obs.tracer.span_counts(),
            "span_totals": self.obs.tracer.totals(),
            "flight": {"records": self.obs.recorder.total,
                       "capacity": self.obs.recorder.capacity},
        }
        if self.obs.watchdog is not None:
            self.obs.watchdog.review(counters)
        return snap

    def dump_flight_recorder(self, file=None) -> str:
        """Postmortem: write the flight-recorder ring (most recent epoch
        records) as JSONL to ``file`` (default stderr) and return it."""
        return self.obs.recorder.dump(
            file=file, header=f"flight recorder "
            f"({self.obs.recorder.total} records total)")

    # ------------------------------------------------------------- stability
    def stability_vs_prev(self, parent: np.ndarray,
                          source: int | None = None) -> float:
        """Paper §5.4: fraction of vertices whose predecessor is unchanged
        (over vertices present in both results).  Shape-agnostic: a batched
        [S, N] parent stack scores all lanes at once.  ``source`` scopes
        the comparison: pass ``QueryResult.source`` so a routed lane's
        snapshot is only ever compared against the SAME lane's previous
        snapshot (the first observation of each scope scores 1.0)."""
        key = None if source is None else int(source)
        prev = self._last_parent.get(key)
        self._last_parent[key] = parent.copy()
        if prev is None or prev.shape != parent.shape:
            return 1.0
        both = (prev >= 0) & (parent >= 0)
        return float(np.mean(prev[both] == parent[both])) if both.any() else 1.0
