#!/usr/bin/env python3
"""Bring-up smoke test: the dynamic-SSSP service on a TPU at Graph500 scale.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py                 # one chip
    python3 chip_smoke.py --four-chips    # the sharded engine on four chips

The graph is the Graph500 specification's R-MAT graph at scale 20,
edgefactor 16 (N = 1,048,576 vertices; ``graphs/generators.py:rmat``),
vertex ids randomly relabeled as Graph500 relabels them, weights uniform
in (0, 4], generated from ``--seed``.  It is loaded in
chunks through ``ingest_log``; then a sliding-window churn replays
``--churn`` DELs of the oldest edges and as many ADDs of new R-MAT edges,
with a QUERY every ``--query-every`` events.  Each window's DELs settle in
one epoch (``batch_deletions=True``, DESIGN.md §3).

One chip runs two phases through ``repro.make_engine``:

  phase 2  the default engine (segment backend, rounds schedule,
           frontier-compacted waves); its final query is also checked
           against the Dijkstra oracle (``core/oracle.py``);
  phase 3  ``relax_backend="sliced"`` on ``wave_schedule="buckets"``, the
           hub-aware layout built for power-law graphs (DESIGN.md §6/§9).

Every answered query is checked with an O(E) numpy certificate on the live
edge set, independently of JAX.  ``--four-chips`` runs only the sharded
engine (``partitions=4``, allgather exchange, default backend) and the
single-device engine on one of the same four chips, and checks that their
``(dist, parent)`` agree bit for bit at every query.

Timings, events/s and peak device memory printed on the way are bring-up
observations, not benchmark numbers.  The last line of stdout is
``{"ok": true, "device": {...}}``; any failure exits non-zero without it,
and so does a run whose first JAX device is not a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import events as ev  # noqa: E402
from repro.core.oracle import dijkstra  # noqa: E402
from repro.graphs.generators import rmat  # noqa: E402

LOAD_CHUNK = 1 << 22
# a window's DELs settle in one epoch (DESIGN.md §3); one epoch per DEL
# would make every tree-edge deletion its own pass over the 2^24-slot pool
ENGINE_KNOBS = {"batch_deletions": True}


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------- workload --
@dataclasses.dataclass(frozen=True)
class Workload:
    """The base graph in insertion order followed by the churn's new edges:
    after ``d`` DELs and ``a`` ADDs the live set is ``edges[d : E0 + a]``."""

    n: int
    src: np.ndarray     # i64[E0 + churn]
    dst: np.ndarray
    w: np.ndarray       # f32
    e0: int             # base graph edges
    churn: int
    query_every: int
    source: int

    def window(self, queries_done: int) -> slice:
        """Live edges at the ``queries_done``-th churn query (0 = after the
        load): each window deletes then adds ``query_every // 2`` edges."""
        k = min(queries_done * (self.query_every // 2), self.churn)
        return slice(k, self.e0 + k)

    def live(self, queries_done: int) -> tuple[np.ndarray, ...]:
        sl = self.window(queries_done)
        return self.src[sl], self.dst[sl], self.w[sl]


def make_workload(scale: int, edgefactor: int, churn: int, query_every: int,
                  seed: int) -> Workload:
    n, src, dst, w = rmat(scale, edgefactor, seed=seed)
    # new edges: further R-MAT draws absent from the base graph
    _, s2, d2, w2 = rmat(scale, max(1, -(-4 * churn // n)), seed=seed + 1)
    fresh = ~np.isin(s2 * n + d2, src * n + dst)
    s2, d2, w2 = s2[fresh][:churn], d2[fresh][:churn], w2[fresh][:churn]
    assert len(s2) == churn, f"only {len(s2)} new R-MAT edges for {churn}"
    # Graph500 relabels the vertices with a random permutation, so that
    # ids carry no degree locality (R-MAT's hubs sit at low ids otherwise,
    # and a block partition would put most edges on one shard)
    perm = np.random.default_rng(seed + 2).permutation(n)
    src, dst, s2, d2 = perm[src], perm[dst], perm[s2], perm[d2]
    # the source is the largest out-degree vertex (a Graph500 search key
    # must have an edge; the hub's tree spans most of the graph)
    source = int(np.argmax(np.bincount(src, minlength=n)))
    return Workload(n, np.concatenate([src, s2]), np.concatenate([dst, d2]),
                    np.concatenate([w, w2]).astype(np.float32), len(src),
                    churn, query_every, source)


def load_chunks(wl: Workload):
    """The base graph as chunked ADD logs; a QUERY closes the last one."""
    for a in range(0, wl.e0, LOAD_CHUNK):
        b = min(a + LOAD_CHUNK, wl.e0)
        chunk = ev.adds(wl.src[a:b], wl.dst[a:b], wl.w[a:b])
        yield (ev.EventLog.concatenate([chunk, ev.query_marker()])
               if b == wl.e0 else chunk)


def churn_log(wl: Workload) -> ev.EventLog:
    """Sliding window: per window DEL the oldest ``half`` live edges, ADD
    ``half`` new ones, then QUERY."""
    half = wl.query_every // 2
    out = []
    for k in range(0, wl.churn, half):
        m = min(half, wl.churn - k)
        out.append(ev.dels(wl.src[k:k + m], wl.dst[k:k + m]))
        a = wl.e0 + k
        out.append(ev.adds(wl.src[a:a + m], wl.dst[a:a + m],
                           wl.w[a:a + m]))
        out.append(ev.query_marker())
    return ev.EventLog.concatenate(out)


# ---------------------------------------------------------- certificate --
def certify(n: int, src: np.ndarray, dst: np.ndarray, w: np.ndarray,
            source: int, dist: np.ndarray, parent: np.ndarray,
            live: slice = slice(None), order: np.ndarray | None = None
            ) -> int:
    """O(E) numpy proof that (dist, parent) is a shortest-path tree of the
    live edges ``[live]`` of (src, dst, w), in the engine's own f32
    arithmetic; returns the number of reached vertices.  ``order`` is
    ``edge_order(n, src, dst)``, passed in when many queries share the
    arrays.  Tight parent edges with positive weights make dist strictly
    increase along parent pointers, so the tree is acyclic."""
    dist = np.asarray(dist, np.float32)
    parent = np.asarray(parent, np.int64)
    assert dist.shape == (n,) and parent.shape == (n,)
    assert dist[source] == 0 and parent[source] == -1, "source state"
    lo, hi, _ = live.indices(len(src))
    # no live edge can still relax
    ls, ld = src[lo:hi], dst[lo:hi]
    bad = dist[ls] + w[lo:hi] < dist[ld]
    assert not bad.any(), (
        f"{int(bad.sum())} live edges still relax, e.g. "
        f"{ls[bad][:3]}->{ld[bad][:3]}")
    reached = np.isfinite(dist)
    assert np.all(parent[~reached] == -1), "unreached vertex with a parent"
    assert np.all(dist[~reached] == np.inf)
    # every reached vertex but the source has a live, tight parent edge
    v = np.nonzero(reached)[0]
    v = v[v != source]
    p = parent[v]
    assert np.all((p >= 0) & (p < n)), "reached vertex without a parent"
    if order is None:
        order = edge_order(n, src, dst)
    skey = dst[order] * n + src[order]
    want = v * n + p
    pos = np.minimum(np.searchsorted(skey, want), len(skey) - 1)
    e = order[pos]
    live_e = (skey[pos] == want) & (e >= lo) & (e < hi)
    assert live_e.all(), (f"{int((~live_e).sum())} parent edges are not "
                          f"live, e.g. {p[~live_e][:3]}->{v[~live_e][:3]}")
    tight = dist[p] + w[e] == dist[v]
    assert tight.all(), (f"{int((~tight).sum())} parent edges are not "
                         f"tight, e.g. {p[~tight][:3]}->{v[~tight][:3]}")
    return int(reached.sum())


def edge_order(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Edge indices sorted by (dst, src): the parent-edge lookup of
    ``certify`` (keys are unique: the workload has no parallel edges)."""
    return np.argsort(dst * n + src, kind="stable")


def check_dijkstra(wl: Workload, q: int, dist: np.ndarray) -> float:
    """The ``q``-th query against ``core/oracle.py:dijkstra``; returns the
    seconds the oracle took."""
    t0 = time.perf_counter()
    s, d, w = wl.live(q)
    ref, _ = dijkstra(wl.n, s, d, w, wl.source)
    got = np.asarray(dist, np.float64)
    assert np.array_equal(np.isinf(ref), np.isinf(got)), "reach differs"
    fin = np.isfinite(ref)
    err = np.abs(ref[fin] - got[fin])
    assert np.all(err <= 1e-4 + 1e-5 * np.abs(ref[fin])), (
        f"max |dijkstra - engine| = {err.max()}")
    return time.perf_counter() - t0


# ---------------------------------------------------------------- timing --
_COMPILE_S = [0.0]


def _on_event(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def _block(engine) -> None:
    import jax
    state = getattr(engine, "state", None)
    jax.block_until_ready(state.sssp if state is not None
                          else (engine.dist, engine.parent))


def replay(engine, wl: Workload, label: str) -> list:
    """Load + churn through ``ingest_log``; returns the query results."""
    t0, c0 = time.perf_counter(), _COMPILE_S[0]

    def progress(res) -> None:
        log(f"[{label}] query answered at {time.perf_counter() - t0:.3f} s")

    results = engine.ingest_log(load_chunks(wl), on_query=progress)
    _block(engine)
    t1, c1 = time.perf_counter(), _COMPILE_S[0]
    results += engine.ingest_log(churn_log(wl), on_query=progress)
    _block(engine)
    t2, c2 = time.perf_counter(), _COMPILE_S[0]
    events = 2 * wl.churn
    log(f"[{label}] load: {wl.e0} ADDs in {t1 - t0:.3f} s "
        f"(of which compile {c1 - c0:.3f} s)")
    log(f"[{label}] replay: {events} events, {len(results) - 1} queries in "
        f"{t2 - t1:.3f} s (of which compile {c2 - c1:.3f} s); "
        f"{events / (t2 - t1):.1f} events/s")
    log(f"[{label}] rounds={int(np.sum(engine.n_rounds))} "
        f"messages={int(np.sum(engine.n_messages))} "
        f"epochs={engine.n_epochs}")
    return results


def certify_all(wl: Workload, results: list, label: str) -> None:
    t0 = time.perf_counter()
    order = edge_order(wl.n, wl.src, wl.dst)
    for q, res in enumerate(results):
        reached = certify(wl.n, wl.src, wl.dst, wl.w, wl.source, res.dist,
                          res.parent, live=wl.window(q), order=order)
        log(f"[{label}] query {q}: certificate ok, {reached} reached, "
            f"readback {res.latency_s * 1e3:.3f} ms")
    log(f"[{label}] {len(results)} queries certified in "
        f"{time.perf_counter() - t0:.3f} s")


def peak_bytes(devices) -> list[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in devices]


# ---------------------------------------------------------------- phases --
def run_one_chip(wl: Workload, devices) -> None:
    from repro import make_engine

    cap = 1 << int(np.ceil(np.log2(wl.e0 + wl.churn)))
    log(f"edge pool: {cap} slots")
    with ThreadPoolExecutor(1) as host:
        oracle = None
        for label, knobs in (
                ("phase2 segment/rounds", {}),
                ("phase3 sliced/buckets", {"relax_backend": "sliced",
                                           "wave_schedule": "buckets"})):
            engine = make_engine(num_vertices=wl.n, edge_capacity=cap,
                                 source=wl.source, **ENGINE_KNOBS, **knobs)
            results = replay(engine, wl, label)
            log(f"[{label}] peak_bytes_in_use={peak_bytes(devices)[0]}")
            certify_all(wl, results, label)
            if oracle is None:
                # the pure-Python oracle runs on the host while phase 3
                # keeps the chip busy (its host timings share the CPU)
                oracle = host.submit(check_dijkstra, wl, len(results) - 1,
                                     results[-1].dist)
            del engine, results
        log(f"[phase2 segment/rounds] final query matches Dijkstra "
            f"({oracle.result():.3f} s)")


def run_four_chips(wl: Workload, devices) -> None:
    import jax
    from repro import make_engine

    P = 4
    # ownership is by destination block (dst // (N / P)): size every
    # partition's pool for the fullest one
    owner = wl.dst // (wl.n // P)
    need = max(int(np.sum(owner[:wl.e0] == p)) for p in range(P)) + wl.churn
    epp = 1 << int(np.ceil(np.log2(need)))
    cap = 1 << int(np.ceil(np.log2(wl.e0 + wl.churn)))
    log(f"sharded edge pool: {epp} slots per partition x {P}; single-device "
        f"pool: {cap} slots on device {P - 1}")
    one = f"single on device {P - 1}"

    def run_single():
        with jax.default_device(devices[P - 1]):
            eng = make_engine(num_vertices=wl.n, edge_capacity=cap,
                              source=wl.source, **ENGINE_KNOBS)
            return eng, replay(eng, wl, one)

    # the two engines run concurrently (the single one in a host thread):
    # their host planning and compiles overlap, device P-1 serves both, and
    # the compile seconds each prints are the process's
    with ThreadPoolExecutor(1) as host:
        single_run = host.submit(run_single)
        sharded = make_engine(num_vertices=wl.n, edge_capacity=P * epp,
                              source=wl.source, partitions=P, **ENGINE_KNOBS)
        res_sh = replay(sharded, wl, "sharded P=4")
        single, res_one = single_run.result()
    assert single.state.sssp.dist.devices() == {devices[P - 1]}
    certify_all(wl, res_one, one)
    assert len(res_sh) == len(res_one)
    for q, (a, b) in enumerate(zip(res_sh, res_one)):
        assert np.array_equal(a.dist, b.dist), f"query {q}: dist differs"
        assert np.array_equal(a.parent, b.parent), \
            f"query {q}: parent differs"
    log(f"sharded P=4 == single-device: (dist, parent) bit-identical at "
        f"all {len(res_sh)} queries")
    for i, b in enumerate(peak_bytes(devices[:P])):
        log(f"device {i}: peak_bytes_in_use={b}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on four chips against "
                         "the single-device engine")
    ap.add_argument("--scale", type=int, default=20)
    ap.add_argument("--edgefactor", type=int, default=16)
    ap.add_argument("--churn", type=int, default=65536)
    ap.add_argument("--query-every", type=int, default=16384)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    log(f"platform={d0.platform} device_kind={d0.device_kind} "
        f"device_count={len(devices)}")
    if d0.platform != "tpu":
        log(f"chip_smoke: no TPU (JAX platform {d0.platform!r})")
        return 2
    if args.four_chips and len(devices) < 4:
        log(f"chip_smoke: --four-chips needs 4 devices, have {len(devices)}")
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_event)

    t0 = time.perf_counter()
    wl = make_workload(args.scale, args.edgefactor, args.churn,
                       args.query_every, args.seed)
    log(f"R-MAT scale {args.scale} edgefactor {args.edgefactor}: N={wl.n} "
        f"E={wl.e0} (+{wl.churn} churn ADDs), source={wl.source}, "
        f"generated in {time.perf_counter() - t0:.3f} s")
    if args.four_chips:
        run_four_chips(wl, devices)
    else:
        run_one_chip(wl, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
