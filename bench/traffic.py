"""The one traffic generator: a closed-loop sliding window of edge churn.

A mix file (``bench/traffic/<mix>.json``) gives the window's shape:

    del_edges       edges deleted per window, the oldest live ones first
    add_edges       edges added per window, the next ones in stream order
    warmup_windows  windows run before the timed window, of the same shape
    fresh_edges     edges generated beyond the base graph for the ADDs

Every edge is undirected, so it reaches the engine as two arcs, and one
arc is one event.  A window is one DEL batch, then one ADD batch, then one
QUERY.  The window slides through the edge sequence ``base ++ fresh``
cyclically: after the fresh edges the ADDs take base edges that the window
deleted long before, so the stream never runs dry and never adds an edge
that is live.
"""
from __future__ import annotations

import numpy as np

MIX_KEYS = ("del_edges", "add_edges", "warmup_windows", "fresh_edges")


def check_mix(mix: dict) -> dict:
    missing = [k for k in MIX_KEYS if k not in mix]
    if missing:
        raise ValueError(f"traffic mix lacks {missing}")
    if not 0 <= mix["del_edges"] <= mix["add_edges"] or mix["add_edges"] < 1:
        raise ValueError("a window adds at least one edge and deletes "
                         "no more than it adds")
    if mix["add_edges"] != mix["del_edges"]:
        raise ValueError("a growing window needs a pool bound; only "
                         "del_edges == add_edges is supported")
    if mix["fresh_edges"] < mix["add_edges"]:
        raise ValueError("fresh_edges must hold at least one window's ADDs")
    return mix


def pool_arcs(cfg: dict, mix: dict) -> int:
    """Edge-pool slots: every arc the generator draws for the base graph,
    before duplicates go, plus one window's ADDs.  It bounds the live arcs
    for every seed, so every seed runs the same shapes."""
    return 2 * (cfg["degree"] << cfg["scale"]) + 2 * mix["add_edges"]


def arcs(u: np.ndarray, v: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Both arcs of each edge, ``(u, v)`` then ``(v, u)``, edge by edge."""
    return np.stack([u, v], 1).ravel(), np.stack([v, u], 1).ravel()


class Stream:
    """Windows over a ``graphgen.Graph``; window ``k`` counts from the first
    window after the load (warm-up windows included)."""

    def __init__(self, graph, mix: dict):
        self.g = graph
        self.mix = check_mix(mix)
        self.length = len(graph.u)

    def _ids(self, start: int, count: int) -> np.ndarray:
        return (start + np.arange(count, dtype=np.int64)) % self.length

    def deleted(self, k: int) -> np.ndarray:
        d = self.mix["del_edges"]
        return self._ids(k * d, d)

    def added(self, k: int) -> np.ndarray:
        a = self.mix["add_edges"]
        return self._ids(self.g.e0 + k * a, a)

    def live(self, k: int) -> np.ndarray:
        """Edge ids live after window ``k`` (``k = -1``: after the load)."""
        lo = (k + 1) * self.mix["del_edges"]
        hi = self.g.e0 + (k + 1) * self.mix["add_edges"]
        return self._ids(lo, hi - lo)

    def load_log(self, ev):
        """The base graph as one ADD chunk (``ev`` is the engine's event
        module)."""
        g = self.g
        s, d = arcs(g.u[:g.e0], g.v[:g.e0])
        return ev.adds(s, d, np.repeat(g.w[:g.e0], 2))

    def updates_log(self, ev, k: int):
        """Window ``k``'s DEL batch then ADD batch; the QUERY is the
        caller's."""
        g = self.g
        parts = []
        gone = self.deleted(k)
        if len(gone):
            parts.append(ev.dels(*arcs(g.u[gone], g.v[gone])))
        new = self.added(k)
        s, d = arcs(g.u[new], g.v[new])
        parts.append(ev.adds(s, d, np.repeat(g.w[new], 2)))
        return ev.EventLog.concatenate(parts)
