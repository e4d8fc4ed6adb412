"""The plain reference and the comparison that decides ``correct``.

The reference is SciPy's Dijkstra (``scipy.sparse.csgraph.dijkstra``) over
the live arcs, in float64.  It imports nothing of the program and takes
nothing the program made: the live arcs come from the benchmark's own
stream.

``compare`` holds an answer ``(dist, parent)`` to the reference's distances
and to the live arcs, as the O(E) certificate of the bring-up smoke test
(``chip_smoke.py:certify``) did:

    dist_mismatch  vertices whose distance differs from the reference's
                   (an unreached vertex has distance +inf on both sides)
    parent_bad     vertices whose parent is not what a shortest-path tree of
                   the live graph allows: the source has none, an unreached
                   vertex has none, every other vertex's parent arc is live
                   and tight under the reference's distances

Both are counts with the limit 0.  Weights are integers, so every distance
is an exact integer and the comparison is exact.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse
from scipy.sparse import csgraph

LIMITS = {"dist_mismatch": 0, "parent_bad": 0}


class LiveGraph:
    """The live arcs of one answer as a CSR matrix, with a sorted key per
    arc (``tail * n + head``) to look arcs up by their ends."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray,
                 w: np.ndarray):
        self.n = n
        m = scipy.sparse.csr_matrix(
            (np.asarray(w, np.float64), (src, dst)), shape=(n, n))
        m.sum_duplicates()    # also sorts each row's heads
        self.csr = m
        rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(m.indptr))
        self.keys = rows * n + m.indices

    @staticmethod
    def of_edges(n: int, u: np.ndarray, v: np.ndarray, w: np.ndarray
                 ) -> "LiveGraph":
        """Both arcs of each undirected edge."""
        return LiveGraph(n, np.concatenate([u, v]), np.concatenate([v, u]),
                         np.concatenate([w, w]))

    def weight(self, tail: np.ndarray, head: np.ndarray) -> np.ndarray:
        """Weight of each arc ``tail -> head``; NaN where it is not live."""
        want = tail * self.n + head
        pos = np.minimum(np.searchsorted(self.keys, want), len(self.keys) - 1)
        found = self.keys[pos] == want
        return np.where(found, self.csr.data[pos], np.nan)

    def sssp(self, source: int) -> np.ndarray:
        return csgraph.dijkstra(self.csr, directed=True, indices=source)


def compare(g: LiveGraph, source: int, ref: np.ndarray, dist: np.ndarray,
            parent: np.ndarray) -> dict[str, int]:
    """The counts of the module docstring for one answer."""
    n = g.n
    dist = np.asarray(dist, np.float64).reshape(-1)
    parent = np.asarray(parent, np.int64).reshape(-1)
    if dist.shape != (n,) or parent.shape != (n,):
        return {"dist_mismatch": n, "parent_bad": n}
    reached = np.isfinite(ref)
    bad = np.zeros(n, np.bool_)
    bad[~reached] = parent[~reached] != -1
    v = np.flatnonzero(reached)
    v = v[v != source]
    p = parent[v]
    ok = (p >= 0) & (p < n)
    p = np.where(ok, p, 0)
    ok &= ref[p] + g.weight(p, v) == ref[v]    # NaN: no live arc
    bad[v] = ~ok
    bad[source] = parent[source] != -1
    return {"dist_mismatch": int(np.sum(dist != ref)),
            "parent_bad": int(bad.sum())}
