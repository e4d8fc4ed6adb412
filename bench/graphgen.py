"""Seeded GAP graphs: Graph500 Kronecker (``kron``) and uniform random
(``urand``), undirected, integer weights, vertex ids relabelled.

A vectorised copy of the R-MAT loop in the program's
``graphs/generators.py:rmat``, kept here so that a change to the program
cannot change the inputs it is measured on.  It follows the GAP Benchmark
Suite (Beamer, Asanovic, Patterson, arXiv:1508.03619): ``degree * 2**scale``
generated edges, self-loops and duplicates removed, every edge kept as one
undirected pair whose two arcs share a weight drawn uniformly from the
integers ``[low, high]``; the source is a random vertex of non-zero degree.
The configuration's ``graph_seed`` draws that instance; the run's seed
draws the random permutation of vertex ids that Graph500 relabels with.
Since the instance does not depend on the run's seed, ``cached_instance``
keeps it on disk after the first run and each run relabels it.

Edges are canonical pairs ``(u, v)`` with ``u < v`` after relabelling, kept
in generated order (the sliding window slides through that order).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from pathlib import Path

import numpy as np

# sub-streams of one --seed: independent generators for each purpose
_BASE, _FRESH, _PERM, _WEIGHT, _SOURCE = range(5)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose of one seed; any whole number is a seed
    (negative and beyond 64 bits fold into 64 bits)."""
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), stream])


def raw_edges(kind: str, scale: int, m: int, rng: np.random.Generator,
              kron: dict | None = None) -> tuple[np.ndarray, np.ndarray]:
    """``m`` generated (src, dst) pairs over ``2**scale`` vertices, before
    relabelling and clean-up."""
    n = 1 << scale
    if kind == "urand":
        return (rng.integers(0, n, m, dtype=np.int64),
                rng.integers(0, n, m, dtype=np.int64))
    if kind != "kron":
        raise ValueError(f"unknown generator {kind!r}; known: kron, urand")
    a, b, c = kron["A"], kron["B"], kron["C"]
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    for bit in range(scale):
        r = rng.random(m, dtype=np.float32)
        # quadrant per level: A (0,0), B (0,1), C (1,0), D (1,1)
        src_bit = r >= a + b
        dst_bit = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src |= src_bit.astype(np.int32) << bit
        dst |= dst_bit.astype(np.int32) << bit
    return src, dst


def canonical(src: np.ndarray, dst: np.ndarray, n: int
              ) -> tuple[np.ndarray, np.ndarray]:
    """Drop self-loops and duplicate undirected pairs, keep the first
    occurrence in generated order; returns ``(u, v)`` with ``u < v``."""
    keep = src != dst
    u = np.minimum(src[keep], dst[keep])
    v = np.maximum(src[keep], dst[keep])
    _, first = np.unique(u * n + v, return_index=True)
    first.sort()
    return u[first], v[first]


@dataclasses.dataclass(frozen=True)
class Graph:
    """Undirected edges in stream order: the first ``e0`` are the base
    graph, the rest are fresh edges absent from it, all distinct."""

    n: int
    u: np.ndarray    # i64[E]
    v: np.ndarray    # i64[E]
    w: np.ndarray    # f32[E], integer-valued
    e0: int
    source: int


def instance(cfg: dict, fresh_edges: int) -> Graph:
    """The configuration's graph instance, drawn from its ``graph_seed``,
    plus ``fresh_edges`` further edges of the same generator that the base
    graph does not hold; vertex ids as generated."""
    scale, kind, g_seed = cfg["scale"], cfg["generator"], cfg["graph_seed"]
    n = 1 << scale
    s, d = raw_edges(kind, scale, cfg["degree"] * n, rng_for(g_seed, _BASE),
                     cfg.get("kron"))
    u, v = canonical(s.astype(np.int64), d.astype(np.int64), n)
    e0 = len(u)
    base_keys = np.sort(u * n + v)
    fu = fv = np.empty(0, np.int64)
    rng = rng_for(g_seed, _FRESH)
    while len(fu) < fresh_edges:
        s, d = raw_edges(kind, scale, 2 * (fresh_edges - len(fu)) + 1024,
                         rng, cfg.get("kron"))
        a, b = canonical(s.astype(np.int64), d.astype(np.int64), n)
        key = a * n + b
        order = np.argsort(key)      # sorted probes keep the search cached
        pos = np.empty_like(order)
        pos[order] = np.searchsorted(base_keys, key[order])
        new = base_keys[np.minimum(pos, e0 - 1)] != key
        # drawn in several rounds, fresh edges may repeat one another
        fu, fv = canonical(np.concatenate([fu, a[new]]),
                           np.concatenate([fv, b[new]]), n)
    u = np.concatenate([u, fu[:fresh_edges]])
    v = np.concatenate([v, fv[:fresh_edges]])
    lo, hi = cfg["weights"]["low"], cfg["weights"]["high"]
    w = rng_for(g_seed, _WEIGHT).integers(lo, hi + 1, len(u))
    deg = np.bincount(u[:e0], minlength=n) + np.bincount(v[:e0], minlength=n)
    nonzero = np.flatnonzero(deg)
    source = int(nonzero[rng_for(g_seed, _SOURCE).integers(len(nonzero))])
    return Graph(n, u, v, w.astype(np.float32), e0, source)


def cached_instance(cfg: dict, fresh_edges: int,
                    cache_dir: Path | None) -> Graph:
    """``instance(cfg, fresh_edges)``, kept in ``cache_dir`` under a key of
    the configuration, the count and this file, so that only the first run
    of a checkout generates it."""
    if cache_dir is None:
        return instance(cfg, fresh_edges)
    key = hashlib.sha256(json.dumps([cfg, fresh_edges], sort_keys=True)
                         .encode() + Path(__file__).read_bytes())
    path = Path(cache_dir) / f"{cfg['name']}-{key.hexdigest()[:16]}.npz"
    if path.exists():
        with np.load(path) as z:
            return Graph(int(z["n"]), z["u"].astype(np.int64),
                         z["v"].astype(np.int64), z["w"], int(z["e0"]),
                         int(z["source"]))
    g = instance(cfg, fresh_edges)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.stem + ".partial.npz")
    ids = np.int32 if g.n <= np.iinfo(np.int32).max else np.int64
    np.savez(tmp, n=g.n, u=g.u.astype(ids), v=g.v.astype(ids), w=g.w,
             e0=g.e0, source=g.source)
    os.replace(tmp, path)
    return g


def relabel(g: Graph, seed: int) -> Graph:
    """``g`` with its vertex ids permuted by a permutation drawn from
    ``seed``: the same graph and stream in another vertex order, so the
    work does not change with the seed."""
    perm = rng_for(seed, _PERM).permutation(g.n)
    pu, pv = perm[g.u], perm[g.v]
    return Graph(g.n, np.minimum(pu, pv), np.maximum(pu, pv), g.w, g.e0,
                 int(perm[g.source]))


def generate(cfg: dict, seed: int, fresh_edges: int,
             cache_dir: Path | None = None) -> Graph:
    """The configuration's instance (``instance``), with vertex ids
    relabelled by a permutation drawn from ``seed``."""
    return relabel(cached_instance(cfg, fresh_edges, cache_dir), seed)
