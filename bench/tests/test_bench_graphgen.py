"""The generator repeats bit for bit, and kron and urand have the degree
shapes their sources give them."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import graphgen, traffic

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name: str, scale: int = 10) -> dict:
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg["scale"] = scale
    return cfg


@pytest.mark.parametrize("name", ["gap-kron", "gap-urand"])
@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**40 + 3, -5])
def test_same_seed_same_graph(name, seed):
    a = graphgen.generate(config(name), seed, 512)
    b = graphgen.generate(config(name), seed, 512)
    for f in ("u", "v", "w"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert (a.e0, a.source) == (b.e0, b.source)


@pytest.mark.parametrize("name", ["gap-kron", "gap-urand"])
def test_another_seed_relabels_the_same_graph(name):
    """The work does not change with the seed: the same instance and
    stream, in another vertex order."""
    a = graphgen.generate(config(name), 11, 512)
    b = graphgen.generate(config(name), 12, 512)
    assert a.e0 == b.e0 and np.array_equal(a.w, b.w)
    assert not np.array_equal(a.u, b.u)
    ends_a = np.stack([a.u, a.v], 1)
    ends_b = np.stack([b.u, b.v], 1)
    deg_a = np.bincount(ends_a[:a.e0].ravel(), minlength=a.n)
    deg_b = np.bincount(ends_b[:b.e0].ravel(), minlength=b.n)
    assert np.array_equal(np.sort(deg_a), np.sort(deg_b))
    assert deg_a[a.source] == deg_b[b.source] > 0
    # each edge keeps its endpoints' degrees, edge by edge
    assert np.array_equal(np.sort(deg_a[ends_a], 1),
                          np.sort(deg_b[ends_b], 1))


@pytest.mark.parametrize("name", ["gap-kron", "gap-urand"])
def test_a_cached_instance_is_the_generated_one(name, tmp_path):
    """The first run keeps the instance on disk; a later run, reading it,
    gets the same graph bit for bit, relabelled by its own seed."""
    fresh = graphgen.generate(config(name), 2**31 + 3, 512)
    first = graphgen.generate(config(name), 2**31 + 3, 512, tmp_path)
    again = graphgen.generate(config(name), 2**31 + 3, 512, tmp_path)
    assert len(list(tmp_path.glob("*.npz"))) == 1
    for g in (first, again):
        for f in ("u", "v", "w"):
            assert np.array_equal(getattr(g, f), getattr(fresh, f))
            assert getattr(g, f).dtype == getattr(fresh, f).dtype
        assert (g.n, g.e0, g.source) == (fresh.n, fresh.e0, fresh.source)
    # another scale is another instance, kept beside the first
    graphgen.generate(config(name, 9), 1, 512, tmp_path)
    assert len(list(tmp_path.glob("*.npz"))) == 2


@pytest.mark.parametrize("name", ["gap-kron", "gap-urand"])
def test_edges_are_simple_undirected_and_weighted(name):
    g = graphgen.generate(config(name), 5, 1024)
    assert np.all(g.u < g.v), "canonical pairs, no self-loop"
    keys = g.u * g.n + g.v
    assert len(np.unique(keys)) == len(keys), "no duplicate edge"
    assert len(g.u) == g.e0 + 1024
    assert np.all(g.w == np.round(g.w)) and g.w.min() >= 1 and g.w.max() <= 255
    assert g.w.dtype == np.float32
    deg = (np.bincount(g.u[:g.e0], minlength=g.n)
           + np.bincount(g.v[:g.e0], minlength=g.n))
    assert deg[g.source] > 0
    assert g.e0 <= 16 * g.n


def test_kron_is_skewed_and_urand_is_not():
    """Graph500's Kronecker graph has hubs and many isolated vertices; a
    uniform graph of the same size has neither (Beamer et al., GAP)."""
    shape = {}
    for name in ("gap-kron", "gap-urand"):
        g = graphgen.generate(config(name), 3, 64)
        deg = (np.bincount(g.u[:g.e0], minlength=g.n)
               + np.bincount(g.v[:g.e0], minlength=g.n))
        shape[name] = (deg.max() / deg.mean(), np.mean(deg == 0), g.e0)
    kron, urand = shape["gap-kron"], shape["gap-urand"]
    assert kron[0] > 10 * urand[0] and urand[0] < 3
    assert kron[1] > 0.1 and urand[1] < 0.01
    # duplicates: kron loses many of its 16 * n draws, urand almost none
    assert urand[2] > 0.97 * 16 * 1024 > 0.9 * 16 * 1024 > kron[2]


def test_window_slides_cyclically_and_never_adds_a_live_edge():
    g = graphgen.generate(config("gap-kron", 8), 1, 64)
    mix = {"del_edges": 48, "add_edges": 48, "warmup_windows": 0,
           "fresh_edges": 64}
    s = traffic.Stream(g, mix)
    assert np.array_equal(s.live(-1), np.arange(g.e0))
    # enough windows to wrap round the whole edge sequence twice
    for k in range(2 * (g.e0 + 64) // 48 + 3):
        before = set(s.live(k - 1).tolist())
        gone, new = s.deleted(k).tolist(), s.added(k).tolist()
        assert set(gone) <= before
        assert not set(new) & (before - set(gone))
        assert set(s.live(k).tolist()) == (before - set(gone)) | set(new)
        assert len(s.live(k)) == g.e0
    assert traffic.pool_arcs(config("gap-kron", 8), mix) >= 2 * g.e0 + 96


def test_window_log_is_both_arcs_of_each_edge():
    from repro.core import events as ev
    g = graphgen.generate(config("gap-urand", 8), 2, 64)
    s = traffic.Stream(g, {"del_edges": 4, "add_edges": 4,
                           "warmup_windows": 0, "fresh_edges": 64})
    log = s.updates_log(ev, 0)
    assert list(log.kind) == [ev.DEL] * 8 + [ev.ADD] * 8
    new = s.added(0)
    assert np.array_equal(log.src[8::2], g.u[new])
    assert np.array_equal(log.src[9::2], g.v[new])
    assert np.array_equal(log.w[8::2], g.w[new])
    assert len(s.load_log(ev)) == 2 * g.e0
