"""Readings that set the limits of ``correct``: the program over many
seeds, and the control in the program's place over a few.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --program-seeds <n> ... --control-seeds <n> ...

runs each seed through the whole harness in one process (one set-up of
JAX, programs from the compilation cache) and prints one JSON line per run
with the numbers compared.  The benchmark's own runs never run it.

The configurations state no floating-point precision (integer weights,
exact distances), so the control breaks a guarantee they state instead:
``ReferenceEngine(apply_deletions=False)`` answers every QUERY with the
reference's exact shortest paths over every edge ever added, the DELs never
applied.  It has to come out not correct.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, reference  # noqa: E402


class _Answer:
    def __init__(self, dist, parent):
        self.dist, self.parent = dist, parent


class ReferenceEngine:
    """The reference behind the engine's interface: ``ingest_log`` keeps the
    arcs on the host, ``query()`` answers with Dijkstra's distances and a
    shortest-path tree.  With ``apply_deletions=False`` it ignores DELs."""

    def __init__(self, *, num_vertices: int, source: int,
                 apply_deletions: bool = True, **_engine_settings):
        self.n, self.source = num_vertices, source
        self.apply_deletions = apply_deletions
        self.keys = np.empty(0, np.int64)     # tail * n + head
        self.w = np.empty(0, np.float64)
        self.n_rounds = 0

    def ingest_log(self, log) -> list:
        from repro.core import events as ev
        answers = []
        for batch in log.runs():
            keys = batch.src * self.n + batch.dst
            if batch.kind == ev.ADD:
                self.keys = np.concatenate([self.keys, keys])
                self.w = np.concatenate([self.w, batch.w])
            elif batch.kind == ev.DEL and self.apply_deletions:
                keep = ~np.isin(self.keys, keys)
                self.keys, self.w = self.keys[keep], self.w[keep]
            elif batch.kind == ev.QUERY:
                answers.append(self.query())
        return answers

    def query(self) -> _Answer:
        g = reference.LiveGraph(self.n, self.keys // self.n,
                                self.keys % self.n, self.w)
        dist = g.sssp(self.source)
        # a parent per reached vertex: the tail of one of its tight arcs
        rows = g.keys // self.n
        heads = g.csr.indices
        tight = dist[rows] + g.csr.data == dist[heads]
        parent = np.full(self.n, -1, np.int64)
        parent[heads[tight]] = rows[tight]
        parent[self.source] = -1
        parent[~np.isfinite(dist)] = -1
        return _Answer(dist.astype(np.float32), parent)


def control_engine(**settings):
    return ReferenceEngine(apply_deletions=False, **settings)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    compiles = harness.CompileCounter()
    runs = [("program", s, None) for s in args.program_seeds]
    runs += [("control", s, control_engine) for s in args.control_seeds]
    for who, seed, make in runs:
        try:
            rec = harness.run_cell(args.workload, seed, args.seconds, False,
                                   time.perf_counter(), make_engine=make,
                                   compiles=compiles)
        except harness.NoChip as e:
            harness.log(f"bench: {e}")
            return 2
        print(json.dumps({
            "who": who, "workload": args.workload, "seed": seed,
            "answers": len(rec["windows"]),
            "answers_checked": rec["answers_checked"],
            "failed": rec["checks"]["failed"], **rec["checks"]["worst"],
            "setup_s": rec["setup_s"], "check_s": rec["check_s"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
