"""Record the small TPU trace ``data/spans.xplane.pb`` that the program-span
readers are tested on.  Run on a machine with a TPU:

    python3 bench/tests/record_span_trace.py <out.xplane.pb>

One traced run of the harness on ``gap-kron.trickle`` cut to scale 10 and
three timed windows of 32 edges each way, so the trace holds the
program's own spans (``plan_adds`` ... ``readback``) inside the
benchmark's ``bench.ingest_log`` and ``bench.query`` spans.  Prints the
run's span readings.
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, xplane  # noqa: E402

WORKLOAD = "gap-kron.trickle"
READERS = ("plan_ms_per_window", "dispatch_ms_per_window", "readback_ms",
           "host_ms_per_window")


def small_root(d: Path) -> Path:
    """The benchmark with ``gap-kron`` at scale 10, 32 edges each way per
    window, and a nominal window of 0.1 s."""
    shutil.copytree(ROOT / "bench", d / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(ROOT / "BENCHMARK.json", d / "BENCHMARK.json")
    cfg = d / "bench" / "configs" / "gap-kron.json"
    cfg.write_text(json.dumps(dict(json.loads(cfg.read_text()), scale=10)))
    mix = d / "bench" / "traffic" / "trickle.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   del_edges=32, add_edges=32,
                                   fresh_edges=512)))
    (d / "bench" / "cells" / f"{WORKLOAD}.json").write_text(
        json.dumps({"window_s": 0.1}))
    return d


def main(out: str) -> None:
    with tempfile.TemporaryDirectory() as d:
        root = small_root(Path(d))
        rec = harness.run_cell(WORKLOAD, 2**31 + 14, 0.3, True,
                               time.perf_counter(), harness.CompileCounter(),
                               root=root)
        shutil.copy(xplane.find_xplane(root / harness.TRACE_DIR / WORKLOAD),
                    out)
        harness.ROOT = root     # where the readers look for the trace
        print({name: harness.reader(name, root)(rec) for name in READERS},
              "windows", len(rec["windows"]))


if __name__ == "__main__":
    main(sys.argv[1])
