"""Run one cell of the benchmark once, on the chip this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout.  Standard error carries the set-up's parts,
the window's counts and, last, each number compared beside its limit.  The
last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``.  With no TPU, or fewer chips than the
cell asks for, it exits 2 and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, peaks, stats  # noqa: E402
from bench.reference import LIMITS  # noqa: E402


def result(rec: dict, spec: dict, trace: bool, root: Path = ROOT) -> dict:
    """The result line of a run's record."""
    metrics = {}
    for m in harness.cell_metrics(spec, rec["workload"], trace):
        value = harness.reader(m["name"], root)(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    worst = rec["checks"]["worst"]
    device = dict(rec["device"], memory_peak_bytes=rec["peak_bytes"])
    out = {
        "correct": rec["checks"]["failed"] == 0 and rec["answers_checked"] > 0,
        "attempted": len(rec["windows"]),
        "failed": rec["checks"]["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace:
        tr = rec["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": [list(g) for g in tr["idle_gaps"]]}
    out["checks"] = {
        **{n: {"value": worst[n], "limit": LIMITS[n]} for n in LIMITS},
        "answers_checked": {"value": rec["answers_checked"], "limit": 1},
    }
    return out


def report(rec: dict, out: dict) -> None:
    """The readings behind the result, on standard error; the numbers
    compared come last."""
    log = harness.log
    lat = [w["answered"] - w["start"] for w in rec["windows"]]
    parts = " ".join(f"{k}={v:.6g}" for k, v in rec["setup_parts"].items())
    log(f"setup_s={rec['setup_s']:.6g} {parts}")
    log(f"window: answers={len(lat)} events="
        f"{sum(w['events'] for w in rec['windows'])} "
        f"seconds={rec['t_end'] - rec['t_start']:.6g} "
        f"latency_p50_ms={stats.pctile(lat, 50) * 1e3:.6g} "
        f"latency_max_ms={max(lat) * 1e3:.6g} rounds={rec['rounds']} "
        f"programs_in_window={rec['programs_in_window']} "
        f"compiles_in_window={rec['compiles_in_window']}")
    if rec["device"]["platform"] == "tpu":
        hbm = peaks.of(rec["device"]["kind"])["hbm_bytes"]
        log(f"peak_bytes={rec['peak_bytes']} "
            f"({100 * rec['peak_bytes'] / hbm:.3g}% of {hbm}); after "
            + " ".join(f"{k}={v}" for k, v in rec["peak_by_phase"].items()))
    if "trace" in rec:
        tr = rec["trace"]
        log(f"trace: busy_s={tr['busy_s']:.6g} window_s={tr['window_s']:.6g} "
            f"idle_by_span={json.dumps(tr['idle_by_span'])}")
    log(f"check: {rec['answers_checked']} sampled answers in "
        f"{rec['check_s']:.3f} s")
    for name, c in out["checks"].items():
        rel = ">=" if name == "answers_checked" else "<="
        log(f"check {name}={c['value']} limit {rel} {c['limit']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        rec = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS,
                               harness.CompileCounter())
    except harness.NoChip as e:
        harness.log(f"bench: {e}")
        return 2
    out = result(rec, harness.load_spec(), bool(args.trace))
    report(rec, out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
