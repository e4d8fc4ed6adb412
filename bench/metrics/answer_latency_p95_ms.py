"""95th percentile over every answer of the timed window of the time from
its window's first event handed to ``ingest_log`` to its ``(dist, parent)``
on the host."""
from bench import stats


def read(rec):
    return 1e3 * stats.pctile(
        [w["answered"] - w["start"] for w in rec["windows"]], 95)
