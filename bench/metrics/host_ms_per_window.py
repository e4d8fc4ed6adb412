"""Mean host time of the benchmark's span around ``ingest_log`` of a
window's DEL and ADD batches, which returns after host planning and
asynchronous dispatch."""


def read(rec):
    w = rec["windows"]
    return 1e3 * sum(x["dispatched"] - x["start"] for x in w) / len(w)
