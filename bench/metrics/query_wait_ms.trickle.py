"""Mean host time of the benchmark's span around ``query()``: settling the
epochs dispatched before it, and the readback of ``(dist, parent)``."""


def read(rec):
    w = rec["windows"]
    return 1e3 * sum(x["answered"] - x["dispatched"] for x in w) / len(w)
