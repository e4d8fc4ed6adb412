"""Self time of the program's ``add_epoch`` and ``del_epoch`` spans in the
traced window, over the windows answered in it: padding, the upload, the
layout patch and the asynchronous dispatch of each epoch, no planning."""
from bench import spans


def read(rec):
    found = spans.of(rec)
    if found is None:
        return None
    return 1e-6 * spans.self_ns(
        found, ("add_epoch", "del_epoch")) / len(rec["windows"])
