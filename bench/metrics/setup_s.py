"""Seconds from process start to the first timed event: generating the
graph, loading it, the warm-up windows, and every compile among them."""


def read(rec):
    return rec["setup_s"]
