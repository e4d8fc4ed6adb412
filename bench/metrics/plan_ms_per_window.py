"""Host time of the program's slot planning, its ``plan_adds`` and
``plan_dels`` spans, in the traced window, over the windows answered in
it."""
from bench import spans


def read(rec):
    found = spans.of(rec)
    if found is None:
        return None
    return 1e-6 * sum(spans.durations_ns(
        found, ("plan_adds", "plan_dels"))) / len(rec["windows"])
