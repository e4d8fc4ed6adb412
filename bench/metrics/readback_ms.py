"""Mean duration of the program's ``readback`` spans in the traced window:
the copy of an answer's ``(dist, parent)`` to the host, which starts once
the pair is ready (the ``settle`` span before it waits for that)."""
from bench import spans


def read(rec):
    found = spans.of(rec)
    got = [] if found is None else spans.durations_ns(found, ("readback",))
    return 1e-6 * sum(got) / len(got) if got else None
