"""Events answered in the timed window over the window's whole length:
every ADD and DEL arc of every window whose answer came in it."""
from bench import stats


def read(rec):
    events = sum(w["events"] for w in rec["windows"])
    return stats.rate(events, rec["t_start"], rec["t_end"])
