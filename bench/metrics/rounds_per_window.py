"""The engine's ``n_rounds`` device counter across the timed window, over
the windows answered in it."""


def read(rec):
    return rec["rounds"] / len(rec["windows"])
