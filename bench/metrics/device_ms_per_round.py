"""Device busy time in the traced window over the rounds in it."""


def read(rec):
    if "trace" not in rec or rec["rounds"] == 0:
        return None
    return 1e3 * rec["trace"]["busy_s"] / rec["rounds"]
