"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals) / window, in percent."""


def read(rec):
    if "trace" not in rec:
        return None
    return 100.0 * rec["trace"]["idle_share"]
