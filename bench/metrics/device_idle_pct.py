"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals / window."""


def read(run):
    t = run.trace
    if not t or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
