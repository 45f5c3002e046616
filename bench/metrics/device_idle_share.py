"""device_idle_share: the share of the profiled stretch in which no
operation ran on the device (mean over the chips)."""


def read(rec):
    tr = rec.trace
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
