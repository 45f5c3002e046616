"""stack_kernel_roofline: the least time the chip needs for the stacking
kernel's calls in the profiled stretch (bytes from its shapes over the HBM
peak, ``bench/lib/cost.py``), over the device time of those calls."""


def read(rec):
    tr, kc = rec.trace, rec.kernel_cost
    if tr is None or kc is None or not tr["kernel_calls"]:
        return None
    return 100.0 * tr["kernel_calls"] * kc["min_s"] / tr["kernel_s"]
