"""idle_unattributed_share: the share of the device's idle time in the
profiled stretch that the host trace cannot name below the benchmark's
span: idle gaps whose name carries no inner span (no ``" > "``), over all
idle gaps.  A guard on the spans' coverage, not the speed of a layer: it
rises where a phase of the task path runs outside every span."""


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    gaps = tr["idle_gaps"]
    total = sum(v for _, v in gaps)
    if total <= 0:
        return None
    return 100.0 * sum(v for k, v in gaps if " > " not in k) / total
