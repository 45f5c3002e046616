"""gen_lag_p95_ms: 95th percentile of how late the benchmark's load
generator submitted each arrival (the runtime's submit stamp against the
time it was due); an open-loop mix only."""
from bench.lib.readout import pct


def read(rec):
    if rec.traffic_kind != "poisson":
        return None
    p = pct([s["submit"] - s["due"] for s in rec.stacks()], 95)
    return None if p is None else p * 1e3
