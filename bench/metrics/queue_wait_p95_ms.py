"""queue_wait_p95_ms: 95th percentile of the time a stack waited in the
dispatcher's queue (its submit to dispatch stamps), over every stack due in
the window."""
from bench.lib.readout import pct


def read(rec):
    p = pct([s["dispatch"] - s["submit"] for s in rec.stacks() if s["ok"]],
            95)
    return None if p is None else p * 1e3
