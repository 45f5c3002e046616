"""stack_p95_ms: the 95th percentile over every stack due in the window of
the time from when it was due to when it completed (host clock).  Every
stack offered is waited for, so a stack that ends after the window counts
with its whole wait; one that failed counts at the time the run gave up on
it."""
from bench.lib.readout import pct


def read(rec):
    if rec.traffic_kind != "poisson":
        return None
    gave_up = rec.notes.get("drained_at", rec.window[1])
    lat = [(s["end"] if s["ok"] else gave_up) - s["due"]
           for s in rec.stacks()]
    p = pct(lat, 95)
    return None if p is None else p * 1e3
