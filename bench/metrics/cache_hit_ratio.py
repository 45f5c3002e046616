"""cache_hit_ratio: input reads served by the executor's own cache, as a
share of all input reads, over the stacks completed in the window (the
runtime's per-task ledger)."""
from bench.lib.readout import completed_in_window


def read(rec):
    done = completed_in_window(rec)
    reads = sum(s["cache_hits"] + s["cache_misses"] for s in done)
    if not reads:
        return None
    return 100.0 * sum(s["cache_hits"] for s in done) / reads
