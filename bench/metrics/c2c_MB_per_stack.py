"""c2c_MB_per_stack: bytes fetched cache-to-cache from another executor
(the runtime's per-task ledger), per stack completed in the window."""
from bench.lib.readout import completed_in_window


def read(rec):
    done = completed_in_window(rec)
    if not done:
        return None
    return sum(s["bytes_c2c"] for s in done) / len(done) / 1e6
