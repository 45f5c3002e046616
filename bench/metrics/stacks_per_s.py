"""stacks_per_s: stacks completed inside the window, over its length
(host clock).  Every cell whose traffic keeps a backlog reports it."""
from bench.lib.readout import completed_in_window


def read(rec):
    t0, t1 = rec.window
    return len(completed_in_window(rec)) / (t1 - t0)
