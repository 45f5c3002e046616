"""task_ms_p50: median task fn time (the runtime's exec_start to exec_end
events: host prep, copy to the device, kernel, result) of the stacks
completed in the window."""
from bench.lib.readout import completed_in_window, exec_seconds, pct


def read(rec):
    p = pct(exec_seconds(rec, {s["tid"] for s in completed_in_window(rec)}),
            50)
    return None if p is None else p * 1e3
