"""task_ms_p95: 95th percentile of task fn time (exec_start to exec_end
events) over every stack due in the window."""
from bench.lib.readout import exec_seconds, pct


def read(rec):
    p = pct(exec_seconds(rec, {s["tid"] for s in rec.stacks() if s["ok"]}),
            95)
    return None if p is None else p * 1e3
