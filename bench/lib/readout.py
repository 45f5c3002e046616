"""What the metric readers share: which stacks count, and percentiles."""
from __future__ import annotations

from typing import Optional

import numpy as np


def completed_in_window(rec) -> list[dict]:
    """Stacks that completed inside the window (all stacks the window
    offered are waited for; those that end after it do not count here)."""
    t0, t1 = rec.window
    return [s for s in rec.stacks() if s["ok"] and t0 <= s["end"] <= t1]


def pct(values, q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation), or None with no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def exec_seconds(rec, tids: set) -> list[float]:
    """Task fn time of each stack in ``tids``: the runtime's ``exec_start``
    to ``exec_end`` events of its last attempt (one host's clock)."""
    if rec.events is None:
        return []
    start: dict[str, float] = {}
    dur: dict[str, float] = {}
    for ev in rec.events:
        tid = ev.get("tid")
        if tid not in tids:
            continue
        if ev["kind"] == "exec_start":
            start[tid] = ev["t"]
        elif ev["kind"] == "exec_end" and tid in start:
            dur[tid] = ev["t"] - start[tid]
    return list(dur.values())
