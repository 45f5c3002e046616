"""What the span readers share: the durations of one program span
(``repro.obs.spans``) over the stacks completed in the window."""
from __future__ import annotations

from typing import Optional

from .readout import completed_in_window, pct


def span_p50(rec, name: str) -> Optional[float]:
    """Median duration in seconds of the ``name`` spans of the stacks
    completed in the window; None where the run recorded no such span."""
    if rec.events is None:
        return None
    tids = {s["tid"] for s in completed_in_window(rec)}
    return pct([ev["dur"] for ev in rec.events
                if ev["kind"] == "span" and ev["name"] == name
                and ev.get("tid") in tids], 50)
