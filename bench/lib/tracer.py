"""A profiled stretch of the window, taken on a thread of its own.

The thread sleeps until ``start`` (wall-clock seconds since the epoch),
starts the JAX profiler with the Python tracer off (the runtime's own host
spans and the benchmark's ``bench.*`` annotations stay), holds a
``bench.stretch`` span for ``seconds``, and stops the profiler.  The same
class runs in the benchmark's process on one chip and inside each fleet host
(see ``fleet_task``), so both read one kind of trace.
"""
from __future__ import annotations

import glob
import os
import threading
import time
from typing import Optional

from .xtrace import STRETCH_SPAN


class Tracer(threading.Thread):
    def __init__(self, log_dir: str, start: float, seconds: float) -> None:
        super().__init__(daemon=True, name="bench-tracer")
        self.log_dir, self.start_at, self.seconds = log_dir, start, seconds
        self.path: Optional[str] = None
        self.error: Optional[str] = None

    def run(self) -> None:
        import jax

        try:
            time.sleep(max(self.start_at - time.time(), 0.0))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(STRETCH_SPAN):
                    time.sleep(self.seconds)
            finally:
                jax.profiler.stop_trace()
            found = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                              recursive=True)
            self.path = max(found, key=os.path.getmtime) if found else None
        except Exception as e:  # noqa: BLE001 - reported, the run goes on
            self.error = f"{type(e).__name__}: {e}"
