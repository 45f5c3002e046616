"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

What it reads, as the TPU runtime writes it:
  * planes ``/device:TPU:<n>``, line ``XLA Ops``: one event per HLO op run,
    named by the op's HLO text (``%copy.1 = f32[...] copy(...)``); line
    ``Async XLA Ops``: the DMA copies that run beside them.  Device busy time
    is the union of both lines' intervals.
  * plane ``/host:CPU``: one line per host thread, with the runtime's own
    spans (``PjitFunction(stack_rois)``, ``DevicePut`` ...) and the spans the
    benchmark opens with ``jax.profiler.TraceAnnotation`` (``bench.*``).
Host and device events share one clock in nanoseconds from the trace start.

The stretch measured is the benchmark's ``bench.stretch`` span when the trace
has one, else everything the trace holds.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Optional

STRETCH_SPAN = "bench.stretch"
BENCH_PREFIX = "bench."
BUSY_LINES = ("XLA Ops", "Async XLA Ops")
_OP_NAME = re.compile(r"%?([^\s=]+) = ")


def op_name(hlo_text: str) -> str:
    """``%stack_rois.1 = f32[100,100]{...} custom-call(...)`` -> ``stack_rois.1``."""
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


class _HostLine:
    """One host thread's spans, sorted by start, for lookups by time."""

    def __init__(self, events) -> None:
        self.ev = sorted(events)                       # (start, end, name)
        self.starts = [e[0] for e in self.ev]
        self.bench = [e for e in self.ev if e[2].startswith(BENCH_PREFIX)
                      and e[2] != STRETCH_SPAN]
        self.bench_starts = [e[0] for e in self.bench]

    def bench_overlap(self, s: float, e: float):
        """The benchmark span on this thread that covers most of [s, e]."""
        i = bisect.bisect_left(self.bench_starts, e)
        best, best_ov = None, 0.0
        for j in range(i - 1, max(i - 8, -1), -1):
            span = self.bench[j]
            ov = min(e, span[1]) - max(s, span[0])
            if ov > best_ov:
                best, best_ov = span, ov
        return best, best_ov

    def innermost(self, t: float, within) -> Optional[str]:
        """The shortest span inside ``within`` that contains time ``t``."""
        i = bisect.bisect_right(self.starts, t)
        best = None
        for j in range(i - 1, -1, -1):
            s, e, name = self.ev[j]
            if s < within[0]:
                break
            if t <= e <= within[1] and (s, e, name) != within and (
                    best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else None


def reduce_trace(path: str, kernel_pattern: str) -> Optional[dict]:
    """Device busy time, per-op time, kernel calls and the idle gaps of one
    trace, over the measured stretch; None for a trace with no TPU in it.
    Times are in seconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, dict[str, list]] = {}
    host_lines: list[_HostLine] = []
    stretch = None
    t_min, t_max = float("inf"), 0.0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in BUSY_LINES:
                    lines[line.name] = [(e.start_ns, e.start_ns + e.duration_ns,
                                         e.name) for e in line.events]
            devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = []
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    t_min, t_max = min(t_min, e.start_ns), max(t_max, end)
                    if e.name == STRETCH_SPAN:
                        stretch = (e.start_ns, end)
                    evs.append((e.start_ns, end, e.name))
                host_lines.append(_HostLine(evs))
    if not devices:
        return None
    lo, hi = stretch if stretch is not None else (t_min, t_max)
    kernel = re.compile(kernel_pattern)
    per_device = []
    op_time: dict[str, float] = defaultdict(float)
    gap_time: dict[str, float] = defaultdict(float)
    k_calls, k_ns = 0, 0.0
    for lines in devices.values():
        busy_iv = []
        for lname, evs in lines.items():
            for s, e, name in evs:
                c = _clip(s, e, lo, hi)
                if c is None:
                    continue
                busy_iv.append(c)
                if lname == "XLA Ops":
                    op = op_name(name)
                    op_time[op] += c[1] - c[0]
                    if kernel.match(op):
                        k_calls += 1
                        k_ns += e - s
        busy = union(busy_iv)
        per_device.append(sum(e - s for s, e in busy))
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                gap_time[_what_host_did(host_lines, prev, s)] += s - prev
            prev = max(prev, e)
    n_dev = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(per_device) / n_dev / 1e9,
        "devices": n_dev,
        "kernel_calls": k_calls,
        "kernel_s": k_ns / 1e9,
        "device_ops": sorted(((k, v / n_dev / 1e9) for k, v in op_time.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(((k, v / n_dev / 1e9) for k, v in gap_time.items()),
                            key=lambda kv: -kv[1]),
    }


def _what_host_did(lines: list[_HostLine], s: float, e: float) -> str:
    """Name an idle gap by the benchmark span that covers most of it, and
    the innermost runtime span inside that one at the gap's middle."""
    best, best_ov, where = None, 0.0, None
    for hl in lines:
        span, ov = hl.bench_overlap(s, e)
        if ov > best_ov:
            best, best_ov, where = span, ov, hl
    if best is None:
        return "no benchmark span"
    inner = where.innermost((s + e) / 2, best)
    return f"{best[2]} > {inner}" if inner else best[2]
