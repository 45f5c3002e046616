"""One emit point for spans: the Recorder's event ring and, in the same
call, the JAX profiler.

A span is opened where the work happens::

    with span("prep.host_copy"):
        tiles = np.concatenate(arrays)

and is free unless the calling thread has a sink bound: executor attempts
bind ``(recorder, tid, eid)`` with :func:`bind` while recording is on
(``observe.events``), and everywhere else :func:`span` returns one shared
null context after a thread-local read.

With a sink bound, a span reads the recorder's clock at entry and at exit
and emits ONE event at exit: ``{"kind": "span", "name", "t": <start>,
"dur", "tid", "eid"}``.  Where the process has imported JAX, the same span
opens ``jax.profiler.TraceAnnotation(name, t=<start>)``: a profiler trace
then names host time by the program's own spans, under their bare names,
and the ``t`` stat maps the profiler's clock onto the recorder's.  This
module never imports JAX itself, so the central process of a fleet stays
off the chip.
"""
from __future__ import annotations

import sys
import threading
from contextlib import nullcontext

from .events import SPAN


class _Local(threading.local):
    sink = None      # a class default: reading a missing one would raise


_local = _Local()
_NULL = nullcontext()


class _Span:
    __slots__ = ("name", "sink", "t", "ann")

    def __init__(self, name: str, sink: tuple) -> None:
        self.name = name
        self.sink = sink

    def __enter__(self) -> "_Span":
        self.t = self.sink[0].clock()
        self.ann = None
        ann = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
        if ann is not None:
            self.ann = ann(self.name, t=self.t)
            self.ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        rec, tid, eid = self.sink
        dur = rec.clock() - self.t
        if self.ann is not None:
            self.ann.__exit__(*exc)
        rec.emit(SPAN, t=self.t, tid=tid, eid=eid, name=self.name, dur=dur)


class _Binding:
    __slots__ = ("sink", "prev")

    def __init__(self, sink: tuple) -> None:
        self.sink = sink

    def __enter__(self) -> None:
        self.prev = _local.sink
        _local.sink = self.sink

    def __exit__(self, *exc) -> None:
        _local.sink = self.prev


def span(name: str):
    """A context manager timing ``name`` on the thread's bound sink; the
    shared null context when none is bound."""
    sink = _local.sink
    if sink is None:
        return _NULL
    return _Span(name, sink)


def bind(recorder, tid, eid):
    """Bind ``(recorder, tid, eid)`` to the calling thread for the ``with``
    block, so the spans opened inside it are recorded; with no recorder,
    the null context."""
    if recorder is None:
        return _NULL
    return _Binding((recorder, tid, eid))
