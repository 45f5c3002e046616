"""One run of one cell: set the system up, offer the traffic, close the
window, collect what it did.

The system under test is built as its users build it: the configuration's
``ExperimentSpec`` through ``RuntimeEngine``, a ``DiffusionRuntime`` of
executor threads on one chip or a ``FleetRuntime`` of host processes with a
chip each, running the configuration's task fn.  The benchmark only puts the
catalog's files in the store and submits tasks, as a client would: one task
a stack, whose inputs are the stack's ``files_per_stack`` files in the order
the traffic drew them.
"""
from __future__ import annotations

import gc
import json
import os
import resource
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from .catalog import Catalog, oid_of
from .fleet_task import TASK_ENV
from .traffic import Traffic

FLEET_TASK_FN = "bench.lib.fleet_task:stack_task"
#: set-up and drain bounds (seconds)
WARMUP_TIMEOUT = 300.0
DRAIN_TIMEOUT = 60.0
#: the profiled stretch: where it starts in the window, and how long it is
STRETCH_AT, STRETCH_MAX_S = 0.5, 4.0
#: the span the benchmark opens around each call of the task fn
TASK_SPAN = "bench.stack_task"
#: a placeholder binding: the spec wants one, the benchmark submits its own
_NO_WORKLOAD = {"name": "bench", "n_tasks": 1, "n_objects": 1,
                "object_bytes": 1,
                "arrivals": {"kind": "PoissonArrivals", "rate_per_s": 1.0},
                "popularity": {"kind": "StackingTrace", "locality": 1}}


class NoChip(RuntimeError):
    """The run found fewer chips than the cell asks for."""


@dataclass
class Offered:
    """One stack the window offered, its file ids, and when it was due."""

    task: Any
    fids: tuple[int, ...]
    due: float


@dataclass
class RunRecord:
    """What one run saw; the metric readers take their numbers from here."""

    cell: str
    seconds: float
    traffic_kind: str = "backlog"
    window: tuple[float, float] = (0.0, 0.0)
    setup_s: float = 0.0
    offered: list = field(default_factory=list)
    events: Optional[list] = None
    dispatch_before: dict = field(default_factory=dict)
    dispatch_after: dict = field(default_factory=dict)
    trace: Optional[dict] = None
    kernel_cost: Optional[dict] = None
    min_backlog: Optional[int] = None
    lock_hold_by_s: list = field(default_factory=list)
    gc_pauses: list = field(default_factory=list)
    compiles_in_window: int = 0
    notes: dict = field(default_factory=dict)

    def stacks(self) -> list[dict]:
        """Per offered stack: its times (monotonic seconds), outcome and
        input ledger."""
        out = []
        for o in self.offered:
            t = o.task
            out.append({
                "tid": t.tid, "fids": o.fids, "due": o.due,
                "submit": t.submit_time, "dispatch": t.dispatch_time,
                "end": t.end_time, "state": t.state.value,
                "ok": t.state.value == "done",
                "cache_hits": t.cache_hits, "peer_hits": t.peer_hits,
                "cache_misses": t.cache_misses,
                "bytes_c2c": t.bytes_cache_to_cache})
        return out


def process_start() -> float:
    """This process's start on the ``time.time()`` clock (Linux), else now."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as fh:
            btime = next(int(ln.split()[1]) for ln in fh
                         if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


def compile_cache_env(root: str) -> None:
    """JAX's persistent cache at a fixed place in the checkout
    (``.jax_cache/``, where ``repro.compile_cache`` keeps it too).  Its
    thresholds stay the program's own, which are JAX's: a program that
    compiles in under a second is compiled in every process, as the stacking
    kernel is.  Host processes inherit the directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")


def watch_compiles() -> dict:
    from jax import monitoring

    stats = {"programs": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["programs"] += 1
            stats["compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return stats


def has_accelerator_files() -> bool:
    """Whether the machine shows TPU device files, read without JAX (a fleet's
    central process must leave the chips to its hosts)."""
    try:
        if any(n.startswith("accel") for n in os.listdir("/dev")):
            return True
        return any(n.isdigit() for n in os.listdir("/dev/vfio"))
    except OSError:
        return False


class GcClock:
    """The collector's pauses while it is registered in ``gc.callbacks``:
    (start on the monotonic clock, seconds, generation)."""

    def __init__(self) -> None:
        self.pauses: list[tuple[float, float, int]] = []
        self._t: Optional[float] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.pauses.append((self._t, time.monotonic() - self._t,
                                info["generation"]))
            self._t = None


class System:
    """The program under test, as its configuration builds it."""

    def __init__(self, cfg: dict, trace: bool) -> None:
        from repro.experiments import ExperimentSpec, RuntimeEngine

        d = json.loads(json.dumps(cfg["spec"]))
        d["workload"] = _NO_WORKLOAD
        d["observe"] = {"events": bool(trace), "ring_capacity": 1 << 21}
        spec = ExperimentSpec.from_dict(d)
        self.fleet = spec.hosts > 0
        self.out_bytes = int(cfg["stack_output_bytes"])
        if self.fleet:
            os.environ[TASK_ENV] = cfg["task_fn"]
            self.engine = RuntimeEngine(task_fn_name=FLEET_TASK_FN).prepare(spec)
            self.task_fn = None
        else:
            from repro.fleet.host import resolve_task_fn

            self.engine = RuntimeEngine().prepare(spec)
            self.task_fn = resolve_task_fn(cfg["task_fn"])
            if trace:
                self.task_fn = _annotated(self.task_fn)
        self.rt = self.engine.runtime
        self.recorder = self.engine.recorder
        self._n = 0

    def task(self, fids: tuple[int, ...]):
        from repro.core.objects import DataObject, Task

        self._n += 1
        outs = ((DataObject(f"stack.{self._n}", self.out_bytes),)
                if self.out_bytes else ())
        return Task(inputs=tuple(oid_of(f) for f in fids), outputs=outs,
                    fn=self.task_fn)

    def done_count(self) -> int:
        d = self.rt.dispatcher
        return len(d.completed) + len(d.failed)

    def shutdown(self) -> None:
        """Stops the runtime: executor threads, or every host process."""
        self.engine.shutdown()


def _annotated(fn):
    def stack_task(inputs):
        import jax

        with jax.profiler.TraceAnnotation(TASK_SPAN):
            return fn(inputs)
    return stack_task


class Run:
    """One run: ``setup()``, ``window()``, ``drain()``, then the record."""

    def __init__(self, cell: str, cfg: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool) -> None:
        self.cfg, self.seed, self.trace = cfg, seed, trace
        self.traffic = Traffic(traffic, seed, cfg["files_per_stack"])
        self.rec = RunRecord(cell=cell, seconds=seconds,
                             traffic_kind=self.traffic.arrivals["kind"])
        self.system: Optional[System] = None
        self.catalog: Optional[Catalog] = None
        self.compiles: Optional[dict] = None
        self._stream = self.traffic.stream()
        self._tmp = tempfile.mkdtemp(prefix="bench-")
        self._tracer = None

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        cfg = self.cfg
        marks = [("start", time.monotonic())]
        assumed = cfg["assumed"]
        self.catalog = Catalog(self.seed, assumed["tiles_per_file"],
                               tuple(cfg["roi_shape"]),
                               assumed["distinct_files"])
        if self.catalog.distinct_files < self.traffic.files:
            raise ValueError(
                f"a segment reads {self.traffic.files} files and the catalog "
                f"has {self.catalog.distinct_files} distinct ones")
        marks.append(("catalog", time.monotonic()))
        if not cfg["spec"].get("hosts", 0):
            self.compiles = watch_compiles()
        self.system = System(cfg, self.trace)
        marks.append(("system", time.monotonic()))
        if not self.traffic.fresh:
            # a fixed catalog: every file is in the store before any stack
            for fid in self.traffic.segment(0).files:
                self._put(fid)
            marks.append(("store", time.monotonic()))
        warm = [self._next_task() for _ in range(self.traffic.warmup_stacks)]
        self.system.rt.submit(o.task for o in warm)
        if not self.system.rt.wait(timeout=WARMUP_TIMEOUT):
            raise RuntimeError("warm-up did not drain")
        marks.append(("warm-up", time.monotonic()))
        self.rec.notes["setup_s"] = {b[0]: round(b[1] - a[1], 3)
                                     for a, b in zip(marks, marks[1:])}
        bad = [o.task.tid for o in warm if o.task.state.value != "done"]
        if bad:
            raise RuntimeError(f"{len(bad)} warm-up stacks failed: "
                               f"{warm[0].task.result!r}")

    def _put(self, fid: int) -> None:
        from repro.core.objects import DataObject

        oid = oid_of(fid)
        if oid not in self.system.rt.store:
            self.system.rt.put_object(DataObject(oid, self.catalog.file_bytes),
                                      self.catalog.file(fid))

    def _next_task(self) -> Offered:
        fids, first = next(self._stream)
        for fid in first:
            self._put(fid)
        return Offered(self.system.task(fids), fids, 0.0)

    # -- the measured window --------------------------------------------------
    def window(self) -> None:
        rt, rec = self.system.rt, self.rec
        compiled_before = self.compiles["programs"] if self.compiles else 0
        rec.dispatch_before = rt.dispatch_stats()
        use0, load0 = resource.getrusage(resource.RUSAGE_SELF), os.getloadavg()
        gc_clock = GcClock()
        gc.callbacks.append(gc_clock)
        t0 = time.monotonic()
        self._start_trace()
        try:
            if self.traffic.arrivals["kind"] == "backlog":
                self._backlog(t0, t0 + rec.seconds)
            else:
                self._poisson(t0, t0 + rec.seconds)
        finally:
            gc.callbacks.remove(gc_clock)
        rec.window = (t0, t0 + rec.seconds)
        rec.gc_pauses = [p for p in gc_clock.pauses if p[0] < t0 + rec.seconds]
        rec.dispatch_after = rt.dispatch_stats()
        use1 = resource.getrusage(resource.RUSAGE_SELF)
        rec.notes["host"] = {
            "cpu_s": (use1.ru_utime + use1.ru_stime
                      - use0.ru_utime - use0.ru_stime),
            "ctx_switches": [use1.ru_nvcsw - use0.ru_nvcsw,
                             use1.ru_nivcsw - use0.ru_nivcsw],
            "loadavg_1m": [load0[0], os.getloadavg()[0]]}
        if self.compiles:
            rec.compiles_in_window = self.compiles["programs"] - compiled_before

    def _backlog(self, t0: float, t1: float) -> None:
        a = self.traffic.arrivals
        low, chunk = int(a["low_water"]), int(a["chunk"])
        sys_, offered = self.system, self.rec.offered
        n_exec = len(sys_.rt.workers)
        base = sys_.done_count()
        min_backlog = None
        mark = t0 + 1.0
        while (now := time.monotonic()) < t1:
            if now >= mark:
                # the pump's lock time, once a second, to place stalls
                self.rec.lock_hold_by_s.append(
                    sys_.rt.dispatch_stats().get("lock_hold_s", 0.0))
                mark += 1.0
            backlog = len(offered) - (sys_.done_count() - base) - n_exec
            if min_backlog is not None or backlog >= low:
                # counted once the first fill has reached the low water
                min_backlog = backlog if min_backlog is None \
                    else min(min_backlog, backlog)
            if backlog >= low:
                time.sleep(0.005)
                continue
            batch = [self._next_task() for _ in range(chunk)]
            now = time.monotonic()
            for o in batch:
                o.due = now
            offered.extend(batch)
            sys_.rt.submit(o.task for o in batch)
        self.rec.min_backlog = min_backlog

    def _poisson(self, t0: float, t1: float) -> None:
        offered = self.rec.offered
        rt = self.system.rt
        for off in self.traffic.arrival_offsets(t1 - t0):
            o = self._next_task()
            o.due = t0 + float(off)
            wait = o.due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            offered.append(o)
            rt.submit((o.task,))

    def _start_trace(self) -> None:
        if not self.trace:
            return
        secs = self.rec.seconds
        start = time.time() + STRETCH_AT * secs
        length = min(STRETCH_MAX_S, 0.4 * secs)
        if self.system.fleet:
            return  # the chips belong to the hosts; no device trace
        from .tracer import Tracer

        self._tracer = Tracer(os.path.join(self._tmp, "trace"), start, length)
        self._tracer.start()

    # -- after the window -------------------------------------------------------
    def drain(self) -> bool:
        """Wait for every offered stack; True when all came."""
        ok = self.system.rt.wait(timeout=DRAIN_TIMEOUT)
        if self.trace and self.system.recorder is not None:
            self.rec.events = self.system.recorder.events()
        return ok

    def trace_path(self) -> Optional[str]:
        """The trace this run took on its one chip, if it took one."""
        if self._tracer is None:
            return None
        self._tracer.join(120.0)
        if self._tracer.error:
            raise RuntimeError(f"tracing failed: {self._tracer.error}")
        return self._tracer.path

    def probe_hosts(self) -> dict:
        """Fleet: one probe task per executor, until every host answered;
        returns host_id -> probe result."""
        from repro.core.objects import DataObject, Task
        from repro.fleet.host import PeerClient

        rt = self.system.rt
        hosts = {h.host_id: h for h in rt.manager.live_handles()}
        got: dict[str, dict] = {}
        peers = PeerClient(rt.manager.codec)
        try:
            for attempt in range(4):
                probes = [Task(inputs=(), outputs=(DataObject(
                    f"probe.{attempt}.{i}", 64),)) for i in range(len(rt.workers))]
                rt.submit(probes)
                if not rt.wait(timeout=180.0):
                    raise RuntimeError("probe tasks did not drain")
                for t in probes:
                    host = rt.workers[t.executor].host
                    if host.host_id in got or t.state.value != "done":
                        continue
                    res = peers.fetch((host.peer_host, host.peer_port),
                                      t.executor, t.outputs[0].oid)
                    if res is not None:
                        got[host.host_id] = res
                if len(got) == len(hosts):
                    break
        finally:
            peers.close()
        self.rec.notes["probes"] = got
        return got

    def results(self, offered: list) -> dict:
        """tid -> stacked pixels, for the given stacks: from the task on one
        chip, fetched back from the executor cache that holds it on a fleet."""
        if not self.system.fleet:
            return {o.task.tid: np.asarray(o.task.result) for o in offered}
        from repro.fleet.host import PeerClient

        rt = self.system.rt
        out = {}
        peers = PeerClient(rt.manager.codec)
        try:
            for o in offered:
                oid = o.task.outputs[0].oid
                locs = sorted(rt.dispatcher.index.lookup(oid))
                for eid in locs:
                    host = rt.workers[eid].host
                    got = peers.fetch((host.peer_host, host.peer_port), eid,
                                      oid)
                    if got is not None:
                        out[o.task.tid] = np.asarray(got)
                        break
        finally:
            peers.close()
        return out

    def close(self) -> None:
        if self.system is not None:
            self.system.shutdown()

    def cleanup(self) -> None:
        import shutil

        shutil.rmtree(self._tmp, ignore_errors=True)
