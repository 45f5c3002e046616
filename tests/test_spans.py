"""repro.obs.spans: one emit point for the recorder and the JAX profiler.

Spans are free unless an executor attempt bound a sink (recording on); with
one bound, each span is one ``span`` event on the recorder's clock and, in
the same call, a profiler annotation that carries the start stamp.  On the
runtime, the stacking task fn's three spans partition it and each input
resolve is a span of its own; none of it changes the lifecycle fingerprints
the engine-parity tests compare.
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from repro.core import DataObject, DiffusionRuntime, Task
from repro.obs import (EVENT_SCHEMA_VERSION, Recorder, chrome_trace,
                       lifecycle_fingerprints, load_events)
from repro.obs.spans import bind, span

SRC = Path(__file__).resolve().parents[1] / "src"
STACK_SPANS = ("stack.prep", "stack.launch", "stack.result")


def _clock(start=10.0, step=0.25):
    n = [0]

    def tick():
        n[0] += 1
        return start + step * n[0]
    return tick


# --------------------------------------------------------------------------
# the primitive
# --------------------------------------------------------------------------

def test_spans_off_emit_nothing_and_no_package_imports_jax():
    code = (
        "import sys\n"
        "import repro.obs, repro.core, repro.fleet, repro.experiments\n"
        "from repro.obs.spans import bind, span\n"
        "assert span('a') is span('b') is bind(None, 't', 'w')\n"
        "with span('a'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=str(SRC)),
                         timeout=120)
    assert out.returncode == 0, out.stderr


def test_unbound_thread_records_nothing():
    rec = Recorder(clock=_clock())
    with span("stack.prep"):
        pass
    assert rec.events() == []


def test_bound_span_is_one_event_on_the_recorders_clock():
    rec = Recorder(clock=_clock())
    with bind(rec, "t7", "w2"):
        with span("stack.prep"):
            pass
    assert rec.events() == [{"kind": "span", "name": "stack.prep",
                             "t": 10.25, "dur": 0.25, "tid": "t7",
                             "eid": "w2"}]


def test_binding_is_per_thread_and_restored_on_exit():
    rec, other = Recorder(clock=_clock()), Recorder(clock=_clock())
    seen = []
    with bind(rec, "t0", "w0"):
        th = threading.Thread(target=lambda: seen.append(span("x")))
        th.start()
        th.join()
        with bind(other, "t1", "w1"):
            with span("inner"):
                pass
        with span("outer"):
            pass
    with span("after"):
        pass
    assert seen[0] is span("anything")          # the other thread: null
    assert [e["name"] for e in other.events()] == ["inner"]
    assert [(e["name"], e["tid"]) for e in rec.events()] == [("outer", "t0")]


def test_bindings_stay_on_their_threads_under_contention():
    rec = Recorder(capacity=1 << 20)

    def work(i):
        for k in range(200):
            with bind(rec, f"t{i}.{k}", f"w{i}"):
                with span("a"):
                    pass
                with span("b"):
                    pass
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    evs = rec.events()
    assert len(evs) == 16 * 200 * 2 and rec.dropped == 0
    assert all(e["tid"].split(".")[0] == "t" + e["eid"][1:] for e in evs)
    names: dict = {}
    for e in evs:
        names.setdefault(e["tid"], []).append(e["name"])
    assert all(v == ["a", "b"] for v in names.values()) and len(names) == 3200


def test_a_span_that_raises_is_still_recorded():
    rec = Recorder(clock=_clock())
    with pytest.raises(ValueError):
        with bind(rec, "t0", "w0"), span("stack.launch"):
            raise ValueError("kernel")
    assert [e["name"] for e in rec.events()] == ["stack.launch"]


# --------------------------------------------------------------------------
# the runtime and the stacking task fn
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stacking_run():
    """Twelve stacks of one or two 6 MB files on a recording two-executor
    runtime, through the real task fn (Pallas in interpret mode here).  JAX
    and the kernel are imported first, as a deployment's warm-up does."""
    import repro.kernels.stacking.ops  # noqa: F401
    from repro.workloads.astro import TILES_PER_FIT, make_tiles, stack_task

    rec = Recorder()
    rt = DiffusionRuntime(n_executors=2, recorder=rec, seed=1)
    try:
        oids = [f"astro.g0.o{k}" for k in range(4)]
        for oid in oids:
            tiles = make_tiles(oid, TILES_PER_FIT)
            rt.put_object(DataObject(oid, tiles.nbytes), tiles)
        tasks = [Task(inputs=tuple(oids[(i + j) % 4]
                                   for j in range(1 + i % 2)), fn=stack_task)
                 for i in range(12)]
        rt.submit(tasks)
        assert rt.wait(timeout=300)
        stats = rt.dispatch_stats()
    finally:
        rt.shutdown()
    assert all(t.state.value == "done" for t in tasks), \
        [t.result for t in tasks]
    return tasks, rec, stats


def test_each_stack_has_its_three_spans_and_a_resolve_per_input(stacking_run):
    tasks, rec, _ = stacking_run
    evs = rec.events()
    covered = total = 0.0
    for t in tasks:
        mine = [e for e in evs if e.get("tid") == t.tid]
        start = next(e for e in mine if e["kind"] == "exec_start")
        end = next(e for e in mine if e["kind"] == "exec_end")
        spans = [e for e in mine if e["kind"] == "span"]
        stack = [e for e in spans if e["name"].startswith("stack.")]
        resolve = [e for e in spans if e["name"] == "task.resolve"]
        assert tuple(e["name"] for e in stack) == STACK_SPANS, t.tid
        assert len(resolve) == len(t.inputs), t.tid
        assert {e["eid"] for e in spans} == {start["eid"]} == {t.executor}
        # the task fn's phases lie inside exec_start..exec_end, in order;
        # the inputs are resolved before it starts
        assert all(start["t"] <= e["t"] and e["t"] + e["dur"] <= end["t"]
                   for e in stack), t.tid
        assert all(a["t"] + a["dur"] <= b["t"]
                   for a, b in zip(stack, stack[1:])), t.tid
        assert all(e["t"] + e["dur"] <= start["t"] for e in resolve), t.tid
        covered += sum(e["dur"] for e in stack)
        total += end["t"] - start["t"]
    assert covered >= 0.95 * total, (covered, total)


def test_only_a_stack_of_several_files_copies_them_on_the_host(stacking_run):
    """One file goes to the kernel as the cached array; two are
    concatenated inside ``prep.host_copy``, which lies inside ``stack.prep``."""
    tasks, rec, _ = stacking_run
    evs = [e for e in rec.events() if e["kind"] == "span"]
    for t in tasks:
        prep = next(e for e in evs
                    if e["tid"] == t.tid and e["name"] == "stack.prep")
        copies = [e for e in evs
                  if e["tid"] == t.tid and e["name"] == "prep.host_copy"]
        assert len(copies) == (len(t.inputs) > 1), t.tid
        assert all(prep["t"] <= e["t"] and
                   e["t"] + e["dur"] <= prep["t"] + prep["dur"]
                   for e in copies), t.tid


def test_lock_wait_is_counted_beside_the_hold(stacking_run):
    _, _, st = stacking_run
    assert st["lock_wait_s"] >= 0.0
    assert st["lock_hold_s"] > 0.0


def test_lock_wait_counts_the_time_until_the_lock_is_held():
    rt = DiffusionRuntime(n_executors=1, seed=1)
    try:
        base = rt.dispatch_stats()["lock_wait_s"]
        held = threading.Event()

        def hold():
            with rt._lock:
                held.set()
                time.sleep(0.05)
        th = threading.Thread(target=hold)
        th.start()
        held.wait(5)
        rt._pump()                      # waits out the other holder
        th.join()
        assert rt.dispatch_stats()["lock_wait_s"] - base >= 0.03
    finally:
        rt.shutdown()


def test_spans_leave_lifecycle_fingerprints_unchanged(stacking_run):
    _, rec, _ = stacking_run
    evs = rec.events()
    assert any(e["kind"] == "span" for e in evs)
    fps = lifecycle_fingerprints(evs)
    assert fps == lifecycle_fingerprints(
        [e for e in evs if e["kind"] != "span"])
    assert all("span" not in kinds for kinds, _, _ in fps.values())


def test_a_sink_with_spans_round_trips(stacking_run, tmp_path):
    _, rec, _ = stacking_run
    path = tmp_path / "sink.jsonl"
    rec.dump(path)
    header, evs = load_events(path)
    assert header["schema_version"] == EVENT_SCHEMA_VERSION == 3
    assert evs == rec.events()
    # three phases a stack, a resolve an input, a host copy a two-file stack
    assert sum(e["kind"] == "span" for e in evs) == 12 * 3 + 18 + 6


def test_chrome_trace_draws_spans_on_their_executors_track(stacking_run):
    _, rec, _ = stacking_run
    evs = rec.events()
    out = chrome_trace(evs)["traceEvents"]
    track = {e["args"]["name"]: e["tid"] for e in out if e["ph"] == "M"}
    slices = [e for e in out if e["ph"] == "X" and e["cat"] == "span"]
    spans = [e for e in evs if e["kind"] == "span"]
    assert len(slices) == len(spans)
    assert {s["name"] for s in slices} == {*STACK_SPANS, "task.resolve",
                                           "prep.host_copy"}
    by_task = {s["args"]["task"]: s["tid"] for s in slices}
    for e in spans:
        assert by_task[e["tid"]] == track[e["eid"]]
    assert all(s["dur"] >= 0 and s["ts"] >= 0 for s in slices)


# --------------------------------------------------------------------------
# the profiler's copy
# --------------------------------------------------------------------------

def _profiled(tmp_path, body):
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True))
    return sorted((e for plane in ProfileData.from_file(path).planes
                   if plane.name == "/host:CPU"
                   for line in plane.lines for e in line.events),
                  key=lambda e: e.start_ns)


def test_profiler_spans_carry_bare_names_and_the_stamp(tmp_path):
    import jax  # noqa: F401 - the profiler copy needs JAX imported

    rec = Recorder(clock=lambda: 123.5)

    def body():
        with bind(rec, "t0", "w0"), span("stack.launch"):
            pass
    got = [e for e in _profiled(tmp_path, body) if "stack" in e.name]
    assert [(e.name, list(e.stats)) for e in got] == \
        [("stack.launch", [("t", 123.5)])]


def test_profiler_and_recorder_clocks_differ_by_one_offset(tmp_path):
    import jax  # noqa: F401

    rec = Recorder()

    def body():
        with bind(rec, "t0", "w0"):
            for i in range(8):
                with span(STACK_SPANS[i % 3]):
                    time.sleep(0.002)
                time.sleep(0.003)
    got = [e for e in _profiled(tmp_path, body) if e.name in STACK_SPANS]
    assert [e.name for e in got] == [STACK_SPANS[i % 3] for i in range(8)]
    stamps = [e["t"] for e in rec.events()]
    offsets = [e.start_ns / 1e9 - dict(e.stats)["t"] for e in got]
    assert max(offsets) - min(offsets) < 100e-6, offsets
    assert len(stamps) == 8 and \
        sorted(dict(e.stats)["t"] for e in got) == stamps
