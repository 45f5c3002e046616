"""The readers of the program's spans and lock-wait counter, and the share
of idle time the trace cannot name, on a synthetic run."""
import pytest

from bench.lib import layout
from bench.lib.drive import RunRecord
from bench.tests.test_bench_readers import _record

PHASES = {"stack.prep": 3e-3, "stack.launch": 700e-6,
          "stack.result": 400e-6, "task.resolve": 20e-6}


def read(name, rec):
    return layout.reader(name).read(rec)


def _with_spans(rec):
    # stack i's phases take (1 + i / 100) times their base length; stack i
    # has 1 + i % 2 inputs, so a resolve span each
    for i in range(40):
        k = 1 + i / 100
        for name, base in PHASES.items():
            for _ in range(1 + i % 2 if name == "task.resolve" else 1):
                rec.events.append({"kind": "span", "name": name,
                                   "t": float(i), "dur": base * k,
                                   "tid": f"t{i}", "eid": "w0"})
    return rec


def _median_over_window(base):
    # stacks 0..35 end in the window, 7 failed
    import numpy as np

    return float(np.median([base * (1 + i / 100)
                            for i in range(36) if i != 7]))


@pytest.mark.parametrize("name,span,scale", [
    ("prep_ms_p50", "stack.prep", 1e3),
    ("launch_us_p50", "stack.launch", 1e6),
    ("result_us_p50", "stack.result", 1e6),
])
def test_phase_medians_over_the_stacks_completed_in_the_window(
        name, span, scale):
    rec = _with_spans(_record())
    assert read(name, rec) == pytest.approx(
        _median_over_window(PHASES[span]) * scale)


def test_resolve_median_over_every_input_of_those_stacks():
    import numpy as np

    rec = _with_spans(_record())
    durs = [PHASES["task.resolve"] * (1 + i / 100)
            for i in range(36) if i != 7 for _ in range(1 + i % 2)]
    assert read("resolve_us_p50", rec) == pytest.approx(
        float(np.median(durs)) * 1e6)


@pytest.mark.parametrize("name", ["prep_ms_p50", "launch_us_p50",
                                  "result_us_p50", "resolve_us_p50"])
def test_a_program_without_spans_reads_as_nothing(name):
    rec = _record()                     # lifecycle events, no spans
    assert read(name, rec) is None
    rec.events = None                   # a run with recording off
    assert read(name, rec) is None


def test_lock_wait_per_dispatch():
    rec = _record()
    rec.dispatch_before["lock_wait_s"] = 2.0
    rec.dispatch_after["lock_wait_s"] = 2.002
    assert read("lock_wait_us_per_stack", rec) == pytest.approx(50.0)


def test_lock_wait_reads_nothing_without_the_counter_or_dispatches():
    rec = _record()                     # a program without the counter
    assert read("lock_wait_us_per_stack", rec) is None
    rec = RunRecord(cell="c", seconds=1.0,
                    dispatch_before={"dispatches": 5, "lock_wait_s": 1.0},
                    dispatch_after={"dispatches": 5, "lock_wait_s": 1.0})
    assert read("lock_wait_us_per_stack", rec) is None


def test_unattributed_idle_is_the_gaps_named_by_no_inner_span():
    rec = _record()
    rec.trace["idle_gaps"] = [
        ["bench.stack_task > stack.prep", 2.0],
        ["bench.stack_task > DevicePut", 0.6],
        ["bench.stack_task", 0.3],
        ["no benchmark span", 0.1]]
    assert read("idle_unattributed_share", rec) == pytest.approx(
        100 * 0.4 / 3.0)
    rec.trace["idle_gaps"] = []
    assert read("idle_unattributed_share", rec) is None
    rec.trace = None
    assert read("idle_unattributed_share", rec) is None
