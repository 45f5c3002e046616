"""Each metric reader on a synthetic run: offered stacks with their stamps
and ledgers, the runtime's events, dispatch counters and a reduced trace."""
from types import SimpleNamespace

import pytest

from bench.lib import layout
from bench.lib.drive import Offered, RunRecord


def _task(i, *, submit, dispatch, end, hits=1, misses=0, c2c=0,
          state="done"):
    return SimpleNamespace(
        tid=f"t{i}", submit_time=submit, dispatch_time=dispatch,
        end_time=end, state=SimpleNamespace(value=state), cache_hits=hits,
        peer_hits=0, cache_misses=misses, bytes_cache_to_cache=c2c)


def _record(kind="backlog"):
    rec = RunRecord(cell="c", seconds=10.0, traffic_kind=kind,
                    window=(100.0, 110.0), setup_s=12.5)
    # 40 stacks due 0.25 s apart; stack i waits i ms, runs 5 ms; the last
    # four end after the window; one in ten misses; stack 7 failed
    for i in range(40):
        due = 100.0 + 0.25 * i + (1.0 if i >= 36 else 0.0)
        t = _task(i, submit=due + 0.0005, dispatch=due + 0.0005 + i * 1e-3,
                  end=due + 0.0055 + i * 1e-3, hits=0 if i % 10 == 0 else 1,
                  misses=1 if i % 10 == 0 else 0, c2c=6_000_000 * (i % 10 == 0),
                  state="failed" if i == 7 else "done")
        rec.offered.append(Offered(t, (i,), due))
    rec.events = []
    for i in range(40):
        rec.events += [{"kind": "exec_start", "tid": f"t{i}", "t": float(i)},
                       {"kind": "exec_end", "tid": f"t{i}",
                        "t": float(i) + 0.004 + 1e-4 * i}]
    rec.dispatch_before = {"dispatches": 100, "lock_hold_s": 1.0}
    rec.dispatch_after = {"dispatches": 140, "lock_hold_s": 1.008}
    rec.notes["drained_at"] = 111.0
    rec.trace = {"window_s": 2.0, "busy_s": 0.05, "kernel_calls": 100,
                 "kernel_s": 100 * 23.2e-6}
    rec.kernel_cost = {"min_s": 7.3778e-6, "bound": "bytes"}
    return rec


def read(name, rec):
    return layout.reader(name).read(rec)


def test_throughput_counts_what_completed_inside_the_window():
    # stacks 0..35 end inside the window, stack 7 failed: 35 in 10 s
    assert read("stacks_per_s", _record()) == pytest.approx(3.5)


def test_setup_is_what_the_run_measured():
    assert read("setup_s", _record()) == 12.5


def test_cache_hit_ratio_over_reads_of_stacks_in_the_window():
    # 35 stacks: 0, 10, 20, 30 missed
    assert read("cache_hit_ratio", _record()) == pytest.approx(100 * 31 / 35)


def test_pump_time_per_dispatch():
    assert read("pump_us_per_stack", _record()) == pytest.approx(200.0)


def test_task_time_from_the_runtime_events():
    rec = _record()
    done = [i for i in range(36) if i != 7]
    expect = sorted(4.0 + 0.1 * i for i in done)
    assert read("task_ms_p50", rec) == pytest.approx(
        (expect[17] + expect[16]) / 2 if len(expect) % 2 == 0 else expect[17])
    rec.events = None
    assert read("task_ms_p50", rec) is None


def test_cache_to_cache_bytes_per_stack():
    assert read("c2c_MB_per_stack", _record()) == pytest.approx(4 * 6 / 35)


def test_kernel_roofline_and_device_idle_from_the_trace():
    rec = _record()
    assert read("stack_kernel_roofline", rec) == pytest.approx(
        100 * 7.3778 / 23.2)
    assert read("device_idle_share", rec) == pytest.approx(97.5)
    rec.trace = None
    assert read("stack_kernel_roofline", rec) is None
    assert read("device_idle_share", rec) is None


def test_open_loop_tails_count_every_stack_due():
    rec = _record("poisson")
    lat = sorted([5.5 + i for i in range(40) if i != 7]
                 + [1e3 * (111.0 - (100.0 + 0.25 * 7))])
    import numpy as np
    assert read("stack_p95_ms", rec) == pytest.approx(
        float(np.percentile(lat, 95)))
    assert read("gen_lag_p95_ms", rec) == pytest.approx(0.5)
    waits = [float(i) for i in range(40) if i != 7]
    assert read("queue_wait_p95_ms", rec) == pytest.approx(
        float(np.percentile(waits, 95)))
    assert read("task_ms_p95", rec) == pytest.approx(float(np.percentile(
        [4.0 + 0.1 * i for i in range(40) if i != 7], 95)))


def test_open_loop_readers_have_nothing_to_read_in_a_closed_loop():
    rec = _record("backlog")
    assert read("stack_p95_ms", rec) is None
    assert read("gen_lag_p95_ms", rec) is None


@pytest.mark.parametrize("name", sorted(p.stem for p in
                                        (layout.BENCH / "metrics").glob("*.py")))
def test_every_reader_reads_an_empty_run_as_nothing_or_a_number(name):
    rec = RunRecord(cell="c", seconds=1.0, window=(0.0, 1.0))
    value = read(name, rec)
    assert value is None or isinstance(value, float)
