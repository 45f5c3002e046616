"""Kernel cost from shapes, the table of peaks, and the trace reduction on a
trace recorded on a TPU v5e: 20 calls of ``repro.workloads.astro:stack_task``
(N=150 cutouts of 100x100), each inside a ``bench.stack_task`` span."""
from pathlib import Path

import pytest

from bench.lib import cost
from bench.lib.xtrace import op_name, reduce_trace, union

TRACE = Path(__file__).parent / "data" / "stack_probe.xplane.pb"
KERNEL = r"^stack_rois(\.\d+)?$"


def test_stacking_kernel_bytes_and_operations_from_shapes():
    assert cost.stack_bytes(150, 100, 100) == 6_042_400
    assert cost.stack_flops(150, 100, 100) == 10 * 150 * 100 * 100 + 100 * 100
    pk = cost.peaks("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9 and pk["bf16_flops_per_s"] == 197e12
    t, bound = cost.min_seconds(150, 100, 100, pk)
    assert bound == "bytes" and t == pytest.approx(6_042_400 / 819e9)


def test_an_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError, match="no peaks for device kind 'cpu'"):
        cost.peaks("cpu")


def test_op_names_and_interval_union():
    assert op_name("%stack_rois.1 = f32[100,100]{1,0} custom-call(f32[152])") \
        == "stack_rois.1"
    assert op_name("%copy.1 = f32[150] copy(%rois.1)") == "copy.1"
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduction_of_a_recorded_chip_trace():
    r = reduce_trace(str(TRACE), KERNEL)
    assert r["devices"] == 1
    # the kernel appears as the custom call ``stack_rois.1``, once a call
    assert r["kernel_calls"] == 20
    assert r["device_ops"][0][0] == "stack_rois.1"
    per_call = r["kernel_s"] / r["kernel_calls"]
    assert 15e-6 < per_call < 40e-6
    assert 0 < r["busy_s"] < r["window_s"]
    ops = dict(r["device_ops"])
    assert r["kernel_s"] <= r["busy_s"] <= 1.01 * sum(ops.values())
    assert {"copy.1", "pad.0"} <= set(ops)
    # idle time is named by the benchmark span around the task fn
    assert r["idle_gaps"][0][0].startswith("bench.stack_task")
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    t_min = cost.min_seconds(150, 100, 100, cost.peaks("TPU v5 lite"))[0]
    assert 20 < 100 * t_min / per_call < 50


def test_a_trace_without_a_tpu_reads_as_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    assert reduce_trace(str(path), KERNEL) is None
