"""Whole runs of the harness on the CPU, with its look for a chip skipped:
a sound run is correct, and the bfloat16 control and each fault the cells
can have, put in the timed path (``bench/tests/faults.py``), make
``correct`` false.  Also: the
command exits non-zero, with no result, where there is no TPU."""
import copy
import json
import os
import subprocess
import sys

import pytest

from bench.lib import check, layout
from bench.run import execute

B = layout.load_benchmark()
SEED = 2**31 + 4242


@pytest.fixture(autouse=True)
def _environment():
    """A run sets the fleet's environment variables; put them back."""
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


#: the four-host cell, kept out of BENCHMARK.json until its hosts survive
#: set-up on the chip (PERF.md, Open questions); its files stay
FLEET = {"name": "sdss-l30-fleet4", "config": "sdss-fit-fleet4",
         "traffic": "l30-catalog-backlog", "chips": 4}


def _small(cell_name, hosts=0, k=1):
    if hosts:
        cell = FLEET
        cfg = json.loads((layout.BENCH / "configs" /
                          f"{cell['config']}.json").read_text())
    else:
        cell = layout.cell(B, cell_name)
        cfg = copy.deepcopy(layout.config(B, cell["config"]))
    cfg["correctness"].update(sample=8, per_source=2)
    # a small catalog: 8 files of bytes of their own, 30 reads each; a stack
    # of k files coadds as many cutouts as a stack of one
    cfg["files_per_stack"] = k
    cfg["assumed"]["distinct_files"] = 8
    cfg["assumed"]["tiles_per_file"] //= k
    if hosts:
        cfg["spec"].update(hosts=hosts, threads_per_host=1)
        cfg["spec"]["cluster"]["n_nodes"] = hosts
    traffic = dict(layout.traffic(cell["traffic"]), warmup_stacks=8,
                   segment={"stacks": 240, "files": 8})
    return cell, cfg, traffic


def _run(cell, cfg, traffic, trace=False):
    return execute(B, cell, cfg, traffic, SEED, 0.5, trace,
                   require_chip=False)


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    root = layout.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR="")
    p = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         B["workloads"][0]["name"], "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no chip" in p.stderr


@pytest.mark.parametrize("k", [1, 3])
def test_a_sound_one_chip_run_is_correct_and_names_its_device(k):
    out = _run(*_small("sdss-l30-backlog", k=k))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    assert {"stacks_per_s", "setup_s"} <= set(out["metrics"])
    assert list(out)[-1] == "checks"
    assert out["checks"]["pixel_gap"]["value"] < \
        out["checks"]["pixel_gap"]["limit"]


def test_a_traced_run_reports_the_per_layer_metrics_it_can_read():
    out = _run(*_small("sdss-l30-backlog"), trace=True)
    assert out["correct"] is True
    # no device in a CPU trace: the device readers find nothing to read
    assert {"cache_hit_ratio", "task_ms_p50", "pump_us_per_stack"} <= \
        set(out["metrics"])
    assert "device_idle_share" not in out["metrics"]


@pytest.mark.parametrize("fault,check", [
    ("bf16", "pixel_gap"),
    ("half_batch", "pixel_gap"),
    ("altered", "pixel_gap"),
    ("dropped", "unread"),
])
def test_a_fault_in_the_timed_path_makes_the_run_incorrect(fault, check):
    cell, cfg, traffic = _small("sdss-l30-backlog")
    cfg["task_fn"] = f"bench.tests.faults:{fault}"
    out = _run(cell, cfg, traffic)
    assert out["correct"] is False
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]


@pytest.mark.parametrize("fault", ["input_dropped", "inputs_reversed",
                                   "input_replaced"])
def test_a_fault_in_a_stack_of_several_files_makes_the_run_incorrect(fault):
    cell, cfg, traffic = _small("sdss-l30-backlog", k=3)
    cfg["task_fn"] = f"bench.tests.faults:{fault}"
    out = _run(cell, cfg, traffic)
    assert out["correct"] is False
    assert out["checks"]["pixel_gap"]["value"] > \
        out["checks"]["pixel_gap"]["limit"]


@pytest.mark.parametrize("hits,peers,misses,path", [
    (1, 0, 0, "local"), (0, 1, 1, "peer"), (0, 0, 1, "store"),
    (3, 0, 0, "local"), (2, 1, 1, "peer"), (2, 0, 1, "store"),
    (1, 1, 2, "store"), (0, 3, 3, "peer"),
])
def test_a_stacks_input_path_is_the_slowest_any_of_its_files_took(
        hits, peers, misses, path):
    from repro.core.objects import Task

    k = hits + misses
    t = Task(inputs=tuple(f"fit.{i}" for i in range(k)))
    t.cache_hits, t.peer_hits, t.cache_misses = hits, peers, misses
    assert check.source(t) == path


@pytest.mark.parametrize("k,fault", [(1, None), (1, "dropped"), (3, None)])
def test_fleet_pixels_come_back_from_the_host_that_caches_them(k, fault):
    cell, cfg, traffic = _small("sdss-l30-fleet4", hosts=2, k=k)
    if fault:
        cfg["task_fn"] = f"bench.tests.faults:{fault}"
    out = _run(cell, cfg, traffic)
    assert out["device"]["count"] == 2
    assert out["correct"] is (fault is None)
