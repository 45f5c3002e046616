"""The task fn a fleet cell's hosts resolve: ``bench.lib.fleet_task:stack_task``.

A task with inputs runs the configuration's own task fn (named by
``PERFBENCH_TASK_FN``) unchanged.  A task with no inputs is the benchmark's
probe: it returns what only the host process can see, its chip's peak
memory.
"""
from __future__ import annotations

import os
from typing import Any

TASK_ENV = "PERFBENCH_TASK_FN"
_FN: list = []


def _task_fn():
    if not _FN:
        from repro.fleet.host import resolve_task_fn

        _FN.append(resolve_task_fn(os.environ[TASK_ENV]))
    return _FN[0]


def _probe() -> dict:
    import jax

    from repro.fleet.host import chip_files

    stats = jax.devices()[0].memory_stats() or {}
    return {"pid": os.getpid(), "chip_files": chip_files(),
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}


def stack_task(inputs: dict) -> Any:
    if not inputs:
        return _probe()
    return _task_fn()(inputs)


def _warmup() -> dict:
    return _task_fn().warmup()


stack_task.warmup = _warmup
