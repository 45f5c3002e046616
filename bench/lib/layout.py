"""Where the benchmark keeps what it runs, found by the names in
``BENCHMARK.json``:

  bench/configs/<config>.json     a configuration, as it is run
  bench/configs/<reference>.py    the plain reference its file names
  bench/traffic/<traffic>.json    a traffic mix's parameters
  bench/metrics/<metric>.py       the reader of one metric: ``read(record)``
                                  returns its number, or None where the run
                                  holds nothing to read

Adding a configuration, a mix or a metric is adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in bench['workloads']]})")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cfg: dict) -> ModuleType:
    return _module(BENCH / "configs" / f"{cfg['reference']}.py")


def reader(metric: str) -> ModuleType:
    return _module(BENCH / "metrics" / f"{metric}.py")


def metrics_of(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell_name`` reports: end-to-end ones with
    ``--trace 0``, per-layer ones with ``--trace 1``."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]
