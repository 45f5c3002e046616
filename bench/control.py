"""Readings that set the limit of the comparison that decides ``correct``.

  python3 bench/control.py --workload <cell> [--seeds <n>,<n>,...] \
      [--seconds 3] [--fault half_batch,altered --fault-seeds <n>,...]

For each seed, in one process: one run of the cell at its own size with a
short window, then
  * ``program``: the widest pixel gap of the sampled stacks (what a run
    compares against its limit; the lower reading);
  * ``control``: the same gap for the plain reference computed in bfloat16,
    the precision below the configuration's float32 (the upper reading);
  * ``bf16_inputs``: the reference over cutouts rounded to bfloat16 and
    summed in float64, a cheaper path a change might take;
and, for each ``--fault``, one run on each fault seed with that task fn of
``bench/tests/faults.py`` in the program's place (``bf16`` is the control
itself).  Prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: sampled stacks per seed that the controls recompute
CONTROL_STACKS = 8


def readings(bench: dict, cell: dict, cfg: dict, traffic: dict, seed: int,
             seconds: float, *, require_chip: bool = True,
             controls: bool = True) -> dict:
    """One run of the cell; the program's gap and, with ``controls``, the
    controls' gaps over the same sampled stacks."""
    import ml_dtypes
    import numpy as np

    from bench.lib import check, layout
    from bench.run import execute

    kept = {}
    out = execute(bench, cell, cfg, traffic, seed, seconds, False,
                  require_chip=require_chip, keep=kept)
    row = {"seed": seed, "correct": out["correct"],
           "program": out["checks"]["pixel_gap"]["value"],
           "checks": out["checks"]}
    if controls:
        refmod, catalog = layout.reference(cfg), kept["catalog"]
        sample = kept["sample"][:CONTROL_STACKS]
        row["control"] = check.control_gap(sample, refmod, catalog, cfg,
                                           ml_dtypes.bfloat16)
        row["bf16_inputs"] = max(
            check.gap(check.reference_stack(
                refmod, _Rounded(catalog, ml_dtypes.bfloat16), cfg, o.fids),
                check.reference_stack(refmod, catalog, cfg, o.fids))
            for o in sample)
    return row


class _Rounded:
    """A catalog whose files read rounded to ``dtype`` and back."""

    def __init__(self, catalog, dtype) -> None:
        self.catalog, self.dtype = catalog, dtype

    def file(self, fid: int):
        return self.catalog.file(fid).astype(self.dtype).astype("float32")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.lib import drive, layout

    drive.compile_cache_env(ROOT)
    bench = layout.load_benchmark()
    cell = layout.cell(bench, args.workload)
    cfg = layout.config(bench, cell["config"])
    traffic = layout.traffic(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",") if s):
        print(json.dumps(readings(bench, cell, cfg, traffic, seed,
                                  args.seconds)), flush=True)
    for fault in (f for f in args.fault.split(",") if f):
        bad = dict(cfg, task_fn=f"bench.tests.faults:{fault}")
        for seed in (int(s) for s in args.fault_seeds.split(",") if s):
            row = readings(bench, cell, bad, traffic, seed, args.seconds,
                           controls=False)
            print(json.dumps(dict(row, fault=fault)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
