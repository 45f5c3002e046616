"""The knee of an open-loop cell: the highest rate the system sustains.

  python3 bench/sweep.py --workload <cell> --rates 300,350,... [--seconds 8]

Runs the cell once per rate in one process, each with the cell's traffic at
that rate, and prints per rate: stacks completed per second in the window,
the median and 95th percentile of stack latency, and the median queue wait
of the first and the last quarter of the arrivals.  A rate is sustained
when the last quarter waits no longer than the first, give or take noise;
the knee is the highest such rate.  The rate a traffic file offers is set
from it by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def summarize(rec) -> dict:
    import numpy as np

    st = sorted(rec.stacks(), key=lambda s: s["due"])
    t0, t1 = rec.window
    lat = [s["end"] - s["due"] for s in st if s["ok"]]
    wait = [s["dispatch"] - s["submit"] for s in st if s["ok"]]
    q = max(len(wait) // 4, 1)
    done = sum(1 for s in st if s["ok"] and t0 <= s["end"] <= t1)
    return {"offered_per_s": len(st) / (t1 - t0),
            "done_per_s": done / (t1 - t0),
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p95_ms": 1e3 * float(np.percentile(lat, 95)),
            "wait_first_q_ms": 1e3 * float(np.median(wait[:q])),
            "wait_last_q_ms": 1e3 * float(np.median(wait[-q:]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=2**31 + 77)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.lib import drive, layout
    from bench.run import execute

    drive.compile_cache_env(ROOT)
    bench = layout.load_benchmark()
    cell = layout.cell(bench, args.workload)
    cfg = layout.config(bench, cell["config"])
    traffic = layout.traffic(cell["traffic"])
    for rate in (float(r) for r in args.rates.split(",")):
        tr = dict(traffic, arrivals=dict(traffic["arrivals"], rate_per_s=rate))
        kept = {}
        out = execute(bench, cell, cfg, tr, args.seed, args.seconds, False,
                      keep=kept)
        print(json.dumps(dict(rate=rate, correct=out["correct"],
                              **summarize(kept["record"]))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
