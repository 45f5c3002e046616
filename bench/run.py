"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets the cell's configuration up on the chip(s) it asks for, warms it up,
offers the cell's traffic for ``--seconds`` (stacks of the configuration's
``files_per_stack`` files each), waits for every stack offered, reads the
device's peak memory, frees the system, and checks a sample of the stacked
images against the plain reference over the same files.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, read from a profiled stretch and the runtime's events),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared, beside its limit.  The same checks are the last lines of
standard error.  Without the TPU chips the cell asks for it exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _paths() -> None:
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def _say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


def one_chip_device(chips: int, require_chip: bool) -> dict:
    import jax

    from bench.lib.drive import NoChip

    devs = jax.devices()
    d = {"platform": devs[0].platform, "kind": devs[0].device_kind,
         "count": len(devs)}
    if require_chip and (d["platform"] != "tpu" or d["count"] < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{d['count']} {d['platform']} device(s)")
    return d


def fleet_device(rt, hosts: int, chips: int, require_chip: bool) -> dict:
    """What the fleet's hosts said of their chips in their hello."""
    from bench.lib.drive import NoChip

    devs = [h.device or {} for h in rt.manager.live_handles()]
    if len(devs) < hosts:
        raise RuntimeError(f"the fleet lost {hosts - len(devs)} of its "
                           f"{hosts} hosts in set-up")
    files = {tuple(d.get("chip_files") or ()) for d in devs}
    d = {"platform": devs[0].get("platform"), "kind": devs[0].get("kind"),
         "count": len(devs)}
    if require_chip and (any(x.get("platform") != "tpu" for x in devs)
                         or len(files) < chips or () in files):
        raise NoChip(f"the cell needs {chips} TPU chips, one per host; the "
                     f"hosts hold {devs}")
    return d


def execute(bench: dict, cell: dict, cfg: dict, traffic: dict, seed: int,
            seconds: float, trace: bool, *, require_chip: bool = True,
            t_start: float | None = None, keep: dict | None = None) -> dict:
    """One run; returns the result line as a dict."""
    from bench.lib import check, cost, drive, layout

    t_start = drive.process_start() if t_start is None else t_start
    fleet = cfg["spec"].get("hosts", 0) > 0
    chips = int(cell["chips"])
    if not fleet:
        device = one_chip_device(chips, require_chip)
    elif require_chip and not drive.has_accelerator_files():
        raise drive.NoChip("no TPU device files on this machine")
    t_ready = time.time()
    run = drive.Run(cell["name"], cfg, traffic, seed, seconds, trace)
    rec, c = run.rec, cfg["correctness"]
    try:
        run.setup()
        if fleet:
            device = fleet_device(run.system.rt, cfg["spec"]["hosts"], chips,
                                  require_chip)
        if require_chip:
            n = cfg["assumed"]["tiles_per_file"] * cfg["files_per_stack"]
            h, w = cfg["roi_shape"]
            t_min, bound = cost.min_seconds(n, h, w, cost.peaks(device["kind"]))
            rec.kernel_cost = {"min_s": t_min, "bound": bound}
        rec.setup_s = time.time() - t_start
        _say(f"set-up {rec.setup_s:.3f} s ({t_ready - t_start:.3f} s to the "
             f"device check, then {rec.notes['setup_s']}); window {seconds} s"
             f" of {cell['traffic']} on {cell['config']}")
        run.window()
        drained = run.drain()
        rec.notes["drained_at"] = time.monotonic()
        device["memory_peak_bytes"] = _memory_peak(run, fleet)
        trace_path = run.trace_path() if trace else None
        sample = check.draw(rec.offered, seed, c["sample"], c["per_source"])
        results = run.results(sample)
        if keep is not None:
            keep.update(catalog=run.catalog, sample=sample, record=rec)
    finally:
        run.close()
    try:
        if trace_path is not None:
            from bench.lib.xtrace import reduce_trace

            rec.trace = reduce_trace(trace_path, cfg["kernel_pattern"])
        if trace and not fleet and rec.trace is None and require_chip:
            raise RuntimeError(f"no device trace was read from {trace_path}")
        if rec.trace is not None:
            device["busy_s"] = rec.trace["busy_s"]
            device["window_s"] = rec.trace["window_s"]
        metrics = {}
        for m in layout.metrics_of(bench, cell["name"], trace):
            value = layout.reader(m["name"]).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        stacks = rec.stacks()
        n_failed = sum(1 for s in stacks if s["state"] == "failed")
        n_lost = sum(1 for s in stacks if not s["ok"]) - n_failed
        cmp = check.compare(sample, results, layout.reference(cfg),
                            run.catalog, cfg)
        checks = {
            "pixel_gap": {"value": cmp["pixel_gap"],
                          "limit": c["pixel_gap_limit"]},
            "failed": {"value": n_failed, "limit": 0},
            "never_came": {"value": n_lost, "limit": 0},
            "unread": {"value": cmp["missing"], "limit": 0},
        }
        correct = (drained and sum(cmp["compared"].values()) > 0
                   and all(v["value"] <= v["limit"] for v in checks.values()))
        _describe(rec, stacks, cmp["compared"])
        out = {"correct": bool(correct), "attempted": len(stacks),
               "failed": n_failed + n_lost, "metrics": metrics,
               "device": device}
        if trace and rec.trace is not None:
            out["breakdown"] = {
                "device_ops": [list(x) for x in rec.trace["device_ops"][:10]],
                "idle_gaps": [list(x) for x in rec.trace["idle_gaps"][:10]]}
        out["checks"] = checks
        return out
    finally:
        run.cleanup()


def _memory_peak(run, fleet: bool) -> int:
    """Peak bytes in use on the fullest chip."""
    if fleet:
        return max((int(p.get("memory_peak_bytes", 0))
                    for p in run.probe_hosts().values()), default=0)
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _describe(rec, stacks: list, compared: dict) -> None:
    """What a reader of standard error needs to judge the run."""
    import numpy as np

    t0, t1 = rec.window
    done = [x for x in stacks if x["ok"]]
    per_s = np.bincount([int(x["end"] - t0) for x in done
                         if t0 <= x["end"] < t1],
                        minlength=max(int(np.ceil(t1 - t0)), 1))
    held0 = rec.dispatch_before.get("lock_hold_s", 0)
    held = rec.dispatch_after.get("lock_hold_s", 0) - held0
    _say(f"host during the window: {rec.notes.get('host')}; pump held the "
         f"runtime lock {held:.3f} s")
    _say(f"completed per second of the window: {per_s.tolist()}")
    marks = [held0, *rec.lock_hold_by_s]
    _say("pump lock ms per second: "
         f"{[round(1e3 * (b - a), 1) for a, b in zip(marks, marks[1:])]}")
    gc_s = np.zeros(len(per_s))
    for start, secs, _ in rec.gc_pauses:
        gc_s[min(max(int(start - t0), 0), len(gc_s) - 1)] += secs
    longest = sorted(rec.gc_pauses, key=lambda p: -p[1])[:3]
    _say(f"collector pauses: {len(rec.gc_pauses)} "
         f"({sum(p[2] == 2 for p in rec.gc_pauses)} of generation 2), ms per "
         f"second {[round(1e3 * float(x), 1) for x in gc_s]}, longest "
         f"{[(round(p[0] - t0, 2), round(1e3 * p[1], 1), p[2]) for p in longest]}")
    _say(f"offered {len(stacks)} stacks; smallest backlog {rec.min_backlog};"
         f" compiles in the window {rec.compiles_in_window}; compared "
         f"{compared}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    from bench.lib import drive, layout

    t_start = drive.process_start()
    drive.compile_cache_env(ROOT)
    bench = layout.load_benchmark()
    cell = layout.cell(bench, args.workload)
    cfg = layout.config(bench, cell["config"])
    traffic = layout.traffic(cell["traffic"])
    try:
        out = execute(bench, cell, cfg, traffic, args.seed, args.seconds,
                      bool(args.trace), t_start=t_start)
    except drive.NoChip as e:
        _say(f"no chip: {e}")
        return 3
    for name, v in out["checks"].items():
        _say(f"check {name} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
