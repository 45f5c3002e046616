"""The comparison that decides ``correct``.

Once the window has closed and every offered stack has come (or the drain
bound has passed), a sample of the stacks the window completed is drawn from
the seed, a few through each input path the run used (a local cache hit, a
peer fetch, a store read) and the rest at random.  Each stacked image the
system produced is compared with the plain reference run over the files the
stack named, in their order: the number compared is the widest pixel gap, as
a share of the reference image's largest pixel, over the sample.  Beside it,
counts that must be 0: stacks that failed, stacks that never came, sampled
images that could not be read back.
"""
from __future__ import annotations

import numpy as np

SOURCES = ("local", "peer", "store")


def source(task) -> str:
    """The slowest path any input of the task took: store, then peer, then
    local, so "local" means every input was a local hit.  Of the inputs the
    task's ledger counts as misses, those that were no peer hit came from
    the store."""
    if task.cache_misses > task.peer_hits:
        return "store"
    if task.peer_hits:
        return "peer"
    return "local"


def draw(offered: list, seed: int, n: int, per_source: int) -> list:
    """The stacks to check: up to ``per_source`` of each input path, then
    uniform draws to ``n``, all from the seed."""
    rng = np.random.default_rng([seed, 0xC4EC])
    done = [o for o in offered if o.task.state.value == "done"]
    picked: dict[int, object] = {}
    for kind in SOURCES:
        pool = [i for i, o in enumerate(done) if source(o.task) == kind]
        for i in rng.permutation(pool)[:per_source]:
            picked[int(i)] = done[int(i)]
    rest = [i for i in range(len(done)) if i not in picked]
    for i in rng.permutation(rest)[:max(n - len(picked), 0)]:
        picked[int(i)] = done[int(i)]
    return [picked[i] for i in sorted(picked)]


def gap(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(got.astype(np.float64) - ref))
                 / np.max(np.abs(ref)))


def reference_stack(refmod, catalog, cfg: dict, fids, dtype=np.float64):
    c = cfg["calibration"]
    tiles = np.concatenate([catalog.file(f) for f in fids])
    return refmod.stack(tiles, list(fids), sky_fraction=c["sky_fraction"],
                        shift_seed=c["shift_seed"], dtype=dtype)


def compare(sample: list, results: dict, refmod, catalog, cfg: dict) -> dict:
    """Widest gap over the sample, and what could not be compared."""
    worst, missing, by_source = 0.0, 0, {k: 0 for k in SOURCES}
    for o in sample:
        got = results.get(o.task.tid)
        if got is None or got.shape != tuple(cfg["roi_shape"]):
            missing += 1
            continue
        by_source[source(o.task)] += 1
        worst = max(worst, gap(got, reference_stack(refmod, catalog, cfg,
                                                    o.fids)))
    return {"pixel_gap": worst, "missing": missing, "compared": by_source}


def control_gap(sample: list, refmod, catalog, cfg: dict, dtype) -> float:
    """The same number for the reference itself in a lower precision."""
    return max(gap(np.asarray(reference_stack(refmod, catalog, cfg, o.fids,
                                              dtype), np.float64),
                   reference_stack(refmod, catalog, cfg, o.fids))
               for o in sample)
