"""The one traffic generator: stacking requests in segments of Table 2 shape.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

  segment      {"stacks": S, "files": F}: S stacks over F files.  Each stack
               has a primary file, and every file is primary S // F or
               S // F + 1 times (which files get the extra read is drawn from
               the seed), in shuffled order, as ``StackingTrace`` shuffles a
               Table 2 trace.
  fresh_files  true: every segment reads F files no segment read before, so
               a segment's hit ratio does not depend on how many came before;
               false: every segment reads the same F files (a fixed catalog
               cycled in a new order).
  warmup_stacks  how many stacks of the stream run during set-up.
  arrivals     {"kind": "backlog", "low_water": L, "chunk": C}: keep at least
               L stacks queued, topping up C at a time (closed loop);
               {"kind": "poisson", "rate_per_s": R, "gap_seed": G}: an open
               loop at R stacks/s.  The gaps are one fixed multiset drawn
               from G, scaled to fill the window exactly; the run's seed
               only orders them, so every seed offers the same arrivals.

k, the files a stack reads, is the configuration's ``files_per_stack``.  A
stack reads its primary's stack group: the k consecutive file ids of the
segment that hold the primary (the last group wraps to the segment's first
ids), primary first, then the group's next ids in turn.  The stream of a
seed is the same in every run: segment ``n`` depends only on (seed, n), and
its primaries do not depend on k.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterator

import numpy as np

ARRIVAL_KINDS = ("backlog", "poisson")


@dataclass(frozen=True)
class Segment:
    index: int
    files: tuple[int, ...]      # file ids, in the order they are first put
    stacks: tuple[tuple[int, ...], ...]     # each stack's k file ids, primary
                                            # first


class Traffic:
    def __init__(self, params: dict, seed: int,
                 files_per_stack: int = 1) -> None:
        seg = params["segment"]
        self.stacks, self.files = int(seg["stacks"]), int(seg["files"])
        if not 1 <= self.files <= self.stacks:
            raise ValueError(f"a segment needs 1 <= files <= stacks: {seg}")
        self.k = int(files_per_stack)
        if not 1 <= self.k <= self.files:
            raise ValueError(f"files_per_stack {files_per_stack!r} is not in "
                             f"[1, {self.files}], the files of a segment")
        self.fresh = bool(params["fresh_files"])
        self.warmup_stacks = int(params["warmup_stacks"])
        self.arrivals = dict(params["arrivals"])
        if self.arrivals["kind"] not in ARRIVAL_KINDS:
            raise ValueError(f"unknown arrival kind {self.arrivals['kind']!r}"
                             f" (known: {ARRIVAL_KINDS})")
        self.seed = seed

    def segment(self, n: int) -> Segment:
        rng = np.random.default_rng([self.seed, 0x5E6, n])
        base = n * self.files if self.fresh else 0
        fids = np.arange(base, base + self.files)
        reads = np.repeat(fids, self.stacks // self.files)
        extra = rng.choice(fids, self.stacks % self.files, replace=False)
        reads = np.concatenate([reads, extra])
        rng.shuffle(reads)
        k, F = self.k, self.files
        stacks = tuple(tuple(base + (p - p % k + (p + j) % k) % F
                             for j in range(k))
                       for p in (int(f) - base for f in reads))
        return Segment(n, tuple(int(f) for f in fids), stacks)

    def stream(self) -> Iterator[tuple[tuple[int, ...], tuple[int, ...]]]:
        """Endless (a stack's file ids, those of them read for the first time
        in their segment)."""
        n = 0
        while True:
            seen: set[int] = set()
            for fids in self.segment(n).stacks:
                first = tuple(f for f in fids if f not in seen)
                seen.update(first)
                yield fids, first
            n += 1

    def ideal_hit_ratio(self) -> float:
        """1 - F/(S k): every read after a file's first is a hit, and every
        file of a segment is some stack's primary."""
        return 1.0 - self.files / (self.stacks * self.k)

    def arrival_offsets(self, seconds: float) -> np.ndarray:
        """Open loop: due times, in seconds after the window opens."""
        a = self.arrivals
        if a["kind"] != "poisson":
            raise ValueError("a closed-loop mix has no schedule")
        n = max(int(math.ceil(float(a["rate_per_s"]) * seconds)), 1)
        gaps = np.random.default_rng(int(a["gap_seed"])).exponential(1.0, n)
        gaps *= seconds / gaps.sum()
        np.random.default_rng([self.seed, 0xA77]).shuffle(gaps)
        return np.cumsum(gaps) - gaps[0]
