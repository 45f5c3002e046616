"""The catalog of FIT files, made from the run's seed.

Every file is ``tiles_per_file`` float32 cutouts of ``roi_shape`` pixels
(150 x 100 x 100, the paper's 6 MB uncompressed FIT file).  Drawing 1.5 M
normals per file would make set-up the larger part of a run, so one pool of
float32 normals is drawn once from the seed and ``distinct_files`` arrays of
its own are copied out of it, each from a window of the pool that starts at
``(a * k + b) mod span``, with ``a`` coprime to ``span``: no two arrays share
a start, so their bytes differ, and every array is memory of its own, so a
task reads as many distinct bytes as the files it names.  File ``fid`` is
array ``fid mod distinct_files``: the files of one segment are consecutive
ids, so as long as a segment reads no more files than there are arrays,
every file of a segment has bytes of its own.  The plain reference reads the
same arrays.
"""
from __future__ import annotations

import math

import numpy as np

#: float32 values in the pool (64 MB): the windows of 1.5 M values start
#: anywhere in the first 14.5 M of them
POOL_VALUES = 16_000_000
#: pixel level and spread of the cutouts (sky-subtracted counts)
PIXEL_MEAN = 500.0
PIXEL_STD = 100.0


def oid_of(fid: int) -> str:
    """The object id of file ``fid``; its only integer is ``fid``."""
    return f"fit.{fid}"


def fid_of(oid: str) -> int:
    return int(oid.rsplit(".", 1)[1])


class Catalog:
    """Seeded FIT files: ``file(fid)`` is a read-only (N, H, W) array."""

    def __init__(self, seed: int, tiles_per_file: int,
                 roi_shape: tuple[int, int], distinct_files: int) -> None:
        self.shape = (tiles_per_file, *roi_shape)
        self.file_values = math.prod(self.shape)
        rng = np.random.default_rng([seed, 0xF17])
        pool = rng.standard_normal(POOL_VALUES, dtype=np.float32)
        pool *= np.float32(PIXEL_STD)
        pool += np.float32(PIXEL_MEAN)
        self.span = POOL_VALUES - self.file_values
        a = int(rng.integers(self.span // 3, self.span))
        while math.gcd(a, self.span) != 1:
            a += 1
        self._a, self._b = a, int(rng.integers(0, self.span))
        self.arrays = np.empty((distinct_files, *self.shape), np.float32)
        flat = self.arrays.reshape(distinct_files, self.file_values)
        for k in range(distinct_files):
            off = self.offset(k)
            flat[k] = pool[off:off + self.file_values]
        self.arrays.flags.writeable = False

    @property
    def distinct_files(self) -> int:
        return self.arrays.shape[0]

    def offset(self, k: int) -> int:
        """Where array ``k`` starts in the pool."""
        return (self._a * k + self._b) % self.span

    def file(self, fid: int) -> np.ndarray:
        return self.arrays[fid % self.distinct_files]

    @property
    def file_bytes(self) -> int:
        return self.file_values * 4
