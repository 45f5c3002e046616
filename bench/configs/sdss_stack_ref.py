"""Plain reference of one SDSS stack, for both sdss-fit configurations.

Raicu et al. (arXiv 0808.3546, section 5.2): each cutout is calibrated,
``(roi - sky) * cal``, shifted by a sub-pixel offset so the object centre
lands on a whole pixel (bilinear interpolation; the edge row and column are
repeated), and the cutouts are averaged (doStacking).  The configuration
files state the calibration this deployment uses: the sky level is
``sky_fraction`` of the cutout's mean, ``cal`` is 1, and the offsets of a
stack are the first values of ``numpy.random.default_rng([shift_seed,
*file ids])``, rounded to float32.

Written straight from that description in NumPy, one stack at a time; it
imports nothing of the system under test.  It computes in float64 unless
``dtype`` names a lower precision for the inputs and every operation, which
is the control.
"""
from __future__ import annotations

import numpy as np


def offsets(fids, n: int, shift_seed: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng([shift_seed, *fids])
    dy = rng.random(n).astype(np.float32)
    dx = rng.random(n).astype(np.float32)
    return dy, dx


def stack(tiles: np.ndarray, fids, *, sky_fraction: float, shift_seed: int,
          dtype=np.float64) -> np.ndarray:
    """The mean coadd of ``tiles`` (N, H, W), the cutouts of files ``fids``
    in order."""
    n = tiles.shape[0]
    dy32, dx32 = offsets(fids, n, shift_seed)
    x = tiles.astype(dtype)
    sky = (tiles.astype(np.float64).mean(axis=(1, 2))
           * sky_fraction).astype(dtype)[:, None, None]
    dy, dx = dy32.astype(dtype)[:, None, None], dx32.astype(dtype)[:, None, None]
    one = np.asarray(1, dtype)
    img = (x - sky) * one
    down = np.concatenate([img[:, :1], img[:, :-1]], axis=1)
    right = np.concatenate([img[:, :, :1], img[:, :, :-1]], axis=2)
    downright = np.concatenate([down[:, :, :1], down[:, :, :-1]], axis=2)
    shifted = ((one - dy) * (one - dx) * img + (one - dy) * dx * right
               + dy * (one - dx) * down + dy * dx * downright)
    return shifted.sum(axis=0, dtype=dtype) / np.asarray(n, dtype)
