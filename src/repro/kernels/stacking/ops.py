"""Jit'd wrapper for the stacking kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode

from .stacking import DEFAULT_BLOCK_N, stack_rois_fwd


@functools.partial(jax.jit,
                   static_argnames=("block_n", "mean", "sky_fraction"))
def stack_rois(rois, *params, block_n: int = DEFAULT_BLOCK_N,
               mean: bool = True, sky_fraction: Optional[float] = None):
    """The coadd of ``rois`` (N, H, W), mean or sum.

    ``params`` are the per-cutout ``sky, cal, dy, dx`` (N,) each.  With
    ``sky_fraction`` they are ``dy, dx`` alone, and the calibration is
    computed here, on the device, in float32: ``sky`` is ``sky_fraction`` of
    each cutout's mean and ``cal`` is 1, so the host reads no pixel.

    The kernel's custom call takes this function's name (``stack_rois.N`` in
    the compiled program), which is what a device trace finds it by: wrap
    the kernel in no other jitted function.
    """
    rois = rois.astype(jnp.float32)
    if sky_fraction is None:
        sky, cal, dy, dx = params
    else:
        dy, dx = params
        sky = jnp.mean(rois, axis=(1, 2)) * sky_fraction
        cal = jnp.ones_like(sky)
    out = stack_rois_fwd(rois, sky.astype(jnp.float32),
                         cal.astype(jnp.float32), dy.astype(jnp.float32),
                         dx.astype(jnp.float32), block_n=block_n,
                         interpret=interpret_mode())
    if mean:
        out = out / rois.shape[0]
    return out
