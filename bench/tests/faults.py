"""Task fns with one planted fault each, and the control, run in place of
the configuration's task fn (``repro.workloads.astro:stack_task``) by the
fault tests and by ``bench/control.py --fault``."""
from __future__ import annotations

import functools

import numpy as np

from repro.workloads.astro import stack_inputs, stack_task

from bench.lib import layout
from bench.lib.catalog import fid_of

#: the configuration whose reference and calibration the control uses
CONTROL_CONFIG = "sdss-fit-1chip"


@functools.cache
def _reference():
    cfg = layout.config(layout.load_benchmark(), CONTROL_CONFIG)
    return layout.reference(cfg), cfg["calibration"]


def bf16(inputs: dict) -> np.ndarray:
    """The control: the plain reference computed in bfloat16, the precision
    below the configuration's float32, handed back as float32."""
    import ml_dtypes

    ref, cal = _reference()
    tiles = np.concatenate([np.asarray(v) for v in inputs.values()])
    out = ref.stack(tiles, [fid_of(o) for o in inputs],
                    sky_fraction=cal["sky_fraction"],
                    shift_seed=cal["shift_seed"], dtype=ml_dtypes.bfloat16)
    return np.asarray(out, np.float32)


def half_batch(inputs: dict) -> np.ndarray:
    """Half of the cutouts left out; the mean taken over the rest."""
    from repro.kernels.stacking.ops import stack_rois

    tiles, params = stack_inputs(inputs)
    h = tiles.shape[0] // 2
    return np.asarray(stack_rois(tiles[:h], *(p[:h] for p in params)))


def altered(inputs: dict) -> np.ndarray:
    """One pixel of every stack moved by a thousandth where it is made."""
    out = np.array(stack_task(inputs))
    out[out.shape[0] // 2, out.shape[1] // 2] *= 1.001
    return out


def dropped(inputs: dict) -> None:
    """The stack is computed and never handed back."""
    stack_task(inputs)


def input_dropped(inputs: dict) -> np.ndarray:
    """A stack of several files made without its last file."""
    return stack_task(dict(list(inputs.items())[:-1]))


def inputs_reversed(inputs: dict) -> np.ndarray:
    """A stack of several files made from its files in reverse order."""
    return stack_task(dict(reversed(list(inputs.items()))))


def input_replaced(inputs: dict) -> np.ndarray:
    """A stack of several files whose last file's cutouts are another file
    of the segment's: the first file's, read twice."""
    oids = list(inputs)
    return stack_task({**inputs, oids[-1]: inputs[oids[0]]})


for _fn in (bf16, half_batch, altered, dropped, input_dropped,
            inputs_reversed, input_replaced):
    _fn.warmup = stack_task.warmup
