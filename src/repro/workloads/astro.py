"""The paper's own application workload: SDSS DR5 image stacking (§5).

Table 2 workload characteristics (locality -> objects/files), file sizes
(2 MB compressed GZ / 6 MB uncompressed FIT), and the §5.2 stacking-code
profile used to calibrate per-task compute in the simulator:

  * calibration+interpolation+doStacking < 1 ms
  * radec2xy ~ 10-20% of total (we use 2 ms)
  * GZ decompress is CPU-bound (~40 ms for 2 MB -> 6 MB): single-CPU GZ is
    *slower* than FIT locally, but wins at scale because it moves 3x fewer
    bytes through the saturated shared FS (Figure 7's crossover).

It also holds the application itself, as a runtime runs it: seeded catalog
files of ROI cutouts (:func:`make_tiles`) and one task fn
(:func:`stack_task`) that calibrates, shifts and coadds them through the
Pallas stacking kernel.  JAX is imported only inside the task fn, so a
fleet's central process can build the workload without touching a device.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

import numpy as np

from repro.obs.spans import span

MB = 1_000_000

# Table 2: locality -> (num objects, num files)
WORKLOADS: dict[float, tuple[int, int]] = {
    1: (111_700, 111_700),
    1.38: (154_345, 111_699),
    2: (97_999, 49_000),
    3: (88_857, 29_620),
    4: (76_575, 19_145),
    5: (60_590, 12_120),
    10: (46_480, 4_650),
    20: (40_460, 2_025),
    30: (23_695, 790),
}

GZ_BYTES = 2 * MB
FIT_BYTES = 6 * MB

# §5.2-calibrated per-task CPU costs (seconds)
RADEC2XY_S = 2e-3
STACK_MATH_S = 1e-3          # calibration + interpolation + doStacking
GZ_DECOMPRESS_S = 40e-3
ROI_SHAPE = (100, 100)       # pixels per cutout


@dataclass(frozen=True)
class StackingWorkload:
    locality: float
    n_objects: int
    n_files: int
    compressed: bool

    @property
    def file_bytes(self) -> int:
        return GZ_BYTES if self.compressed else FIT_BYTES

    @property
    def compute_seconds(self) -> float:
        cpu = RADEC2XY_S + STACK_MATH_S
        if self.compressed:
            cpu += GZ_DECOMPRESS_S
        return cpu

    @property
    def ideal_cache_hit_ratio(self) -> float:
        """Paper's Figure 10 ideal: 1 - 1/locality."""
        return 1.0 - 1.0 / self.locality if self.locality > 0 else 0.0


def workload(locality: float, compressed: bool = True,
             scale: float = 1.0) -> StackingWorkload:
    n_obj, n_files = WORKLOADS[locality]
    return StackingWorkload(locality=locality,
                            n_objects=max(int(n_obj * scale), 1),
                            n_files=max(int(n_files * scale), 1),
                            compressed=compressed)


# --------------------------------------------------------------------------
# the application: catalog files, coadd parameters, the task fn
# --------------------------------------------------------------------------

SEED = 0
#: 32-bit float ROI cutouts in one uncompressed FIT file (6 MB / 40 kB)
TILES_PER_FIT = FIT_BYTES // (ROI_SHAPE[0] * ROI_SHAPE[1] * 4)
#: a cutout's sky level, as a share of its mean
SKY_FRACTION = 0.1


def file_ids(oid: str) -> list[int]:
    """The integers of an object id: ``astro.g3.o1`` -> [3, 1]."""
    return [int(s) for s in re.findall(r"\d+", oid)]


def make_tiles(oid: str, tiles_per_file: int) -> np.ndarray:
    """A catalog file's cutouts, ``(tiles_per_file, *ROI_SHAPE)`` float32,
    derived from the file's id alone: the same every run, on every host."""
    rng = np.random.default_rng([SEED, *file_ids(oid)])
    return rng.normal(500, 100, size=(tiles_per_file, *ROI_SHAPE)) \
        .astype(np.float32)


def shifts(oids, n: int) -> tuple[np.ndarray, np.ndarray]:
    """The sub-pixel shifts (dy, dx) of a stack's ``n`` cutouts, float32 in
    [0, 1).  They are seeded by the input ids, never by a shared stream, so
    the pixels do not depend on the order in which tasks ran."""
    rng = np.random.default_rng([SEED + 1, *(i for o in oids
                                             for i in file_ids(o))])
    dy = rng.random(n).astype(np.float32)
    dx = rng.random(n).astype(np.float32)
    return dy, dx


def coadd_params(tiles: np.ndarray, oids) -> tuple[np.ndarray, ...]:
    """Per-cutout sky level, calibration and sub-pixel shift (dy, dx),
    computed on the host: the sky is ``SKY_FRACTION`` of each cutout's mean
    and the calibration is 1."""
    dy, dx = shifts(oids, tiles.shape[0])
    return (tiles.mean(axis=(1, 2)) * SKY_FRACTION,
            np.ones(tiles.shape[0], np.float32), dy, dx)


def is_stack(oid: str) -> bool:
    """Produced stacks (``astro.stack{g}``), as opposed to catalog files."""
    return oid.rsplit(".", 1)[-1].startswith("stack")


def stack_inputs(inputs: dict) -> tuple[np.ndarray, tuple[np.ndarray, ...]]:
    """What one task hands the kernel, all of it made on the host: the
    cutouts of all its inputs in a new array and their coadd parameters.
    Catalog files are calibrated (the sky from :func:`coadd_params`) and
    shifted; produced stacks (the mosaic stage) are coadded as they are.
    :func:`stack_task` computes the same thing, the catalog files' sky on
    the device."""
    oids = list(inputs)
    if all(is_stack(o) for o in oids):
        tiles = np.stack([np.asarray(v) for v in inputs.values()])
        zeros = np.zeros(tiles.shape[0], np.float32)
        return tiles, (zeros, np.ones(tiles.shape[0], np.float32), zeros,
                       zeros)
    tiles = np.concatenate([np.asarray(v) for v in inputs.values()])
    return tiles, coadd_params(tiles, oids)


def stack_task(inputs: dict) -> np.ndarray:
    """The task fn of both stages (``repro.workloads.astro:stack_task``):
    the mean coadd of every input cutout, computed by the Pallas stacking
    kernel on the process's default device.

    One catalog file goes to the kernel's call as the array it is: the host
    reads and writes none of its pixels, draws only the shifts, and the
    device computes the sky.  Several catalog files are concatenated on the
    host first, and their sky is computed on the device too; produced
    stacks are stacked on the host with :func:`stack_inputs`' parameters.
    Those host copies are the span ``prep.host_copy``.

    The task fn's three phases are spans (`repro.obs.spans`): host prep;
    the kernel's call on the host arrays, which copies them to the device
    and launches; and the wait for the result."""
    from repro.kernels.stacking.ops import stack_rois

    with span("stack.prep"):
        oids = list(inputs)
        if all(is_stack(o) for o in oids):
            with span("prep.host_copy"):
                tiles, params = stack_inputs(inputs)
            kw = {}
        else:
            arrays = [np.asarray(v) for v in inputs.values()]
            if len(arrays) == 1:
                tiles = arrays[0]
            else:
                with span("prep.host_copy"):
                    tiles = np.concatenate(arrays)
            params = shifts(oids, tiles.shape[0])
            kw = {"sky_fraction": SKY_FRACTION}
    with span("stack.launch"):
        out = stack_rois(tiles, *params, **kw)
    with span("stack.result"):
        res = np.asarray(out)
        del out         # the device buffer is released inside the span
    return res


def _host_device() -> dict:
    """Fleet-host warm-up (see ``repro.fleet.host``): start this process's
    backend and describe the device its tasks will run on."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "id": d.id,
            "hardware_id": d.local_hardware_id,
            "coords": list(getattr(d, "coords", None) or ()),
            "count": len(jax.devices())}


stack_task.warmup = _host_device
