"""Operations and bytes the stacking kernel needs, from its shapes, and the
chip peaks they are held against (``bench/peaks.json``)."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"

#: per pixel of each cutout: calibration (subtract, multiply), the bilinear
#: shift (four products, three sums) and the running sum
FLOPS_PER_PIXEL = 2 + 7 + 1


def stack_bytes(n: int, h: int, w: int) -> int:
    """HBM bytes one call must move: the cutouts read once, the four
    per-cutout parameters (sky, cal, dy, dx), the (H, W) stack written."""
    return 4 * (n * h * w + 4 * n + h * w)


def stack_flops(n: int, h: int, w: int) -> int:
    """Arithmetic of one call: every cutout pixel, then the division by N."""
    return FLOPS_PER_PIXEL * n * h * w + h * w


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def min_seconds(n: int, h: int, w: int, pk: dict) -> tuple[float, str]:
    """The least time the chip could take for one call, and which bound
    sets it."""
    t_bytes = stack_bytes(n, h, w) / pk["hbm_bytes_per_s"]
    t_flops = stack_flops(n, h, w) / pk["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
