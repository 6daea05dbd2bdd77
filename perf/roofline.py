"""Algorithmic work of a transform, from its shapes alone, and the least
time a chip could take for it.

The counts depend on the plan's global input and output shapes and
dtypes, never on what the compiler made of them, so every
implementation of a transform is held to the same numbers.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).with_name("peaks.json")


def peak(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind`` (``peaks.json``); a kind
    the table lacks is an error, never a default."""
    kinds = json.loads(PEAKS.read_text())["kinds"]
    if device_kind not in kinds:
        raise ValueError(f"no peaks for device_kind {device_kind!r} in "
                         f"{PEAKS.name}: add it there with its source")
    return kinds[device_kind]


def transform_flops(shape, real: bool) -> float:
    """Nominal flops of one 3D transform: 5 N log2 N for c2c, half of it
    for r2c or c2r (heFFTe's ``speed3d`` convention,
    ``fftSpeed3d_c2c.cpp:128``; ``utils/timing.gflops`` in the program)."""
    n = math.prod(shape)
    return (2.5 if real else 5.0) * n * math.log2(n)


def min_hbm_bytes(in_shape, in_dtype, out_shape, out_dtype) -> float:
    """Least HBM traffic of one transform: its input read once and its
    output written once. Any implementation moves at least this; the
    3-pass stream that ``bench.py::_roofline`` assumed can be beaten by
    one that keeps a whole plane in VMEM, so it bounds nothing."""
    return float(math.prod(in_shape) * np.dtype(in_dtype).itemsize
                 + math.prod(out_shape) * np.dtype(out_dtype).itemsize)


def min_seconds(flops: float, hbm_bytes: float, chip: dict) -> float:
    """The roofline: the larger of flops at the bf16 peak and bytes at
    the HBM bandwidth (for a 3D FFT the bytes term is the larger by two
    orders of magnitude)."""
    return max(flops / chip["bf16_flops_per_s"],
               hbm_bytes / chip["hbm_bytes_per_s"])
