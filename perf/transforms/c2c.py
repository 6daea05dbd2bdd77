"""Complex-to-complex 3D DFT: the program's planner for each direction,
the seeded input, and the plain float64 reference.

The reference imports nothing of the program: it is NumPy's pocketfft
(through ``scipy.fft`` for its worker threads) in complex128, the
unnormalized DFT of the FFTW sign convention in both directions.
"""

from __future__ import annotations

import os

import numpy as np

PLANNER = "plan_dft_c2c_3d"
#: FFTW signs: forward -1, backward +1 (the planner's ``direction``).
DIRECTION = {"forward": -1, "backward": 1}
REAL = False


def _mix(x):
    """A 32-bit integer hash (``lowbias32``, C. Wellons' hash-prospector)."""
    import jax.numpy as jnp

    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def make_input(words, shape):
    """Real and imaginary parts uniform in [-1, 1), complex64, from two
    32-bit seed words. A hash of each element's index, so that under
    ``jit`` every chip makes its own shard and nothing else (``jax.random``
    makes the whole array per chip under ``rbg``, and compiles for 25 s
    at 512^3 under threefry: my chip run and described-v5e compiles,
    PR 22)."""
    import jax
    import jax.numpy as jnp

    idx = jnp.zeros(shape, jnp.uint32)
    for axis in range(len(shape)):  # row-major index, mod 2^32
        idx = idx * jnp.uint32(shape[axis]) + jax.lax.broadcasted_iota(
            jnp.uint32, shape, axis)

    def part(w):
        x = _mix(_mix(idx) + w)
        return (x >> 8).astype(jnp.float32) * (2.0 / 2**24) - 1.0

    return jax.lax.complex(part(words[0]), part(words[1]))


def reference(step: str, x: np.ndarray) -> np.ndarray:
    """The unnormalized DFT of ``x`` in complex128 (``x`` may be
    overwritten)."""
    import scipy.fft

    x = np.asarray(x, np.complex128)
    workers = os.cpu_count() or 1
    if step == "forward":
        return scipy.fft.fftn(x, overwrite_x=True, workers=workers)
    return scipy.fft.ifftn(x, norm="forward", overwrite_x=True,
                           workers=workers)
