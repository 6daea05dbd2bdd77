"""Periodic Poisson solve of a real field: the program's planner, the
seeded input, and the plain float64 reference.

One solve is u = laplacian^-1 f on the unit torus: the Green's function
G = -1/|k|^2 with k_d = 2*pi*f_d (f_d the signed integer frequency of
axis d) and G(0) = 0, applied to the half spectrum of the real field.
The reference imports nothing of the program: ``scipy.fft.rfftn``, G
written out here, ``scipy.fft.irfftn``, all in float64.
"""

from __future__ import annotations

import os

import numpy as np

PLANNER = "solve_poisson"
#: An operator plan runs its forward transform first (FFTW sign -1).
DIRECTION = {"solve": -1}
#: One solve is an r2c plus a c2r, 2 x 2.5 N log2 N: the c2c count that
#: ``roofline.transform_flops(shape, False)`` gives, so ``gflops`` stays
#: heFFTe's nominal count and reads on the same scale as a c2c cell's.
REAL = False


def _mix(x):
    """A 32-bit integer hash (``lowbias32``, C. Wellons' hash-prospector);
    the one ``c2c.py`` uses."""
    import jax.numpy as jnp

    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def make_input(words, shape):
    """A float32 field uniform in [-1, 1) from a hash of each element's
    index and the first seed word: the real part of ``c2c.py``'s input
    for the same seed, made per element under ``jit``."""
    import jax
    import jax.numpy as jnp

    idx = jnp.zeros(shape, jnp.uint32)
    for axis in range(len(shape)):  # row-major index, mod 2^32
        idx = idx * jnp.uint32(shape[axis]) + jax.lax.broadcasted_iota(
            jnp.uint32, shape, axis)
    x = _mix(_mix(idx) + words[0])
    return (x >> 8).astype(jnp.float32) * (2.0 / 2**24) - 1.0


def reference(step: str, x: np.ndarray) -> np.ndarray:
    """``irfftn(G * rfftn(x))`` in float64 for the one step, ``solve``;
    G applied plane by plane so that no second full-size array is
    made."""
    import scipy.fft

    workers = os.cpu_count() or 1
    n0, n1, n2 = x.shape
    spec = scipy.fft.rfftn(np.asarray(x, np.float64), workers=workers)
    k0 = 2 * np.pi * np.fft.fftfreq(n0, 1.0 / n0)
    k1 = 2 * np.pi * np.fft.fftfreq(n1, 1.0 / n1)
    k2 = 2 * np.pi * np.fft.rfftfreq(n2, 1.0 / n2)
    k12 = k1[:, None] ** 2 + k2[None, :] ** 2
    for i in range(n0):
        ksq = k0[i] ** 2 + k12
        if i == 0:
            ksq[0, 0] = np.inf  # the zero mode: G(0) = 0
        spec[i] *= -1.0 / ksq
    return scipy.fft.irfftn(spec, s=(n0, n1, n2), axes=(0, 1, 2),
                            overwrite_x=True, workers=workers)
