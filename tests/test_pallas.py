"""Fused four-step Pallas kernel: correctness against numpy, executor
registration, and distributed-plan integration.

On the CPU test backend the kernel runs in Pallas interpreter mode (same
program, interpreted); the compiled Mosaic path is exercised by the on-TPU
benchmarks. Tolerances are float32-tier: the kernel is a complex64 engine
(f32 LUTs + HIGHEST-precision MXU matmuls).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from distributedfft_tpu.ops import pallas_fft
from distributedfft_tpu.ops.executors import get_executor

RTOL = 5e-5


def _rand_c64(rng, shape):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
        np.complex64
    )


def _rel_err(a, b):
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


def test_eligibility():
    assert pallas_fft.eligible(512)
    assert pallas_fft.eligible(65536)
    assert pallas_fft.eligible(1000)      # 2^3 * 5^3
    assert not pallas_fft.eligible(32)    # too small: dense matmul wins
    assert not pallas_fft.eligible(8191)  # prime: Bluestein fallback
    assert pallas_fft.split_for(512) == (16, 32)


@pytest.mark.parametrize("n", [64, 256, 512, 1000, 4096])
def test_forward_matches_numpy(n):
    rng = np.random.default_rng(7)
    x = _rand_c64(rng, (5, n))
    y = np.asarray(pallas_fft.fft_along_axis(jnp.asarray(x), 1, True))
    assert _rel_err(y, np.fft.fft(x, axis=1)) < RTOL


@pytest.mark.parametrize("n", [256, 1000])
def test_inverse_roundtrip(n):
    rng = np.random.default_rng(8)
    x = _rand_c64(rng, (3, n))
    y = pallas_fft.fft_along_axis(jnp.asarray(x), 1, True)
    r = np.asarray(pallas_fft.fft_along_axis(y, 1, False))
    assert _rel_err(r, x) < RTOL


def test_non_last_axis_and_batch_padding():
    rng = np.random.default_rng(9)
    x = _rand_c64(rng, (3, 256, 5))  # batch 15 -> padded to the tile size
    y = np.asarray(pallas_fft.fft_along_axis(jnp.asarray(x), 1, True))
    assert _rel_err(y, np.fft.fft(x, axis=1)) < RTOL


def test_fallback_for_ineligible_lengths():
    rng = np.random.default_rng(10)
    for n in (13, 8191):  # tiny and large-prime: recursive matmul path
        x = _rand_c64(rng, (2, n))
        y = np.asarray(pallas_fft.fft_along_axis(jnp.asarray(x), 1, True))
        assert _rel_err(y, np.fft.fft(x, axis=1)) < 5e-4


def test_registered_executor_multi_axis():
    rng = np.random.default_rng(11)
    ex = get_executor("pallas")
    x = _rand_c64(rng, (64, 64, 64))
    y = np.asarray(ex(jnp.asarray(x), (0, 1, 2), True))
    assert _rel_err(y, np.fft.fftn(x)) < 5e-4
    r = np.asarray(ex(jnp.asarray(y), (0, 1, 2), False))
    assert _rel_err(r, x) < 5e-4


def test_distributed_plan_with_pallas_executor():
    import jax

    import distributedfft_tpu as dfft
    from distributedfft_tpu import testing as tu

    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device mesh")
    shape = (64, 64, 64)
    mesh = dfft.make_mesh(4)
    x = tu.make_world_data(shape, dtype=np.complex64)
    fwd = dfft.plan_dft_c2c_3d(shape, mesh, direction=dfft.FORWARD,
                               dtype=jnp.complex64, executor="pallas")
    bwd = dfft.plan_dft_c2c_3d(shape, mesh, direction=dfft.BACKWARD,
                               dtype=jnp.complex64, executor="pallas")
    y = np.asarray(fwd(x))
    assert _rel_err(y, np.fft.fftn(np.asarray(x))) < 5e-4
    assert _rel_err(np.asarray(bwd(fwd(x))), np.asarray(x)) < 5e-4


@pytest.mark.parametrize("n", [131072, 90000])
def test_two_level_big_axis(n):
    """Axes beyond one kernel's reach run the two-level four-step (both DFT
    stages still fused kernels)."""
    from distributedfft_tpu.ops.pallas_fft import eligible, outer_split

    assert not eligible(n) and outer_split(n) is not None
    rng = np.random.default_rng(13)
    x = _rand_c64(rng, (2, n))
    y = np.asarray(pallas_fft.fft_along_axis(jnp.asarray(x), 1, True))
    ref = np.fft.fft(x, axis=1)
    assert _rel_err(y, ref) < 2e-4
    r = np.asarray(pallas_fft.fft_along_axis(jnp.asarray(ref.astype(np.complex64)),
                                             1, False))
    assert _rel_err(r, x) < 2e-4


def test_zero_batch_falls_back_cleanly():
    x = jnp.zeros((0, 256), jnp.complex64)
    y = pallas_fft.fft_along_axis(x, 1, True)
    assert y.shape == (0, 256)


def test_r2c_real_input_promoted_to_kernel_dtype():
    rng = np.random.default_rng(12)
    from distributedfft_tpu.ops.executors import get_c2r, get_r2c

    x = rng.standard_normal((4, 256)).astype(np.float32)
    y = np.asarray(get_r2c("pallas")(jnp.asarray(x), 1))
    assert _rel_err(y, np.fft.rfft(x, axis=1)) < RTOL
    r = np.asarray(get_c2r("pallas")(jnp.asarray(y.astype(np.complex64)), 256, 1))
    assert _rel_err(r, x) < RTOL


def test_scheduler_feeds_kernel_splits():
    """The native scheduler and the kernel's split agree on bounds."""
    from distributedfft_tpu import native

    for n in (512, 4096, 65536):
        split = pallas_fft.split_for(n)
        sched = native.schedule_axis(n, pallas_fft.MAX_FACTOR, 2)
        assert split is not None and sched is not None
        assert sorted(split) == sorted(sched) or (
            split[0] * split[1] == sched[0] * (sched[1] if len(sched) > 1 else 1)
        )


# ------------------------------------------------------- the executor

def test_pallas_executor_matches_fftn_on_trailing_plane():
    """The executor's complex64 path (one split, ``fft_planes`` on every
    axis, one combine) matches fftn on the trailing plane and on all
    three axes."""
    from distributedfft_tpu.ops.executors import get_executor

    rng = np.random.default_rng(33)
    x = (rng.standard_normal((4, 64, 64))
         + 1j * rng.standard_normal((4, 64, 64))).astype(np.complex64)
    ex = get_executor("pallas")
    got = np.asarray(ex(jnp.asarray(x), (1, 2), True))
    want = np.fft.fftn(x, axes=(1, 2))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 5e-6
    got3 = np.asarray(ex(jnp.asarray(x), (0, 1, 2), True))
    want3 = np.fft.fftn(x)
    assert np.max(np.abs(got3 - want3)) / np.max(np.abs(want3)) < 5e-6


# ------------------------------------------------------ strided axis-0 kernel

@pytest.mark.parametrize("shape", [(64, 5, 7), (128, 12), (64, 130)])
def test_fft_axis0_matches_numpy(shape):
    from distributedfft_tpu.ops import pallas_fft

    rng = np.random.default_rng(41)
    x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
        np.complex64)
    got = np.asarray(pallas_fft.fft_axis0(jnp.asarray(x)))
    want = np.fft.fft(x, axis=0)
    assert np.max(np.abs(got - want)) / np.abs(want).max() < 5e-6


def test_fft_axis0_inverse_roundtrip():
    from distributedfft_tpu.ops import pallas_fft

    rng = np.random.default_rng(42)
    x = (rng.standard_normal((64, 9, 3))
         + 1j * rng.standard_normal((64, 9, 3))).astype(np.complex64)
    y = pallas_fft.fft_axis0(jnp.asarray(x), forward=True)
    back = np.asarray(pallas_fft.fft_axis0(y, forward=False))
    assert np.max(np.abs(back - x)) < 1e-5


def test_fft_along_axis_leading_uses_strided():
    """fft_along_axis(axis=0) matches numpy through the strided path."""
    from distributedfft_tpu.ops import pallas_fft

    rng = np.random.default_rng(43)
    x = (rng.standard_normal((64, 6, 10))
         + 1j * rng.standard_normal((64, 6, 10))).astype(np.complex64)
    got = np.asarray(pallas_fft.fft_along_axis(jnp.asarray(x), 0))
    want = np.fft.fft(x, axis=0)
    assert np.max(np.abs(got - want)) / np.abs(want).max() < 5e-6


def test_fft_along_axis_middle_uses_vmapped_strided():
    from distributedfft_tpu.ops import pallas_fft

    rng = np.random.default_rng(44)
    x = (rng.standard_normal((5, 64, 6, 3))
         + 1j * rng.standard_normal((5, 64, 6, 3))).astype(np.complex64)
    got = np.asarray(pallas_fft.fft_along_axis(jnp.asarray(x), 1))
    want = np.fft.fft(x, axis=1)
    assert np.max(np.abs(got - want)) / np.abs(want).max() < 5e-6
    # inverse through the same path
    back = np.asarray(pallas_fft.fft_along_axis(
        pallas_fft.fft_along_axis(jnp.asarray(x), 1), 1, forward=False))
    assert np.max(np.abs(back - x)) < 1e-5


def test_pallas_split_override(monkeypatch):
    """DFFT_PALLAS_SPLIT steers the kernel's four-step factor pair (the
    MXU-edge experiment knob); numerics must be identical to the balanced
    split. Tables are lru-cached per (n, g) AFTER the split resolves, so
    each override runs in its own cache generation here."""
    from distributedfft_tpu.ops import pallas_fft

    x = (np.random.default_rng(3).standard_normal((16, 512))
         + 1j * np.random.default_rng(4).standard_normal((16, 512))
         ).astype(np.complex64)
    ref = np.fft.fft(x, axis=1)
    try:
        for spec, want in (("512=4x128", (4, 128)), ("512=2x256", (2, 256))):
            monkeypatch.setenv("DFFT_PALLAS_SPLIT", spec)
            pallas_fft._fft_tiles.clear_cache()
            assert pallas_fft.split_for(512) == want
            got = np.asarray(pallas_fft.fft_along_axis(jnp.asarray(x), 1))
            assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-4
        monkeypatch.setenv("DFFT_PALLAS_SPLIT", "512=3x170")
        with pytest.raises(ValueError, match="PALLAS_SPLIT"):
            pallas_fft.split_for(512)
        monkeypatch.setenv("DFFT_PALLAS_SPLIT", "512=foox128")
        with pytest.raises(ValueError, match="not N=AxB"):
            pallas_fft.split_for(512)
    finally:
        monkeypatch.delenv("DFFT_PALLAS_SPLIT", raising=False)
        pallas_fft._fft_tiles.clear_cache()
