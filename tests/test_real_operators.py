"""Real-field operator plans: ``plan_spectral_op(..., real=True)``.

r2c along axis 2, the executor on axes 0 and 1, the multiplier on the
half-spectrum grid, the inverse, and c2r back to n2, as one jitted
program on one device. The reference is numpy's
``irfftn(G_half * rfftn(x))`` in float64 with every multiplier written
out here from the unit-torus definition, importing nothing of the
planner.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributedfft_tpu as dfft
from distributedfft_tpu import operators
from distributedfft_tpu.utils import metrics as m
from distributedfft_tpu.utils import xplane

CDT = jnp.complex128
TOL = 1e-10


def _field(shape, seed=7):
    return np.random.default_rng(seed).standard_normal(shape)


def _half_k(shape):
    """Angular wavenumbers of the half spectrum, broadcastable."""
    n0, n1, n2 = shape
    k0 = 2 * np.pi * np.fft.fftfreq(n0, 1.0 / n0)
    k1 = 2 * np.pi * np.fft.fftfreq(n1, 1.0 / n1)
    k2 = 2 * np.pi * np.fft.rfftfreq(n2, 1.0 / n2)
    return k0[:, None, None], k1[None, :, None], k2[None, None, :]


def _green(name, kw, shape):
    k0, k1, k2 = _half_k(shape)
    ksq = k0 ** 2 + k1 ** 2 + k2 ** 2
    nz = ksq > 0
    safe = np.where(nz, ksq, 1.0)
    if name == "poisson":
        return np.where(nz, -1.0 / safe, 0.0)
    if name == "biharm":
        return np.where(nz, 1.0 / safe ** 2, 0.0)
    if name == "helmholtz":
        return 1.0 / (kw.get("shift", 1.0) + ksq)
    if name == "gauss":
        s = kw.get("sigma", 1.0)
        return np.exp(-0.5 * s * s * ksq)
    if name == "grad":
        return 1j * (k0, k1, k2)[kw.get("axis", 0)] + 0 * ksq
    raise AssertionError(name)


def _reference(name, kw, x):
    spec = np.fft.rfftn(x, axes=(0, 1, 2))
    return np.fft.irfftn(_green(name, kw, x.shape) * spec, s=x.shape,
                         axes=(0, 1, 2))


def _relerr(got, ref):
    got = np.asarray(got)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


OPS = [("poisson", {}), ("grad", {}), ("grad", {"axis": 2}), ("gauss", {}),
       ("biharm", {}), ("helmholtz", {})]
OP_IDS = ["poisson", "grad0", "grad2", "gauss", "biharm", "helmholtz"]


def test_every_menu_op_is_covered():
    assert {name for name, _ in OPS} == set(operators.OP_NAMES)


@pytest.mark.parametrize("n2", [10, 9], ids=["even_n2", "odd_n2"])
@pytest.mark.parametrize("name, kw", OPS, ids=OP_IDS)
def test_real_plan_matches_numpy(name, kw, n2):
    shape = (8, 6, n2)
    plan = operators.plan_spectral_op(
        shape, None, op=operators.named_op(name, **kw), real=True,
        dtype=CDT)
    x = _field(shape)
    got = plan(x)
    assert got.dtype == jnp.float64 and got.shape == shape
    assert _relerr(got, _reference(name, kw, x)) < TOL


@pytest.mark.parametrize("name", ["poisson", "gauss", "biharm", "helmholtz"])
def test_even_real_plan_is_real_part_of_complex_plan(name):
    shape = (8, 6, 10)
    op = operators.named_op(name)
    x = _field(shape, seed=11)
    real = operators.plan_spectral_op(shape, None, op=op, real=True,
                                      dtype=CDT)(x)
    cplx = operators.plan_spectral_op(shape, None, op=op, dtype=CDT)(
        x.astype(np.complex128))
    assert _relerr(real, np.real(np.asarray(cplx))) < TOL


def test_real_convolve_and_custom_take_the_half_grid():
    """A delta kernel at +1 on axis 2 shifts the field; a unit custom
    multiplier gives it back."""
    shape = (8, 6, 10)
    x = _field(shape, seed=5)
    k = np.zeros(shape)
    k[0, 0, 1] = 1.0
    shift = dfft.fft_convolve(shape, kernel=k, real=True, dtype=CDT)
    assert _relerr(shift(x), np.roll(x, 1, axis=2)) < TOL
    unit = operators.custom("unit", lambda i0, i1, i2: jnp.float64(1.0))
    ident = operators.plan_spectral_op(shape, None, op=unit, real=True,
                                       dtype=CDT)
    assert _relerr(ident(x), x) < TOL


def test_solve_poisson_real_is_one_float32_program():
    shape = (16, 8, 12)
    plan = dfft.solve_poisson(shape, real=True, dtype=jnp.complex64)
    assert isinstance(plan, dfft.OpPlan3D) and plan.real
    assert plan.executor == "xla" and plan.decomposition == "single"
    assert plan.in_dtype == plan.out_dtype == jnp.float32
    assert plan.in_shape == plan.out_shape == shape
    x = _field(shape).astype(np.float32)
    y = plan(x)
    assert y.dtype == jnp.float32
    assert _relerr(y, _reference("poisson", {}, x.astype(np.float64))) < 5e-6
    # one program, whose real axis runs the executor's r2c and c2r
    text = plan.fn.lower(jnp.asarray(x)).as_text()
    assert "RFFT" in text and "IRFFT" in text


def test_real_plan_takes_the_executors_real_pair():
    """With ``matmul`` the real axis runs its half-complex r2c and c2r:
    no XLA FFT is left in the program."""
    shape = (8, 6, 10)
    plan = dfft.solve_poisson(shape, real=True, dtype=CDT,
                              executor="matmul")
    x = _field(shape)
    assert "fft" not in plan.fn.lower(jnp.asarray(x)).as_text().lower()
    assert _relerr(plan(x), _reference("poisson", {}, x)) < TOL


def test_direction_must_be_forward():
    shape = (8, 8, 8)
    plan = dfft.solve_poisson(shape, direction=dfft.FORWARD, real=True)
    assert plan.forward
    with pytest.raises(ValueError, match="FORWARD"):
        dfft.solve_poisson(shape, direction=dfft.BACKWARD, real=True)
    with pytest.raises(ValueError, match="FORWARD"):
        dfft.solve_poisson(shape, direction=dfft.BACKWARD)


def test_real_splits_the_plan_cache():
    shape = (8, 8, 8)
    real = dfft.solve_poisson(shape, real=True, dtype=CDT)
    cplx = dfft.solve_poisson(shape, dtype=CDT)
    assert real is not cplx
    assert real.in_dtype == jnp.float64 and cplx.in_dtype == CDT
    assert dfft.solve_poisson(shape, real=True, dtype=CDT) is real


@pytest.mark.parametrize("kw", [{"executor": "auto"}, {"tune": "measure"}],
                         ids=["auto", "tuned"])
def test_real_plan_through_the_selecting_planners(kw):
    shape = (8, 6, 10)
    plan = dfft.solve_poisson(shape, real=True, dtype=CDT, **kw)
    assert plan.real and plan.in_dtype == jnp.float64
    x = _field(shape)
    assert _relerr(plan(x), _reference("poisson", {}, x)) < TOL


def test_real_plan_limits():
    with pytest.raises(ValueError, match="complex working dtype"):
        dfft.solve_poisson((8, 8, 8), real=True, dtype=jnp.float32)
    if len(jax.devices()) >= 4:
        with pytest.raises(ValueError, match="one device"):
            dfft.solve_poisson((8, 8, 8), dfft.make_mesh(4), real=True)


def test_batched_real_plan_matches_per_field():
    shape = (8, 6, 10)
    plan = dfft.solve_poisson(shape, real=True, dtype=CDT, batch=3)
    xs = np.stack([_field(shape, seed=s) for s in range(3)])
    got = np.asarray(plan(xs))
    for b in range(3):
        assert _relerr(got[b], _reference("poisson", {}, xs[b])) < TOL


def _op_names(fn, *args) -> list[str]:
    text = fn.lower(*args).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def test_real_plan_stage_scopes_reach_the_program():
    shape = (8, 6, 10)
    plan = dfft.solve_poisson(shape, real=True, dtype=jnp.complex64)
    names = _op_names(plan.fn, jnp.zeros(shape, jnp.float32))
    by_scope = {s: [n for n in names if f"/{s}/" in n]
                for s in ("t0_r2c_z", "t3_fft_xy", "t_mid_pointwise",
                          "t3_ifft_xy", "t0_c2r_z")}
    assert all(by_scope.values()), {k: len(v) for k, v in by_scope.items()}
    stages = {s: {xplane.op_stage(n) for n in ns}
              for s, ns in by_scope.items()}
    assert stages == {"t0_r2c_z": {"t0"}, "t3_fft_xy": {"t3"},
                      "t_mid_pointwise": {"t_mid"}, "t3_ifft_xy": {"t3"},
                      "t0_c2r_z": {"t0"}}
    assert all("/t_mid/t_mid_pointwise/" in n
               for n in by_scope["t_mid_pointwise"])


_META = re.compile(r", metadata=\{[^}]*\}")


def _instructions(fn, *args) -> list[str]:
    """The compiled program's instructions without their metadata."""
    text = fn.lower(*args).compile().as_text()
    return [_META.sub("", ln) for ln in text.splitlines()
            if " = " in ln]


def test_complex_plan_scopes_change_no_instruction():
    """The complex single-device operator's stage scopes are op
    metadata only: its compiled program is, instruction for instruction,
    that of the same chain traced with no scope."""
    from distributedfft_tpu.ops.executors import get_executor
    from distributedfft_tpu.parallel.slab import apply_multiplier

    shape = (8, 6, 10)
    op = operators.poisson()
    plan = operators.plan_spectral_op(shape, None, op=op,
                                      dtype=jnp.complex64)
    ex = get_executor("xla")
    mult = operators._multiplier_fn(op, shape, jnp.complex64)
    grids = operators._full_grids(shape)

    def _single(x):
        y = ex(x, (0, 1, 2), True)
        y = apply_multiplier(y, mult(*grids))
        return ex(y, (0, 1, 2), False)

    x = jnp.zeros(shape, jnp.complex64)
    assert _instructions(plan.fn, x) == _instructions(jax.jit(_single), x)


@pytest.fixture
def metrics_on():
    dfft.clear_plan_cache()
    m.metrics_reset()
    m.enable_metrics()
    yield
    m.enable_metrics(False)
    m.metrics_reset()
    dfft.clear_plan_cache()


def test_spectral_op_plans_counts_real_and_complex_apart(metrics_on):
    shape = (8, 8, 8)
    dfft.solve_poisson(shape, real=True, dtype=CDT)
    dfft.solve_poisson(shape, real=True, dtype=CDT)  # cache hit: no build
    dfft.solve_poisson(shape, dtype=CDT)
    dfft.spectral_gradient(shape, axis=1, real=True, dtype=CDT)
    series = m.metrics_snapshot()["counters"]["spectral_op_plans"]
    assert series == {
        "decomposition=single,op=poisson,real=True": 1.0,
        "decomposition=single,op=poisson,real=False": 1.0,
        "decomposition=single,op=gradient1,real=True": 1.0,
    }
