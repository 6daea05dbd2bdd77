"""The plane-resident Pallas executor and the single-device and slab default
rules.

A complex64 operand is split into float32 planes once, each axis runs one
in-place plane pass (or, where the 128-wide factoring cannot take it, the
balanced-split kernels), and the planes are combined once. On the CPU the
kernels run in the Pallas interpreter; ``tests/test_tpu_compile.py``
compiles the 512^3 path for a described v5e.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh

import distributedfft_tpu as dfft
from distributedfft_tpu import plan_logic
from distributedfft_tpu import testing as tu
from distributedfft_tpu.ops import pallas_fft
from distributedfft_tpu.ops.executors import get_executor
from distributedfft_tpu.utils import metrics as m


def _rand_c64(shape, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _reference(x, axes, forward):
    f = np.fft.fftn if forward else np.fft.ifftn
    return f(x.astype(np.complex128), axes=axes)


@pytest.mark.parametrize("forward", [True, False], ids=["fwd", "bwd"])
@pytest.mark.parametrize("axes", [(0, 1, 2), (1, 2), (0,)],
                         ids=["xyz", "yz", "x"])
@pytest.mark.parametrize("shape", [(64, 64, 64), (32, 64, 128)],
                         ids=["64^3", "32x64x128"])
def test_executor_matches_numpy(shape, axes, forward):
    x = _rand_c64(shape)
    ex = get_executor("pallas")
    y = np.asarray(jax.jit(lambda v: ex(v, axes, forward))(x))
    tu.assert_approx(y, _reference(x, axes, forward), np.complex64)


@pytest.mark.parametrize("shape, axis", [
    ((512, 8, 128), 0),    # major, radix 4
    ((640, 8, 128), 0),    # major, radix 5 (complex VPU coefficients)
    ((8, 512, 128), 1),    # sublane, radix 4
    ((3, 384, 128), 1),    # sublane, radix 3, one group per step
    ((128, 8, 512), 2),    # lane, radix 4
    ((128, 1024), 1),      # lane, radix 8
    ((1024, 128), 1),      # lane, radix 1 (the dense stage alone)
], ids=["major-r4", "major-r5", "sublane-r4", "sublane-r3", "lane-r4",
        "lane-r8", "lane-r1"])
@pytest.mark.parametrize("forward", [True, False], ids=["fwd", "bwd"])
def test_plane_pass_each_layout(shape, axis, forward):
    assert pallas_fft.plane_pass_eligible(shape, axis)
    x = _rand_c64(shape, seed=1)
    re, im = jax.jit(lambda a, b: pallas_fft.fft_planes(
        a, b, (axis,), forward))(x.real, x.imag)
    y = np.asarray(re) + 1j * np.asarray(im)
    tu.assert_approx(y, _reference(x, (axis,), forward), np.complex64)


@pytest.mark.parametrize("shape, axis", [
    ((64, 64, 64), 0),     # 64 is no multiple of the 128-wide stage
    ((2304, 8, 128), 0),   # radix 18 exceeds PLANE_MAX_RADIX
    ((128, 4, 128), 0),    # 4 columns: no (8, 128) tile of a major block
    ((128, 128, 96), 1),   # 96 lanes
    ((100, 128), 1),       # 100 rows: no group of 128
    ((128, 5, 128), 2),    # merging 5 rows into the row tile would copy
])
def test_ineligible_layouts(shape, axis):
    assert not pallas_fft.plane_pass_eligible(shape, axis)


def test_user_input_is_not_overwritten():
    """Only the internal planes are aliased: the caller's array survives
    the call, inside and outside jit."""
    x_host = _rand_c64((128, 8, 128), seed=2)
    x = jnp.asarray(x_host)
    ex = get_executor("pallas")
    y = jax.jit(lambda v: ex(v, (0, 1, 2), True))(x)
    jax.block_until_ready(y)
    np.testing.assert_array_equal(np.asarray(x), x_host)
    y2 = ex(x, (0, 2), True)
    jax.block_until_ready(y2)
    np.testing.assert_array_equal(np.asarray(x), x_host)


def test_one_split_three_passes_one_combine():
    """The executor splits and combines once, not around every axis."""
    jaxpr = jax.make_jaxpr(lambda v: get_executor("pallas")(
        v, (0, 1, 2), True))(jax.ShapeDtypeStruct((128,) * 3, jnp.complex64))
    prims = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert prims.count("real") == 1 and prims.count("imag") == 1
    assert prims.count("complex") == 1
    assert prims.count("pallas_call") == 3


# ------------------------------------------------------- the default rule

@pytest.fixture
def on_tpu(monkeypatch):
    """The planner sees a TPU backend (the kernels still interpret)."""
    monkeypatch.setattr(plan_logic, "_on_tpu", lambda: True)
    monkeypatch.delenv("DFFT_EXECUTOR", raising=False)
    dfft.clear_plan_cache()
    m.metrics_reset()
    m.enable_metrics()
    yield
    m.enable_metrics(False)
    m.metrics_reset()
    dfft.clear_plan_cache()


def test_default_rule_picks_pallas(on_tpu):
    shape = (128, 128, 128)
    plan = dfft.plan_dft_c2c_3d(shape, None, dtype=jnp.complex64)
    assert plan.executor == "pallas"
    assert plan.options.executor == "pallas"
    assert m.counter_total("c2c_default_pallas") == 1.0
    assert dfft.explain(plan, measure=False)["plan"]["executor"] == "pallas"
    x = _rand_c64(shape, seed=3)
    tu.assert_approx(np.asarray(plan(x)), _reference(x, (0, 1, 2), True),
                     np.complex64)


def _needs(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")


@pytest.mark.parametrize("case", [
    "explicit_xla", "complex128", "batched", "length", "pencil_mesh",
    "slab_lanes96", "slab_t3_chunks", "slab_batched", "slab_hierarchical",
    "slab_explicit_options"])
def test_default_rule_keeps_xla(on_tpu, case):
    shape, mesh, kw = (128, 128, 128), None, {"dtype": jnp.complex64}
    if case.startswith("slab"):
        _needs(4)
        mesh = dfft.make_mesh(4)
    if case == "explicit_xla":
        kw["executor"] = "xla"
    elif case == "complex128":
        kw["dtype"] = jnp.complex128
    elif case in ("batched", "slab_batched"):
        kw["batch"] = 2
    elif case in ("length", "slab_lanes96"):
        shape = (128, 128, 96)
    elif case == "pencil_mesh":
        _needs(4)
        mesh = dfft.make_mesh((2, 2))
    elif case == "slab_t3_chunks":
        # t0's [32, 128, 128] block takes both passes; t3's two overlap
        # chunks are 64 lanes wide, which no major pass tiles.
        kw["overlap_chunks"] = 2
    elif case == "slab_hierarchical":
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("dcn", "ici"))
        kw["algorithm"] = "hierarchical"
    elif case == "slab_explicit_options":
        kw["options"] = dfft.PlanOptions()
    plan = dfft.plan_dft_c2c_3d(shape, mesh, **kw)
    assert plan.decomposition == ("pencil" if case == "pencil_mesh" else
                                  "slab" if mesh is not None else "single")
    assert plan.executor == "xla"
    assert m.counter_total("c2c_default_pallas") == 0.0


@pytest.mark.parametrize("case", ["slab_mesh", "slab_overlap_chunks"])
def test_default_rule_picks_pallas_on_a_slab(on_tpu, case):
    """A slab whose every local block takes the plane passes runs them:
    t0's block [n0/p, n1, n2] on axes 1 and 2, t3's [n0, n1/p, n2/K] on
    axis 0 (the backward chain's blocks are the mirror image)."""
    _needs(4)
    shape, kw = (128, 128, 128), {}
    if case == "slab_overlap_chunks":
        shape, kw = (128, 128, 256), {"overlap_chunks": 2}
    mesh = dfft.make_mesh(4)
    x = _rand_c64(shape, seed=5)
    for n, forward in enumerate((True, False), start=1):
        plan = dfft.plan_dft_c2c_3d(
            shape, mesh, dtype=jnp.complex64,
            direction=dfft.FORWARD if forward else dfft.BACKWARD, **kw)
        assert plan.decomposition == "slab"
        assert plan.executor == "pallas"
        assert plan.options.executor == "pallas"
        assert m.counter_total("c2c_default_pallas",
                               decomposition="slab") == n
        explained = dfft.explain(plan, measure=False)["plan"]
        assert explained["executor"] == "pallas"
        y = np.asarray(plan(jax.device_put(x, plan.in_sharding)))
        tu.assert_approx(y, _reference(x, (0, 1, 2), forward), np.complex64)


def _dot_precisions(jaxpr):
    """The precision of every ``dot_general`` in ``jaxpr`` and in the
    jaxprs its equations hold (jit bodies, Pallas kernels)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _dot_precisions(inner)
    return found


def test_default_plan_stays_exact_under_a_lower_precision_env(
        on_tpu, monkeypatch):
    """``DFFT_MM_PRECISION`` lowers the matmul-family executors' tier; the
    default plan, like the XLA FFT it replaces, stays exact: every
    contraction of its plane passes runs at HIGHEST. An explicit
    ``pallas`` plan still follows the env."""
    monkeypatch.setenv("DFFT_MM_PRECISION", "default")
    shape = (128, 128, 128)
    spec = jax.ShapeDtypeStruct(shape, jnp.complex64)
    plan = dfft.plan_dft_c2c_3d(shape, None, dtype=jnp.complex64)
    assert plan.executor == "pallas"
    found = _dot_precisions(jax.make_jaxpr(plan.fn)(spec).jaxpr)
    assert len(found) == 9, found  # three Gauss matmuls per pass
    assert set(found) == {(lax.Precision.HIGHEST,) * 2}, found
    x = _rand_c64(shape, seed=4)
    tu.assert_approx(np.asarray(plan(x)), _reference(x, (0, 1, 2), True),
                     np.complex64)
    explicit = dfft.plan_dft_c2c_3d(shape, None, dtype=jnp.complex64,
                                    executor="pallas")
    found = _dot_precisions(jax.make_jaxpr(explicit.fn)(spec).jaxpr)
    assert set(found) == {(lax.Precision.DEFAULT,) * 2}, found


def test_default_slab_plan_stays_exact_under_a_lower_precision_env(
        on_tpu, monkeypatch):
    """The slab twin of the test above: under ``DFFT_MM_PRECISION=default``
    every contraction of the default slab plan runs at HIGHEST, while an
    explicit ``pallas`` slab plan follows the env. (Inside ``shard_map``
    the interpreter takes the per-axis matmul route, not the plane
    passes, so the dots are counted per axis, not per pass.)"""
    _needs(4)
    monkeypatch.setenv("DFFT_MM_PRECISION", "default")
    shape, mesh = (128, 128, 128), dfft.make_mesh(4)
    plan = dfft.plan_dft_c2c_3d(shape, mesh, dtype=jnp.complex64)
    assert plan.executor == "pallas"
    spec = jax.ShapeDtypeStruct(shape, jnp.complex64,
                                sharding=plan.in_sharding)
    found = _dot_precisions(jax.make_jaxpr(plan.fn)(spec).jaxpr)
    assert len(found) >= 3, found  # at least one per axis
    assert set(found) == {(lax.Precision.HIGHEST,) * 2}, found
    x = _rand_c64(shape, seed=6)
    y = np.asarray(plan(jax.device_put(x, plan.in_sharding)))
    tu.assert_approx(y, _reference(x, (0, 1, 2), True), np.complex64)
    explicit = dfft.plan_dft_c2c_3d(shape, mesh, dtype=jnp.complex64,
                                    executor="pallas")
    found = _dot_precisions(jax.make_jaxpr(explicit.fn)(spec).jaxpr)
    assert set(found) == {(lax.Precision.DEFAULT,) * 2}, found


def test_env_default_still_overrides(on_tpu, monkeypatch):
    monkeypatch.setenv("DFFT_EXECUTOR", "matmul")
    plan = dfft.plan_dft_c2c_3d((128,) * 3, None, dtype=jnp.complex64)
    assert plan.executor == "matmul"
    assert m.counter_total("c2c_default_pallas") == 0.0


def test_default_is_xla_on_the_cpu():
    dfft.clear_plan_cache()
    plan = dfft.plan_dft_c2c_3d((128,) * 3, None, dtype=jnp.complex64)
    assert plan.executor == "xla"
    dfft.clear_plan_cache()
