"""Full TPU compiles of the main path at real widths, without a chip.

The TPU compiler is installed here and compiles for a described
``v5e:2x2`` topology, so VMEM, tiling and device-memory refusals surface
in CI rather than on the chip (``tests/test_tpu_lowering.py`` only runs
``jax.export``, the front half). Nothing runs; these tests say nothing
about results or times.

Only one process may load libtpu at a time, so the topology is described
inside a module-scoped fixture (never at import), every compile runs in
this test process, and all of them live in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

import distributedfft_tpu as dfft
from distributedfft_tpu import plan_logic
from distributedfft_tpu.ops import dft_matmul, pallas_fft
from distributedfft_tpu.ops.executors import get_executor
from distributedfft_tpu.parallel.mesh import PENCIL_AXES, SLAB_AXIS

N = 512
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # no compiler logs under /tmp
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # The chip runs with x64 off (the suite turns it on): int64 grid
    # indices are not legal Mosaic.
    old_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_x64", old_x64)
        jax.config.update("jax_enable_compilation_cache", old_cache)
        compilation_cache.reset_cache()
        if old_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = old_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def real_kernels(monkeypatch):
    """Trace the Pallas kernels themselves (not the interpret mirrors),
    unpacked: on the chip the pack probe turns packing off, because
    Mosaic refuses the packed reshapes (``infer-vector-layout:
    unsupported shape cast``)."""
    monkeypatch.setenv("DFFT_FORCE_REAL_LOWERING", "1")
    monkeypatch.setenv("DFFT_PALLAS_PACK", "0")
    for f in (pallas_fft._fft_tiles, pallas_fft._fft_strided_tiles):
        f.clear_cache()
    yield
    for f in (pallas_fft._fft_tiles, pallas_fft._fft_strided_tiles):
        f.clear_cache()


def _compile_plan(plan, sharding):
    x = jax.ShapeDtypeStruct(plan.in_shape, plan.in_dtype, sharding=sharding)
    return jax.jit(plan.fn).lower(x).compile()


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB"


@pytest.mark.parametrize("executor", ["xla", "xla_minor"])
def test_xla_plan_512_compiles_and_fits(one_chip, executor):
    plan = dfft.plan_dft_c2c_3d((N,) * 3, None, dtype=jnp.complex64,
                                executor=executor)
    _fits_one_chip(_compile_plan(plan, one_chip))


def test_dense_matmul_plan_512_compiles_and_fits(one_chip, monkeypatch):
    # The TPU branch of the dense-tier bound (one 512x512 DFT contraction
    # per axis); CPU planning would take the four-step split.
    monkeypatch.setattr(dft_matmul, "direct_max", lambda: N)
    dfft.clear_plan_cache()
    plan = dfft.plan_dft_c2c_3d((N,) * 3, None, dtype=jnp.complex64,
                                executor="matmul")
    _fits_one_chip(_compile_plan(plan, one_chip))
    dfft.clear_plan_cache()


@pytest.mark.parametrize("kernel", ["rows", "strided"])
def test_pallas_1d_kernels_512_compile(one_chip, real_kernels, kernel):
    if kernel == "rows":
        t = pallas_fft.batch_tile(N)
        shape = (t, N)
        fn = lambda a, b: pallas_fft._fft_tiles(  # noqa: E731
            a, b, n=N, forward=True, interpret=False)
    else:
        t = pallas_fft.col_tile(N)
        shape = (N, t)
        fn = lambda a, b: pallas_fft._fft_strided_tiles(  # noqa: E731
            a, b, n=N, forward=True, interpret=False)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(fn).lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("forward", [True, False], ids=["fwd", "bwd"])
def test_pallas_plane_path_512(one_chip, real_kernels, forward):
    """The single-device default on the TPU at 512^3 (the planner sees
    the CPU here, so the executor is compiled itself): one complex64
    split, three in-place plane passes, one combine, no FFT op, and no
    more temporary memory than the ``xla`` plan."""
    x = jax.ShapeDtypeStruct((N,) * 3, jnp.complex64, sharding=one_chip)
    ex = get_executor("pallas")
    lowered = jax.jit(lambda v: ex(v, (0, 1, 2), forward)).lower(x)
    assert "stablehlo.fft" not in lowered.as_text()
    compiled = lowered.compile()
    targets = re.findall(r'custom_call_target="([^"]+)"', compiled.as_text())
    assert sorted(targets) == sorted(
        ["X64SplitHigh", "X64SplitLow", "X64Combine"]
        + ["tpu_custom_call"] * 3), targets
    xla_plan = dfft.plan_dft_c2c_3d((N,) * 3, None, dtype=jnp.complex64,
                                    executor="xla")
    xla_temp = _compile_plan(xla_plan, one_chip).memory_analysis()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= xla_temp.temp_size_in_bytes, (temp, xla_temp)


@pytest.mark.parametrize("radix", range(1, pallas_fft.PLANE_MAX_RADIX + 1))
@pytest.mark.parametrize("kind", ["major", "sublane", "lane"])
def test_plane_pass_compiles_each_kind_and_radix(one_chip, real_kernels,
                                                 kind, radix):
    """Every tile kind at every radix the default rule admits (axis
    lengths 128..1024), one in-place pass each, on the tile groups a
    large grid gets (eight per grid step)."""
    n = radix * pallas_fft.PLANE_WIDTH
    shape, axis = {"major": ((n, 8, 128), 0), "sublane": ((8, n, 128), 1),
                   "lane": ((128, 8, n), 2)}[kind]
    assert pallas_fft._plane_layout(shape, axis)[:2] == (kind, 8)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda a, b: pallas_fft._plane_pass(
        a, b, axis, True)).lower(x, x).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("mesh_shape", [(4,), (2, 2)],
                         ids=["slab", "pencil"])
def test_distributed_512_compiles_on_four_chips(topo, mesh_shape):
    names = (SLAB_AXIS,) if len(mesh_shape) == 1 else PENCIL_AXES
    mesh = Mesh(np.asarray(topo.devices).reshape(mesh_shape), names)
    plan = dfft.plan_dft_c2c_3d((N,) * 3, mesh, dtype=jnp.complex64)
    compiled = _compile_plan(plan, plan.in_sharding)
    text = compiled.as_text()
    assert "all-to-all" in text
    _fits_one_chip(compiled)  # per-device bytes


@pytest.mark.parametrize("forward", [True, False], ids=["fwd", "bwd"])
def test_default_slab_plane_path_512_on_four_chips(topo, real_kernels,
                                                   monkeypatch, forward):
    """The slab default on the TPU (the planner is told it runs on one):
    one complex64 split, the float32 planes through t2, three in-place
    plane passes, one combine, no FFT op, and no more temporary memory
    per chip than the ``xla`` plan, but for the passes' tables."""
    monkeypatch.setattr(plan_logic, "_on_tpu", lambda: True)
    dfft.clear_plan_cache()
    mesh = Mesh(np.asarray(topo.devices).reshape(4), (SLAB_AXIS,))
    kw = dict(dtype=jnp.complex64,
              direction=dfft.FORWARD if forward else dfft.BACKWARD)
    try:
        plan = dfft.plan_dft_c2c_3d((N,) * 3, mesh, **kw)
        assert plan.executor == "pallas"
        compiled = _compile_plan(plan, plan.in_sharding)
        xla_plan = dfft.plan_dft_c2c_3d((N,) * 3, mesh, executor="xla", **kw)
        xla_temp = _compile_plan(
            xla_plan, xla_plan.in_sharding).memory_analysis()
    finally:
        dfft.clear_plan_cache()
    text = compiled.as_text()
    targets = re.findall(r'custom_call_target="([^"]+)"', text)
    assert sorted(targets) == sorted(
        ["X64SplitHigh", "X64SplitLow", "X64Combine"]
        + ["tpu_custom_call"] * 3), targets
    assert " fft(" not in text and "all-to-all" in text
    # At 512^3 both plans hold 384 MiB of temp a chip; the passes' float32
    # DFT and twiddle tables add tens of KiB (1024^3: 3.00 GiB against 4.00).
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= xla_temp.temp_size_in_bytes + 2**20, (temp, xla_temp)
