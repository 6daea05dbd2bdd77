"""TPU-platform lowering smoke for the Pallas kernels — no chip needed.

``jax.export`` runs the full TPU lowering pipeline on any host, including
building and serializing the Mosaic MLIR module for every ``pallas_call``
— so Mosaic front-end rejections (unsupported ops, the packed kernels'
lane-changing reshapes, bad block shapes) surface here, in CI, instead of
on first hardware contact. This cannot prove the later Mosaic-to-target
compile succeeds (register/VMEM pressure is target-stage;
tests/test_tpu_compile.py and the per-config compile probe own that),
but it pins the front half that killed interpret-mode-only coverage in
earlier rounds.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import export

from distributedfft_tpu.ops import pallas_fft


@pytest.fixture(autouse=True)
def _fresh_kernel_traces():
    """The tile functions read DFFT_PALLAS_* env at trace time, so their
    jit caches do NOT key on the env (same discipline as
    tune_pallas.py's sweep): clear them around every test or a cached
    trace from a previous test's env silently stands in for this one's
    — e.g. a PACK=0 test re-exporting the packed kernel."""
    for f in (pallas_fft._fft_tiles, pallas_fft._fft_strided_tiles):
        f.clear_cache()
    yield
    for f in (pallas_fft._fft_tiles, pallas_fft._fft_strided_tiles):
        f.clear_cache()


def _export_ok(fn, *args):
    export.export(jax.jit(fn), platforms=["tpu"])(*args)


@pytest.mark.parametrize("n", [256, 512, 1024])
def test_fused_1d_lowers_for_tpu(n, monkeypatch):
    monkeypatch.setenv("DFFT_PALLAS_PACK", "1")  # force packed kernels
    z = jnp.zeros((2048, n), jnp.float32)
    _export_ok(
        lambda a, b: pallas_fft._fft_tiles(
            a, b, n=n, forward=True, interpret=False), z, z)


def test_strided_lowers_for_tpu(monkeypatch):
    monkeypatch.setenv("DFFT_PALLAS_PACK", "1")
    z = jnp.zeros((512, 2048), jnp.float32)
    _export_ok(
        lambda a, b: pallas_fft._fft_strided_tiles(
            a, b, n=512, forward=True, interpret=False), z, z)


def test_shardmap_vma_path_lowers_for_tpu(monkeypatch):
    """The REAL pallas_call under shard_map — the varying-axes/pvary
    path no CPU test can execute (the interpreter mirrors it with jnp
    math). DFFT_FORCE_REAL_LOWERING=1 forces the real kernels at trace
    time so the export builds the actual Mosaic module inside the
    shard_map program, collectives and all."""
    import distributedfft_tpu as dfft
    from distributedfft_tpu.parallel.slab import build_slab_fft3d

    monkeypatch.setenv("DFFT_PALLAS_PACK", "1")
    monkeypatch.setenv("DFFT_FORCE_REAL_LOWERING", "1")
    mesh = dfft.make_mesh(8)
    fn, _ = build_slab_fft3d(
        mesh, (128, 128, 128), axis_name=mesh.axis_names[0],
        executor="pallas", forward=True)
    x = jax.ShapeDtypeStruct((128, 128, 128), jnp.complex64)
    export.export(jax.jit(lambda v: fn(v)), platforms=["tpu"])(x)


def test_ragged_alltoallv_lowers_for_tpu(monkeypatch):
    """The real lax.ragged_all_to_all inside the slab exchange — XLA:CPU
    has no lowering for the op, so every CPU test runs the dense mirror;
    the force-real switch makes the export embed the true ragged
    collective and the TPU pipeline accept it."""
    import distributedfft_tpu as dfft
    from distributedfft_tpu.parallel.slab import build_slab_fft3d

    monkeypatch.setenv("DFFT_FORCE_REAL_LOWERING", "1")
    mesh = dfft.make_mesh(8)
    # Uneven split axis: the a2av path ships true ragged slices.
    fn, _ = build_slab_fft3d(
        mesh, (36, 20, 16), axis_name=mesh.axis_names[0],
        executor="xla", forward=True, algorithm="alltoallv")
    x = jax.ShapeDtypeStruct((36, 20, 16), jnp.complex64)
    # The op lowers to a custom call without cross-version serialization
    # guarantees; we are validating the lowering, not archiving the
    # artifact, so that one serialization check is waived.
    exp = export.export(
        jax.jit(lambda v: fn(v)), platforms=["tpu"],
        disabled_checks=[
            export.DisabledSafetyCheck.custom_call("ragged_all_to_all"),
        ],
    )(x)
    assert "ragged_all_to_all" in exp.mlir_module()


def test_brick_a2av_lowers_for_tpu(monkeypatch):
    """The exact-count brick transport's real path (gather-pack ->
    lax.ragged_all_to_all -> scatter-unpack) through the TPU pipeline."""
    import distributedfft_tpu as dfft
    from distributedfft_tpu.geometry import (
        ceil_splits, make_slabs, world_box,
    )
    from distributedfft_tpu.parallel.bricks import plan_brick_reshape

    monkeypatch.setenv("DFFT_FORCE_REAL_LOWERING", "1")
    mesh = dfft.make_mesh(8)
    w = world_box((13, 16, 12))
    ins = make_slabs(w, 8, axis=0, rule=ceil_splits)
    outs = make_slabs(w, 8, axis=1)
    fn, spec = plan_brick_reshape(mesh, ins, outs, algorithm="a2av")
    x = jax.ShapeDtypeStruct((8,) + spec.in_pad, jnp.complex64)
    exp = export.export(
        jax.jit(fn), platforms=["tpu"],
        disabled_checks=[
            export.DisabledSafetyCheck.custom_call("ragged_all_to_all"),
        ],
    )(x)
    assert "ragged_all_to_all" in exp.mlir_module()


def test_dd_distributed_lowers_for_tpu():
    """The dd slab and pencil programs (compensated arithmetic with
    optimization barriers + bf16 sliced matmuls + collectives) through
    the TPU pipeline."""
    import distributedfft_tpu as dfft

    x = jax.ShapeDtypeStruct((32, 24, 16), jnp.complex64)
    for mesh in (dfft.make_mesh(8), dfft.make_mesh((2, 4))):
        plan = dfft.plan_dd_dft_c2c_3d((32, 24, 16), mesh)
        export.export(jax.jit(lambda a, b: plan.fn(a, b)),
                      platforms=["tpu"])(x, x)


def test_unpacked_fallback_lowers_for_tpu(monkeypatch):
    monkeypatch.setenv("DFFT_PALLAS_PACK", "0")  # the auto-fallback shape
    z = jnp.zeros((2048, 512), jnp.float32)
    _export_ok(
        lambda a, b: pallas_fft._fft_tiles(
            a, b, n=512, forward=False, interpret=False), z, z)


@pytest.mark.parametrize(
    "shape,kind",
    [((1024, 1024, 1024), "slab"),
     ((2048, 2048, 2048), "slab"),      # 8.6e9 elements: past int32
     ((1536, 1024, 768), "pencil")])    # BASELINE.json non-cubic config
def test_campaign_configs_lower_for_tpu(shape, kind):
    """The BASELINE.json campaign shapes through the full TPU lowering
    pipeline, chiplessly — where 64-bit index-math bugs (2048^3 has more
    elements than int32 holds) and shape/layout rejections would
    otherwise wait for first hardware contact. Cheap (~2 s: lowering
    traces scale with program size, not data size), so it stays in the
    default gate."""
    import distributedfft_tpu as dfft
    from distributedfft_tpu.parallel.pencil import build_pencil_fft3d
    from distributedfft_tpu.parallel.slab import build_slab_fft3d

    if kind == "slab":
        mesh = dfft.make_mesh(8)
        fn, _ = build_slab_fft3d(
            mesh, shape, axis_name=mesh.axis_names[0], executor="xla",
            forward=True)
    else:
        mesh = dfft.make_mesh((2, 4))
        fn, _ = build_pencil_fft3d(
            mesh, shape, row_axis=mesh.axis_names[0],
            col_axis=mesh.axis_names[1], executor="xla", forward=True)
    x = jax.ShapeDtypeStruct(shape, jnp.complex64)
    export.export(jax.jit(lambda v: fn(v)), platforms=["tpu"])(x)


def test_brick_order_edge_lowers_for_tpu():
    """The per-box storage-order edge (lax.switch over per-device
    transposes inside shard_map) through the TPU pipeline — a
    shuffled-order brick plan's full fn, orders on both sides."""
    import distributedfft_tpu as dfft
    from distributedfft_tpu.geometry import (
        ceil_splits, make_pencils, make_slabs, world_box,
    )

    mesh = dfft.make_mesh(8)
    shape = (16, 12, 8)
    w = world_box(shape)
    ins = [b.with_order(o) for b, o in zip(
        make_pencils(w, (4, 2), 2),
        [(2, 1, 0), (1, 0, 2), (0, 2, 1), (1, 2, 0),
         (2, 0, 1), (0, 1, 2), (2, 1, 0), (1, 0, 2)])]
    outs = [b.with_order((2, 0, 1)) for b in
            make_slabs(w, 8, axis=1, rule=ceil_splits)]
    plan = dfft.plan_brick_dft_c2c_3d(shape, mesh, ins, outs,
                                      dtype=jnp.complex64)
    x = jax.ShapeDtypeStruct(plan.in_shape, jnp.complex64)
    export.export(jax.jit(plan.fn), platforms=["tpu"])(x)


def test_xla_minor_lowers_for_tpu():
    """The xla_minor layout-experiment executor through the TPU pipeline
    (explicit moveaxis around each fft)."""
    from distributedfft_tpu.ops.executors import get_executor

    ex = get_executor("xla_minor")
    x = jax.ShapeDtypeStruct((32, 32, 32), jnp.complex64)
    _export_ok(lambda v: ex(v, (0, 1, 2), True), x)
