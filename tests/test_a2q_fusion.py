"""Pallas fusion executor tier + split-exponent wire codec (PR 19).

Contracts pinned on the 8-way CPU mesh:

1. **The split codec is a first-class registry member** — int16
   mantissas with a shared power-of-two exponent sidecar: half the c64
   wire bytes at ~100x better accuracy than bf16, exact idempotence,
   and the full transport x decomposition accuracy matrix (usable with
   no Pallas anywhere in the plan).
2. **Fusion is a label, parity is exact** — ``fuse=True`` composes the
   ``pallas:fuse`` executor label; fused plans produce outputs
   IDENTICAL to their unfused twins across slab/pencil x the three flat
   transports x K in {1,2} x batch in {None, 3} (on the CPU shard_map
   interpreter the fused sites run the pure-JAX mirrors, bit-identical
   to the unfused chain; on TPU the kernels quantize with the same
   pow2-step math).
3. **Unfused defaults are untouched** — a default plan's lowered HLO is
   byte-identical to an explicit ``fuse=False`` build (the tier is
   invisible until asked for), and ``DFFT_FUSE`` is plan-cache keyed.
4. **Gates are explained, fallbacks are counted** — ineligible graphs
   gate off with machine-readable reasons (``overlap_k`` /
   ``no_wire_codec``) in ``graph.meta["fusion"]`` and the explain
   record; ineligible kernel sites fall back to the mirrors, counted in
   the ``fusion_fallback`` series — never an error.
5. **The kernels themselves are interpret-exercised** — outside
   shard_map the Pallas bodies run in interpret mode: decode+FFT is
   bit-identical to the unfused chain, FFT+encode agrees within each
   codec's measured error (the CI smoke).
6. **Tuner/wisdom discipline** — fused candidates enter the tournament
   only where the fusion pass can activate (real codec, K=1), model
   cheaper than their unfused twins, admit under the one roundtrip-err
   budget, and replay from wisdom with zero timing executions; a budget
   rejection strips the fuse flag with the codec.

NOTE on the filename: this module must collect BEFORE
``test_alltoallv.py`` (alphabetical collection) — the XLA:CPU fft-thunk
poisoning rule; see ``tests/test_a2g_wire.py``.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributedfft_tpu as dfft
from distributedfft_tpu import regress, tuner
from distributedfft_tpu.ops import pallas_fft, pallas_fuse
from distributedfft_tpu.ops.executors import (
    FUSE_BASES,
    executor_roundtrip_error,
    fused_name,
    split_executor,
    split_fuse,
)
from distributedfft_tpu.parallel.exchange import (
    FLAT_ALGORITHMS,
    WIRE_CODECS,
    WIRE_DTYPES,
    wire_codec,
    wire_itemsize,
    wire_roundtrip_error,
)
from distributedfft_tpu.plan_logic import (
    PlanOptions,
    exchange_payloads,
    fused_model_stages,
    resolve_fuse,
)
from distributedfft_tpu.utils import metrics as m

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the virtual 8-device mesh"
)

SHAPE = (16, 16, 8)
CDT = jnp.complex64
SPLIT_ERR = 1e-4  # split acceptance bound for c64 unit-scale data


def _world(shape=SHAPE, seed=11):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


@pytest.fixture
def wisdom_path(tmp_path, monkeypatch):
    monkeypatch.setenv("DFFT_WISDOM", str(tmp_path / "wisdom.jsonl"))
    return str(tmp_path / "wisdom.jsonl")


# ------------------------------------------------------ the split codec

def test_split_in_registry_menu():
    assert "split" in WIRE_DTYPES and "split" in WIRE_CODECS
    assert WIRE_CODECS["split"].sidecar
    assert wire_itemsize(8, "split") == 4    # c64 -> int16 pair: half
    assert wire_itemsize(16, "split") == 4   # c128 -> int16 pair: quarter


def test_split_roundtrip_bounded_and_idempotent():
    codec = wire_codec("split")
    x = jnp.asarray(_world((8, 12, 5)))
    q, scales = codec.encode(x, tile_axis=1, tiles=4)
    assert q.dtype == jnp.int16 and q.shape == x.shape + (2,)
    # One f32 power-of-two step per (peer tile, component plane).
    assert scales.dtype == jnp.float32
    assert scales.shape == (1, 4, 1, 2)
    s = np.asarray(scales)
    assert np.all(np.exp2(np.round(np.log2(s))) == s)
    y = codec.decode((q, scales), x.dtype, tile_axis=1, tiles=4)
    err = float(np.max(np.abs(np.asarray(y) - np.asarray(x)))
                / np.max(np.abs(np.asarray(x))))
    assert err <= SPLIT_ERR
    # Exact idempotence (power-of-two steps): the staged per-leg
    # decode/re-encode boundary is bit-identical to one cast pair.
    q2, s2 = codec.encode(y, tile_axis=1, tiles=4)
    assert np.array_equal(np.asarray(q2), np.asarray(q))
    assert np.array_equal(np.asarray(s2), np.asarray(scales))


def test_split_beats_bf16_by_orders_of_magnitude():
    e_split = wire_roundtrip_error(np.complex64, "split")
    e_bf16 = wire_roundtrip_error(np.complex64, "bf16")
    assert 0.0 < e_split <= SPLIT_ERR
    assert e_split * 10 < e_bf16  # the headline: finer AND half the bytes


def test_split_payload_wire_factor():
    lp = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=CDT,
                              wire_dtype="split").logic
    entries = exchange_payloads(lp, SHAPE, 8)
    assert entries and all(e["wire_factor"] == 0.5 for e in entries)


@needs_mesh
@pytest.mark.parametrize("alg", FLAT_ALGORITHMS)
@pytest.mark.parametrize("mesh_shape", [8, (2, 4)])
def test_split_accuracy_through_plans(alg, mesh_shape):
    """The standalone-codec acceptance: split works on every transport x
    decomposition with no Pallas anywhere in the plan (executor xla)."""
    mesh = dfft.make_mesh(mesh_shape)
    exact = dfft.plan_dft_c2c_3d(SHAPE, mesh, dtype=CDT, algorithm=alg)
    comp = dfft.plan_dft_c2c_3d(SHAPE, mesh, dtype=CDT, algorithm=alg,
                                wire_dtype="split")
    x = jnp.asarray(_world())
    ref = np.asarray(exact(x))
    err = float(np.max(np.abs(np.asarray(comp(x)) - ref))
                / np.max(np.abs(ref)))
    # x20 slack: two exchanges on the pencil mesh + FFT accumulation.
    assert err <= 20 * SPLIT_ERR, (alg, mesh_shape, err)


# --------------------------------------------- the fuse label algebra

def test_fuse_label_algebra():
    assert split_fuse("pallas:fuse") == ("pallas", True)
    assert split_fuse("pallas:bf16:fuse") == ("pallas:bf16", True)
    assert split_fuse("pallas") == ("pallas", False)
    assert fused_name("pallas", True) == "pallas:fuse"
    assert fused_name("pallas:fuse") == "pallas:fuse"  # idempotent
    with pytest.raises(ValueError, match="fuse"):
        fused_name("pallas:fuse", False)
    with pytest.raises(ValueError, match="fuse"):
        fused_name("xla", True)
    with pytest.raises(ValueError, match="fuse"):
        split_fuse("xla:fuse")
    # The fuse flag is orthogonal to the matmul tier in split_executor.
    assert split_executor("pallas:fuse")[0] == "pallas"
    assert split_executor("pallas:fuse")[1] is None
    assert executor_roundtrip_error("pallas:fuse", np.complex64) == 0.0
    assert "pallas" in FUSE_BASES


def test_fuse_kwarg_composes_label():
    plan = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=CDT, executor="pallas",
                                wire_dtype="split", fuse=True)
    assert plan.executor == "pallas:fuse"
    assert plan.options.fuse is True
    with pytest.raises(ValueError, match="fuse"):
        dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=CDT, executor="xla",
                             fuse=True)


def test_resolve_fuse_env(monkeypatch):
    monkeypatch.delenv("DFFT_FUSE", raising=False)
    assert resolve_fuse(None) is False
    assert resolve_fuse(True) is True
    monkeypatch.setenv("DFFT_FUSE", "1")
    assert resolve_fuse(None) is True
    monkeypatch.setenv("DFFT_FUSE", "0")
    assert resolve_fuse(None) is False


# ------------------------------------------- fused-vs-unfused parity

@needs_mesh
@pytest.mark.parametrize("alg,mesh_shape,k,batch", [
    # Covering set over the full product (transport x slab/pencil x
    # K in {1,2} x batch in {None,3}) — every axis value appears on
    # both meshes and both the active (K=1) and gated (K=2) paths,
    # without paying for all 24 combos in tier-1 wall clock.
    ("alltoall", 8, 1, None),
    ("alltoallv", 8, 1, None),
    ("ppermute", 8, 1, None),
    ("alltoall", (2, 4), 1, 3),
    ("ppermute", (2, 4), 1, None),
    ("alltoallv", (2, 4), 2, None),
    ("alltoall", 8, 2, 3),
])
def test_fused_parity_matrix(alg, mesh_shape, k, batch):
    """The acceptance matrix: a fused plan's output is IDENTICAL to its
    unfused twin's on slab/pencil x all three flat transports x K in
    {1,2} x batch in {None, 3}. At K=1 the fusion pass is active (the
    CPU shard_map interpreter runs the bit-identical mirrors); at K=2
    it gates off (``overlap_k``) and the programs coincide."""
    mesh = dfft.make_mesh(mesh_shape)
    kw = dict(dtype=CDT, algorithm=alg, overlap_chunks=k, batch=batch,
              executor="pallas", wire_dtype="split")
    unfused = dfft.plan_dft_c2c_3d(SHAPE, mesh, **kw)
    fused = dfft.plan_dft_c2c_3d(SHAPE, mesh, fuse=True, **kw)
    assert ":fuse" in fused.executor and ":fuse" not in unfused.executor
    shape = ((batch,) + SHAPE) if batch else SHAPE
    x = jnp.asarray(_world(shape))
    assert np.array_equal(np.asarray(fused(x)), np.asarray(unfused(x)))


@needs_mesh
def test_fusion_active_metadata_and_sites():
    mesh = dfft.make_mesh((2, 4))
    plan = dfft.plan_dft_c2c_3d(SHAPE, mesh, dtype=CDT, executor="pallas",
                                wire_dtype="split", fuse=True)
    fu = plan.graph.meta.get("fusion")
    assert fu["requested"] and fu["active"] and not fu["reasons"]
    plan(jnp.asarray(_world()))  # sites record at trace time
    fu = plan.graph.meta["fusion"]
    assert fu["sites"], "an active fused plan must record its sites"
    for site in fu["sites"].values():
        assert "sender" in site and "receiver" in site


@needs_mesh
@pytest.mark.parametrize("kw,reason", [
    (dict(wire_dtype="split", overlap_chunks=2), "overlap_k"),
    (dict(), "no_wire_codec"),
])
def test_fusion_gates_reasoned_never_error(kw, reason):
    """Ineligible graphs gate off with a machine-readable reason — the
    plan builds and runs; requesting fusion is never an error."""
    mesh = dfft.make_mesh(8)
    plan = dfft.plan_dft_c2c_3d(SHAPE, mesh, dtype=CDT, executor="pallas",
                                fuse=True, **kw)
    fu = plan.graph.meta.get("fusion")
    assert fu["requested"] and not fu["active"]
    assert reason in fu["reasons"]
    x = jnp.asarray(_world())
    ref = dfft.plan_dft_c2c_3d(SHAPE, mesh, dtype=CDT, executor="pallas",
                               **kw)(x)
    assert np.array_equal(np.asarray(plan(x)), np.asarray(ref))


@needs_mesh
def test_explain_surfaces_fusion(monkeypatch):
    from distributedfft_tpu.explain import format_explain

    mesh = dfft.make_mesh((2, 4))
    plan = dfft.plan_dft_c2c_3d(SHAPE, mesh, dtype=CDT, executor="pallas",
                                wire_dtype="split", fuse=True)
    rec = dfft.explain(plan, iters=2)
    fu = rec["fusion"]
    assert fu["requested"] and fu["active"] and fu["sites"]
    assert "fusion: active" in format_explain(rec)
    gated = dfft.plan_dft_c2c_3d(SHAPE, mesh, dtype=CDT,
                                 executor="pallas", wire_dtype="split",
                                 overlap_chunks=2, fuse=True)
    rec2 = dfft.explain(gated, iters=2)
    assert rec2["fusion"]["requested"] and not rec2["fusion"]["active"]
    assert "overlap_k" in rec2["fusion"]["reasons"]
    assert "fusion: requested but gated off" in format_explain(rec2)


# --------------------------------------------- default-unfused HLO pin

@needs_mesh
@pytest.mark.parametrize("executor", ["xla", "pallas"])
def test_default_hlo_unchanged_by_fusion_tier(monkeypatch, executor):
    """The tier is invisible until asked for: a default plan's lowered
    HLO is byte-identical to an explicit ``fuse=False`` build."""
    monkeypatch.delenv("DFFT_FUSE", raising=False)
    mesh = dfft.make_mesh(8)
    kw = dict(dtype=CDT, executor=executor, wire_dtype="split")
    base = dfft.plan_dft_c2c_3d(SHAPE, mesh, **kw)
    pinned = dfft.plan_dft_c2c_3d(SHAPE, mesh, fuse=False, **kw)
    t_base = base.fn.lower(
        jax.ShapeDtypeStruct(base.in_shape, base.in_dtype)).as_text()
    t_pin = pinned.fn.lower(
        jax.ShapeDtypeStruct(pinned.in_shape, pinned.in_dtype)).as_text()
    assert t_base == t_pin


# ----------------------------- the kernels (interpret-mode CI smoke)

_ENC_BOUNDS = {"bf16": 8e-3, "int8": 2e-2, "split": 2e-4}


@pytest.mark.parametrize("codec_name", pallas_fuse.FUSABLE_CODECS)
@pytest.mark.parametrize("forward", [True, False])
def test_kernel_encode_matches_mirror(codec_name, forward):
    """FFT+encode mega-kernel vs the unfused chain, outside shard_map
    (the Pallas bodies run in interpret mode on CPU): decoded outputs
    agree within the codec's error; the pow2-step sidecars coincide
    exactly."""
    codec = wire_codec(codec_name)
    x = jnp.asarray(_world((8, 64)))
    assert pallas_fuse.kernel_ineligible(
        x.shape, 1, 1, 4, x.dtype, codec_name) is None
    parts = pallas_fuse.fused_fft_encode(
        x, fft_axis=1, forward=forward, tile_axis=1, tiles=4,
        wire_dtype=codec_name)
    y_fft = pallas_fft.fft_along_axis(x, 1, forward=forward)
    ref_parts = codec.encode(y_fft, tile_axis=1, tiles=4)
    got = np.asarray(codec.decode(parts, x.dtype, tile_axis=1, tiles=4))
    ref = np.asarray(codec.decode(ref_parts, x.dtype, tile_axis=1,
                                  tiles=4))
    scale = float(np.max(np.abs(np.asarray(y_fft))))
    assert float(np.max(np.abs(got - ref))) / scale \
        <= _ENC_BOUNDS[codec_name]
    if codec_name != "bf16":
        assert np.array_equal(np.asarray(parts[1]).ravel(),
                              np.asarray(ref_parts[1]).ravel())


@pytest.mark.parametrize("codec_name", pallas_fuse.FUSABLE_CODECS)
@pytest.mark.parametrize("forward", [True, False])
def test_kernel_decode_matches_mirror(codec_name, forward):
    """Decode+FFT mega-kernel vs the unfused chain: the unpack is exact
    (a cast / mantissa * pow2 product), so outputs agree to f32
    roundoff of the identical four-step transform."""
    codec = wire_codec(codec_name)
    y = jnp.asarray(_world((8, 64)))
    parts = codec.encode(y, tile_axis=1, tiles=4)
    got = pallas_fuse.fused_decode_fft(
        parts, y.dtype, fft_axis=1, forward=forward, tile_axis=1,
        tiles=4, wire_dtype=codec_name)
    ref = pallas_fft.fft_along_axis(
        codec.decode(parts, y.dtype, tile_axis=1, tiles=4), 1,
        forward=forward)
    scale = float(np.max(np.abs(np.asarray(ref))))
    assert float(np.max(np.abs(np.asarray(got) - np.asarray(ref)))) \
        / scale <= 1e-5, codec_name


def test_kernel_ineligibility_taxonomy():
    ok = ((8, 64), 1, 1, 4, jnp.complex64, "split")
    assert pallas_fuse.kernel_ineligible(*ok) is None
    cases = [
        (((8, 64), 1, 1, 4, jnp.complex64, "nope"), "codec"),
        (((8, 64), 1, 1, 4, jnp.complex128, "split"), "dtype"),
        (((8, 0), 1, 1, 4, jnp.complex64, "split"), "empty"),
        (((8, 64), 1, 0, 4, jnp.complex64, "split"), "tile_axis"),
        (((8, 24), 1, 1, 4, jnp.complex64, "split"), "length"),
        (((8, 64), 1, 1, 5, jnp.complex64, "split"), "uneven_tiles"),
    ]
    for args, why in cases:
        assert pallas_fuse.kernel_ineligible(*args) == why, args


def test_kernel_fallback_counted_never_error():
    """An ineligible site falls back to the mirror AND counts itself in
    the ``fusion_fallback`` series with site+reason labels."""
    m.metrics_reset()
    m.enable_metrics()
    try:
        x = jnp.asarray(_world((4, 10)).astype(np.complex128))
        parts = pallas_fuse.fused_fft_encode(
            x, fft_axis=1, forward=True, tile_axis=1, tiles=2,
            wire_dtype="split", site="t0")
        codec = wire_codec("split")
        ref = codec.encode(pallas_fft.fft_along_axis(x, 1, forward=True),
                           tile_axis=1, tiles=2)
        assert np.array_equal(np.asarray(parts[0]), np.asarray(ref[0]))
        assert m.counter_total("fusion_fallback") == 1.0
        snap = m.metrics_snapshot()["counters"]["fusion_fallback"]
        assert "reason=dtype" in next(iter(snap))
        assert "site=t0" in next(iter(snap))
    finally:
        m.enable_metrics(False)
        m.metrics_reset()


def test_pallas_fallback_counter_labels():
    """The satellite counter: pallas_fft.record_fallback feeds the
    ``pallas_fallback`` series with axis+reason labels."""
    m.metrics_reset()
    m.enable_metrics()
    try:
        pallas_fft.record_fallback(2, "length")
        assert m.counter_total("pallas_fallback") == 1.0
        snap = m.metrics_snapshot()["counters"]["pallas_fallback"]
        key = next(iter(snap))
        assert "axis=2" in key and "reason=length" in key
    finally:
        m.enable_metrics(False)
        m.metrics_reset()


# ---------------------------------------------------- model pricing

def test_fused_model_moves_fewer_hbm_bytes():
    """The pricing contract: fused stage pairs drop the intermediate
    f32 stream — a fused plan's modeled stage seconds are strictly
    below its unfused twin's wherever fusion is active."""
    from distributedfft_tpu.plan_logic import logic_plan3d, \
        model_stage_seconds

    opts = PlanOptions(decomposition="pencil", algorithm="alltoall",
                       executor="pallas:fuse", wire_dtype="split")
    lp = logic_plan3d((64, 64, 64), 8, opts)
    fused = fused_model_stages(lp, (64, 64, 64), 8)
    assert set(fused) == {"t0", "t1", "t3"}
    kw = dict(hbm_gbps=800.0, wire_gbps=50.0, launch_seconds=2e-6)
    base = model_stage_seconds(lp, (64, 64, 64), 8, **kw)
    disc = model_stage_seconds(lp, (64, 64, 64), 8, fused=fused, **kw)
    for st in fused:
        assert disc[st]["hbm_bytes"] < base[st]["hbm_bytes"], st
        assert disc[st]["seconds"] <= base[st]["seconds"], st
        assert disc[st]["fused"] is True


def test_fused_model_stages_gating():
    from distributedfft_tpu.plan_logic import logic_plan3d

    # No wire codec -> nothing to fuse into the stage kernels.
    lp = logic_plan3d((64, 64, 64), 8, PlanOptions(
        decomposition="pencil", executor="pallas:fuse"))
    assert fused_model_stages(lp, (64, 64, 64), 8) == ()
    # K=2 pipelines through chunked exchanges -> gated.
    lp = logic_plan3d((64, 64, 64), 8, PlanOptions(
        decomposition="pencil", executor="pallas:fuse",
        wire_dtype="split", overlap_chunks=2))
    assert fused_model_stages(lp, (64, 64, 64), 8) == ()
    # An unfused executor never prices the discount.
    lp = logic_plan3d((64, 64, 64), 8, PlanOptions(
        decomposition="pencil", executor="pallas", wire_dtype="split"))
    assert fused_model_stages(lp, (64, 64, 64), 8) == ()


# ------------------------------------------------- tuner integration

def test_enumerate_fused_candidates_only_where_activatable():
    cands = tuner.enumerate_candidates(
        SHAPE, 8, executors=("xla", "pallas"), wire_dtypes=WIRE_DTYPES)
    fused = [c for c in cands if ":fuse" in c.executor]
    assert fused, "fused variants must enter the tournament"
    assert all(c.executor == "pallas:fuse" for c in fused)
    assert all(c.wire_dtype is not None for c in fused)
    assert all(c.overlap_chunks == 1 for c in fused)
    lbl = next(c for c in fused if c.wire_dtype == "split").label
    assert "pallas:fuse" in lbl and lbl.endswith("+wsplit")


def test_fused_candidate_error_is_wire_error():
    cand = tuner.Candidate("slab", "alltoall", "pallas:fuse", 1, "split")
    assert tuner.candidate_roundtrip_error(cand, np.complex64) == \
        wire_roundtrip_error(np.complex64, "split")


def test_fused_candidate_models_cheaper():
    kw = dict(itemsize=8, batch=None, corrected=False)
    for wd in ("bf16", "int8", "split"):
        a = tuner.Candidate("pencil", "alltoall", "pallas", 1, wd)
        b = tuner.Candidate("pencil", "alltoall", "pallas:fuse", 1, wd)
        assert (tuner.model_cost(b, (64, 64, 64), 8, **kw)
                < tuner.model_cost(a, (64, 64, 64), 8, **kw)), wd


def test_prune_budget_governs_fused_candidates():
    cands = tuner.enumerate_candidates(
        SHAPE, 8, executors=("pallas",), wire_dtypes=WIRE_DTYPES)
    tight = tuner.prune_candidates(cands, SHAPE, 8, limit=64,
                                   max_err=1e-9, dtype=np.complex64)
    assert tight and all(":fuse" not in c.executor for c in tight)
    e_split = wire_roundtrip_error(np.complex64, "split")
    loose = tuner.prune_candidates(cands, SHAPE, 8, limit=64,
                                   max_err=e_split * 2,
                                   dtype=np.complex64)
    kept = [c for c in loose if ":fuse" in c.executor]
    assert kept and all(c.wire_dtype == "split" for c in kept)


def _fused_wisdom_entry(wisdom_path, err_budget, compression_err):
    key = tuner.wisdom_key(kind="c2c", shape=SHAPE, dtype=np.complex64,
                           direction=dfft.FORWARD, ndev=8,
                           mesh_dims=None, err_budget=err_budget)
    entry = {
        "schema": tuner.WISDOM_SCHEMA,
        "recorded_at": "2026-08-01T00:00:00", "key": key,
        "winner": {"decomposition": "slab", "algorithm": "alltoall",
                   "executor": "pallas:fuse", "overlap_chunks": 1,
                   "wire_dtype": "split"},
        "seconds": 0.001, "compression_err": compression_err,
    }
    with open(wisdom_path, "a") as f:
        f.write(json.dumps(entry) + "\n")


@needs_mesh
def test_fused_winner_replays_with_zero_timing(wisdom_path):
    dfft.clear_plan_cache()
    m.metrics_reset()
    m.enable_metrics()
    try:
        _fused_wisdom_entry(wisdom_path, err_budget=1e-3,
                            compression_err=3e-5)
        plan = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=CDT, tune="wisdom",
                                    max_roundtrip_err=1e-3)
        assert plan.executor == "pallas:fuse"
        assert plan.options.wire_dtype == "split"
        assert m.counter_total("tune_timing_executions") == 0
    finally:
        m.enable_metrics(False)
        m.metrics_reset()
        dfft.clear_plan_cache()


@needs_mesh
def test_fused_winner_rejected_strips_fuse_with_codec(wisdom_path):
    """Over budget, the codec goes — and the fuse flag with it (an
    exact-wire fused label could only gate off as no_wire_codec)."""
    dfft.clear_plan_cache()
    try:
        _fused_wisdom_entry(wisdom_path, err_budget=1e-9,
                            compression_err=3e-5)
        plan = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=CDT, tune="wisdom",
                                    max_roundtrip_err=1e-9)
        assert plan.options.wire_dtype is None
        assert plan.executor == "pallas"
    finally:
        dfft.clear_plan_cache()


# --------------------------------------------------- driver / regress tier

def test_regress_fusion_baseline_group():
    base = {"metric": "fft3d_c2c_512_forward_gflops", "value": 100.0,
            "dtype": "complex64", "devices": 8, "decomposition": "slab",
            "backend": "tpu", "device_kind": "TPU v5 lite",
            "wire_dtype": "split"}
    r0 = regress.normalize_bench_line(dict(base), source="test")
    rf = regress.normalize_bench_line(dict(base, fusion=True),
                                      source="test")
    assert rf["config"]["fusion"] is True
    assert regress.group_key(r0) != regress.group_key(rf)


def test_bench_emit_stamps_fusion(capsys):
    import os
    import sys
    TESTS = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(TESTS))
    import bench

    out = bench._emit(16, 1e-4, 1e-7, "pallas:fuse", 8, "slab",
                      {"pallas:fuse": 1e-4}, wire_dtype="split",
                      fusion=True)
    capsys.readouterr()
    assert out["fusion"] is True
    # Unfused rows keep the old schema — no key at all.
    out = bench._emit(16, 1e-4, 1e-7, "pallas", 8, "slab",
                      {"pallas": 1e-4})
    capsys.readouterr()
    assert "fusion" not in out


def test_speed3d_fuse_label():
    import os
    import sys
    TESTS = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(os.path.dirname(TESTS), "benchmarks"))
    from speed3d import _algorithm_label

    assert _algorithm_label("alltoall", 1, wire="split",
                            fuse=True) == "alltoall+wsplit+pfuse"
    assert _algorithm_label("alltoall", 1) == "alltoall"


def test_calibrate_profile_has_fuse_field():
    """The hwprofile schema carries the fused-tier throughput ratio
    (None off-TPU: interpret-mode timing would measure the
    interpreter, not the kernels)."""
    from distributedfft_tpu import calibrate

    prof = {"schema": calibrate.PROFILE_SCHEMA, "fuse_speedup": None}
    txt = calibrate.format_profile(prof)
    assert "fuse speedup" in txt
