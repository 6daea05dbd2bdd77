"""Mixed-precision MXU executor tiers (``ops/executors.py`` tier labels,
``PlanOptions.mm_precision``, the tuner's precision axis, and the
fft-thunk retirement path).

The accuracy tier of the matmul-family executors used to be a
process-global trace-time env read (``DFFT_MM_PRECISION``) — invisible
to the tuner and racy between a warm-pool preplan and a concurrent
tournament in one process. These tests pin the plan-scoped replacement:

1. **Tier labels are distinct executors** — ``matmul:bf16`` /
   ``matmul:f32`` / ``matmul:highest`` (and ``:gauss``) parse, compose
   idempotently, scope ``dft_matmul.mm_scope`` over their own trace,
   and two tiers coexist in one process (the global-knob race
   regression).
2. **Accuracy is a tuned dimension** — the candidate space crosses
   executors with tiers under a ``max_roundtrip_err`` budget, the
   measured tier error (``executor_roundtrip_error``) composes with the
   wire error into ONE budget, a stored reduced-precision winner never
   replays into a plan whose budget its recorded error violates, and an
   admissible replay pays zero timing executions.
3. **Thunk retirement** — with ``DFFT_THUNK_GUARD=matmul`` (armed by
   conftest for the whole suite) the known-poisoned chain class (CPU,
   uneven inverse pencil) plans through the matmul executor and
   executes correctly; everything outside the class keeps its executor.

NOTE on the filename: this module must collect BEFORE
``test_alltoallv.py`` (the ``test_a2*`` clean-backend convention —
``conftest._check_poison_collection_order`` enforces it on every run).
This file itself triggers no fft-layout fault: its only uneven inverse
pencil executions run the matmul executor, which never touches the FFT
thunk.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import distributedfft_tpu as dfft
from distributedfft_tpu import report, tuner
from distributedfft_tpu import testing as tu
from distributedfft_tpu.ops import dft_matmul, executors
from distributedfft_tpu.plan_logic import (
    PlanOptions,
    mm_dft_flops,
    model_stage_seconds,
)
from distributedfft_tpu.utils import metrics as m

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the virtual 8-device mesh"
)

SHAPE = (8, 8, 8)
UNEVEN = (10, 9, 7)


@pytest.fixture
def wisdom_path(tmp_path, monkeypatch):
    monkeypatch.setenv("DFFT_WISDOM", str(tmp_path / "wisdom.jsonl"))
    return str(tmp_path / "wisdom.jsonl")


@pytest.fixture
def fast_budget(monkeypatch):
    monkeypatch.setenv("DFFT_TUNE_ITERS", "1x1")


@pytest.fixture
def metrics_on():
    dfft.clear_plan_cache()
    m.metrics_reset()
    m.enable_metrics()
    yield
    m.enable_metrics(False)
    m.metrics_reset()
    dfft.clear_plan_cache()


# ------------------------------------------------------- label algebra

def test_split_executor_grammar():
    assert executors.split_executor("matmul") == ("matmul", None, None)
    assert executors.split_executor("matmul:bf16") == (
        "matmul", "bf16", None)
    assert executors.split_executor("matmul:bf16:gauss") == (
        "matmul", "bf16", "gauss")
    assert executors.split_executor("pallas:f32") == ("pallas", "f32", None)
    # The lax-name spellings of the bench menu grammar normalize.
    assert executors.split_executor("matmul:high") == ("matmul", "f32", None)
    assert executors.split_executor("matmul:default") == (
        "matmul", "bf16", None)
    with pytest.raises(ValueError, match="suffix"):
        executors.split_executor("matmul:fast")
    with pytest.raises(ValueError, match="two precision tiers"):
        executors.split_executor("matmul:bf16:f32")
    with pytest.raises(ValueError, match="matmul precision"):
        executors.split_executor("xla:bf16")


def test_tiered_name_composes_and_is_idempotent():
    assert executors.tiered_name("matmul", "bf16") == "matmul:bf16"
    assert executors.tiered_name("matmul:bf16") == "matmul:bf16"
    assert executors.tiered_name("matmul:bf16", "bf16") == "matmul:bf16"
    assert executors.tiered_name("matmul", "high") == "matmul:f32"
    assert executors.tiered_name("matmul", None, "gauss") == "matmul:gauss"
    assert executors.tiered_name("matmul", None, "native") == "matmul"
    assert executors.tiered_name("xla") == "xla"
    with pytest.raises(ValueError, match="already pins"):
        executors.tiered_name("matmul:bf16", "highest")
    with pytest.raises(ValueError, match="matmul precision"):
        executors.tiered_name("xla", "bf16")


def test_get_executor_accepts_tiered_labels():
    for name in ("matmul:bf16", "matmul:f32", "matmul:highest",
                 "matmul:gauss", "matmul:bf16:gauss"):
        fn = executors.get_executor(name)
        x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 8))
                        + 1j * np.random.default_rng(1).standard_normal(
                            (4, 8))).astype(jnp.complex64)
        y = np.asarray(fn(fn(x, (1,), True), (1,), False))
        assert np.max(np.abs(y - np.asarray(x))) < 1e-3
    with pytest.raises(ValueError, match="unknown executor"):
        executors.get_executor("nope")
    with pytest.raises(ValueError, match="matmul precision"):
        executors.get_executor("xla:bf16")


def test_mm_scope_overrides_env(monkeypatch):
    from jax import lax

    monkeypatch.setenv("DFFT_MM_PRECISION", "highest")
    monkeypatch.setenv("DFFT_MM_COMPLEX", "native")
    assert dft_matmul.mm_precision() == lax.Precision.HIGHEST
    with dft_matmul.mm_scope(precision="default", complex_mode="gauss"):
        assert dft_matmul.mm_precision() == lax.Precision.DEFAULT
        assert dft_matmul.complex_mode() == "gauss"
        with dft_matmul.mm_scope(precision="high"):
            assert dft_matmul.mm_precision() == lax.Precision.HIGH
            assert dft_matmul.complex_mode() == "gauss"  # outer survives
        assert dft_matmul.mm_precision() == lax.Precision.DEFAULT
    # The env default is back in force after the scope exits.
    assert dft_matmul.mm_precision() == lax.Precision.HIGHEST
    assert dft_matmul.complex_mode() == "native"


# ---------------------------------------------- plan-scoped tier plans

def test_two_tiers_coexist_in_one_process():
    """The global-knob race regression: two precision tiers planned
    back-to-back in one process are DISTINCT plans (labels, options,
    cache entries) and both execute correctly — the env knob is a
    default, not shared state."""
    dfft.clear_plan_cache()
    hi = dfft.plan_dft_c2c_3d(SHAPE, None, executor="matmul",
                              mm_precision="highest", dtype=np.complex64)
    lo = dfft.plan_dft_c2c_3d(SHAPE, None, executor="matmul",
                              mm_precision="bf16", dtype=np.complex64)
    assert hi.executor == "matmul:highest" and lo.executor == "matmul:bf16"
    assert hi.options.mm_precision == "highest"
    assert lo.options.mm_precision == "bf16"
    assert hi is not lo and hi.fn is not lo.fn
    x = tu.make_world_data(SHAPE, dtype=np.complex64)
    want = np.fft.fftn(x)
    for plan in (hi, lo):
        got = np.asarray(plan(x))
        assert np.max(np.abs(got - want)) / np.abs(want).max() < 1e-3
    # Same call again hits the plan cache per tier (no cross-tier mixup).
    assert dfft.plan_dft_c2c_3d(SHAPE, None, executor="matmul",
                                mm_precision="bf16",
                                dtype=np.complex64) is lo


def test_executor_label_spelling_backfills_options():
    plan = dfft.plan_dft_c2c_3d(SHAPE, None, executor="matmul:high:gauss",
                                dtype=np.complex64)
    assert plan.options.mm_precision == "f32"
    assert plan.options.mm_complex == "gauss"
    assert plan.executor == "matmul:f32:gauss"  # canonical label


def test_tier_equals_env_default_hlo_pin(monkeypatch):
    """Byte-identical pin: an explicit tier compiles exactly the program
    the same tier reaches via the env default — the scope changes WHERE
    the knob is read, never what is traced. (And mm_precision=None with
    no env knobs is the bare executor unchanged.)"""
    dfft.clear_plan_cache()
    monkeypatch.delenv("DFFT_MM_PRECISION", raising=False)
    x = jnp.zeros(SHAPE, jnp.complex64)
    scoped = dfft.plan_dft_c2c_3d(SHAPE, None, executor="matmul",
                                  mm_precision="bf16", dtype=np.complex64)
    monkeypatch.setenv("DFFT_MM_PRECISION", "default")
    dfft.clear_plan_cache()
    env = dfft.plan_dft_c2c_3d(SHAPE, None, executor="matmul",
                               dtype=np.complex64)
    assert env.executor == "matmul"  # env default: bare label, old path
    a = jax.jit(scoped.fn).lower(x).as_text()
    b = jax.jit(env.fn).lower(x).as_text()
    assert a == b
    # The exact tier == the unset-env default program, byte for byte.
    monkeypatch.delenv("DFFT_MM_PRECISION", raising=False)
    dfft.clear_plan_cache()
    bare = dfft.plan_dft_c2c_3d(SHAPE, None, executor="matmul",
                                dtype=np.complex64)
    exact = dfft.plan_dft_c2c_3d(SHAPE, None, executor="matmul",
                                 mm_precision="highest",
                                 dtype=np.complex64)
    assert (jax.jit(bare.fn).lower(x).as_text()
            == jax.jit(exact.fn).lower(x).as_text())


@needs_mesh
@pytest.mark.parametrize("tier", ["bf16", "f32"])
@pytest.mark.parametrize("shape,mesh_dims,batch", [
    (SHAPE, None, None),          # slab (1D from int), even
    (UNEVEN, (2, 4), None),       # pencil, uneven
    (SHAPE, None, 3),             # slab, batched
])
def test_c64_roundtrip_bounds_per_tier(tier, shape, mesh_dims, batch):
    """c64 forward->inverse round trip stays within the tier's measured
    error envelope across slab/pencil x uneven x batch — the bound the
    budget admission is declared against."""
    mesh = dfft.make_mesh(mesh_dims) if mesh_dims else dfft.make_mesh(8)
    kw = dict(dtype=np.complex64, executor=f"matmul:{tier}")
    if batch:
        kw["batch"] = batch
    fwd = dfft.plan_dft_c2c_3d(shape, mesh, **kw)
    bwd = dfft.plan_dft_c2c_3d(shape, mesh, direction=dfft.BACKWARD, **kw)
    full = shape if not batch else (batch,) + tuple(shape)
    x = tu.make_world_data(full, dtype=np.complex64)
    r = np.asarray(bwd(fwd(x)))
    err = np.max(np.abs(r - np.asarray(x))) / np.abs(np.asarray(x)).max()
    # Generous per-tier envelope: honest on TPU (bf16 ~1e-2) and tiny on
    # the CPU backend (lax precision collapses to native kernels there).
    bound = 2e-2 if tier == "bf16" else 1e-3
    assert err < bound, (tier, shape, batch, err)


# ------------------------------------------------ measured tier errors

def test_executor_roundtrip_error_conventions():
    assert executors.executor_roundtrip_error("xla", np.complex64) == 0.0
    assert executors.executor_roundtrip_error("matmul", np.complex64) == 0.0
    assert executors.executor_roundtrip_error(
        "matmul:highest", np.complex64) == 0.0  # the exact tier
    assert executors.executor_roundtrip_error(
        "matmul:gauss", np.complex64) == 0.0
    e1 = executors.executor_roundtrip_error("matmul:bf16", np.complex64)
    assert e1 >= 0.0
    # Cached: the second call returns the identical float (no re-measure).
    assert executors.executor_roundtrip_error(
        "matmul:bf16", np.complex64) == e1


def test_candidate_roundtrip_error_sums_axes():
    from distributedfft_tpu.parallel.exchange import wire_roundtrip_error

    wire = wire_roundtrip_error(np.complex64, "bf16")
    tier = executors.executor_roundtrip_error("matmul:bf16", np.complex64)
    c = tuner.Candidate("slab", "alltoall", "matmul:bf16", 1, "bf16")
    assert tuner.candidate_roundtrip_error(c, np.complex64) == pytest.approx(
        wire + tier)
    exact = tuner.Candidate("slab", "alltoall", "xla", 1, None)
    assert tuner.candidate_roundtrip_error(exact, np.complex64) == 0.0


def test_enumerate_crosses_tiers_and_prune_filters():
    cands = tuner.enumerate_candidates(
        (16, 16, 16), 8, executors=["xla", "matmul"],
        mm_tiers=(None, "bf16", "f32"))
    assert {c.executor for c in cands} == {
        "xla", "matmul", "matmul:bf16", "matmul:f32"}
    # An impossible budget strips every reduced-accuracy candidate ...
    tight = tuner.prune_candidates(cands, (16, 16, 16), 8, limit=64,
                                   max_err=1e-30, dtype=np.complex64)
    assert tight
    assert all(c.wire_dtype is None and ":" not in c.executor
               for c in tight)
    # ... while a loose one keeps the tier axis in play.
    loose = tuner.prune_candidates(cands, (16, 16, 16), 8, limit=64,
                                   max_err=1e-1, dtype=np.complex64)
    assert any(":bf16" in c.executor for c in loose)


def test_model_cost_ranks_tiers_before_any_compile(monkeypatch):
    """At a compute-bound shape the bf16 tier's modeled cost undercuts
    f32 undercuts exact — precision is rankable pre-compile."""
    monkeypatch.setenv("DFFT_HW_PROFILE", "0")
    shape = (512, 512, 512)

    def cost(ex):
        return tuner.model_cost(
            tuner.Candidate("slab", "alltoall", ex, 1), shape, 8)

    assert cost("matmul:bf16") < cost("matmul:f32") <= cost("matmul")
    # Non-matmul executors are untouched by the tier term.
    assert cost("xla") <= cost("matmul")


def test_mm_tier_tflops_profile_override(tmp_path, monkeypatch):
    from distributedfft_tpu import calibrate

    assert tuner.mm_tier_tflops("xla") is None
    assert tuner.mm_tier_tflops("matmul") == tuner.MODEL_MM_TFLOPS[
        "highest"]
    assert tuner.mm_tier_tflops("matmul:bf16") == tuner.MODEL_MM_TFLOPS[
        "bf16"]
    path = str(tmp_path / "hw.json")
    monkeypatch.setenv("DFFT_HW_PROFILE", path)
    kind, platform = calibrate._current_identity()
    calibrate.write_profile({
        "schema": calibrate.PROFILE_SCHEMA, "device_kind": kind,
        "platform": platform, "hbm_gbps": 100.0,
        "mm_bf16_tflops": 40.0, "mm_f32_tflops": 10.0}, path)
    assert tuner.mm_tier_tflops("matmul:bf16") == 40.0
    assert tuner.mm_tier_tflops("matmul:f32") == 10.0
    assert tuner.mm_tier_tflops("matmul") == 5.0        # derived: f32/2
    assert tuner.mm_tier_tflops("matmul:highest") == 5.0


def test_calibrate_measures_mm_tier_fields(monkeypatch):
    from distributedfft_tpu import calibrate

    prof = calibrate.calibrate(iters=1, wire=False)
    assert prof["mm_bf16_tflops"] is None or prof["mm_bf16_tflops"] > 0
    assert prof["mm_f32_tflops"] is None or prof["mm_f32_tflops"] > 0
    text = calibrate.format_profile(prof)
    assert "matmul bf16" in text and "matmul f32" in text


def test_model_stage_seconds_mm_pricing():
    from distributedfft_tpu.plan_logic import logic_plan3d

    lp = logic_plan3d((64, 64, 64), None, PlanOptions(tune="off"))
    base = model_stage_seconds(lp, (64, 64, 64), 8, hbm_gbps=819.0,
                               wire_gbps=45.0, launch_seconds=1e-4)
    slow = model_stage_seconds(lp, (64, 64, 64), 8, hbm_gbps=819.0,
                               wire_gbps=45.0, launch_seconds=1e-4,
                               mm_tflops=0.001)  # absurdly slow tier
    assert "mm_flops" not in base["t0"]
    assert slow["t0"]["mm_flops"] > 0
    assert slow["t0"]["seconds"] > base["t0"]["seconds"]
    # A fast tier floors at the HBM stream — never faster than memory.
    fast = model_stage_seconds(lp, (64, 64, 64), 8, hbm_gbps=819.0,
                               wire_gbps=45.0, launch_seconds=1e-4,
                               mm_tflops=1e9)
    assert fast["t0"]["seconds"] == base["t0"]["seconds"]
    assert mm_dft_flops((4, 4, 4)) == 3 * 8.0 * 64 * 4
    assert mm_dft_flops((4, 4, 4), (2,)) == 8.0 * 64 * 4


# ------------------------------------------- budget admission (wisdom)

def _seed_entry(path, key, executor, wire_dtype=None, precision_err=None,
                compression_err=None):
    entry = {
        "schema": tuner.WISDOM_SCHEMA, "key": key,
        "winner": {"decomposition": "slab", "algorithm": "alltoall",
                   "executor": executor, "overlap_chunks": 1,
                   "wire_dtype": wire_dtype},
        "seconds": 1e-3,
    }
    if precision_err is not None:
        entry["precision_err"] = precision_err
    if compression_err is not None:
        entry["compression_err"] = compression_err
    with open(path, "a") as f:
        f.write(json.dumps(entry) + "\n")


@needs_mesh
def test_tier_winner_never_replays_into_tighter_budget(
        wisdom_path, fast_budget, metrics_on):
    """Property sweep: a stored bf16-tier winner replays tiered only
    into plans whose budget admits its recorded error; a tighter budget
    rebuilds the exact bare tuple — with zero timing executions either
    way (the lookup is a hit in both cases)."""
    rec_err = 1e-3
    for budget, admitted in ((5e-4, False), (1e-3, True), (1e-2, True),
                             (9.9e-4, False)):
        key = tuner.wisdom_key(kind="c2c", shape=SHAPE, dtype=np.complex64,
                               direction=-1, ndev=8, err_budget=budget)
        _seed_entry(wisdom_path, key, "matmul:bf16",
                    precision_err=rec_err)
        dfft.clear_plan_cache()
        m.metrics_reset()
        plan = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=np.complex64,
                                    tune="measure",
                                    max_roundtrip_err=budget)
        assert m.counter_total("tune_timing_executions") == 0, budget
        if admitted:
            assert plan.executor == "matmul:bf16", (budget, plan.executor)
        else:
            assert plan.executor == "matmul", (budget, plan.executor)


@needs_mesh
def test_combined_wire_and_tier_errors_share_one_budget(
        wisdom_path, fast_budget, metrics_on):
    """Each axis alone fits the budget; the sum does not — the stored
    compressed+tiered winner must rebuild fully exact (bare label AND
    exact wire)."""
    budget = 1e-2
    key = tuner.wisdom_key(kind="c2c", shape=SHAPE, dtype=np.complex64,
                           direction=-1, ndev=8, err_budget=budget)
    _seed_entry(wisdom_path, key, "matmul:bf16", wire_dtype="bf16",
                precision_err=6e-3, compression_err=6e-3)
    dfft.clear_plan_cache()
    plan = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=np.complex64,
                                tune="measure", max_roundtrip_err=budget)
    assert plan.executor == "matmul"
    assert plan.options.wire_dtype is None
    assert m.counter_total("tune_timing_executions") == 0
    # And when the sum fits, both axes replay.
    key2 = tuner.wisdom_key(kind="c2c", shape=SHAPE, dtype=np.complex64,
                            direction=1, ndev=8, err_budget=budget)
    _seed_entry(wisdom_path, key2, "matmul:bf16", wire_dtype="bf16",
                precision_err=4e-3, compression_err=4e-3)
    dfft.clear_plan_cache()
    plan2 = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=np.complex64,
                                 direction=dfft.BACKWARD, tune="measure",
                                 max_roundtrip_err=budget)
    assert plan2.executor == "matmul:bf16"
    assert plan2.options.wire_dtype == "bf16"


@needs_mesh
def test_budgetless_plan_never_replays_reduced_tier(
        wisdom_path, fast_budget, metrics_on):
    key = tuner.wisdom_key(kind="c2c", shape=SHAPE, dtype=np.complex64,
                           direction=-1, ndev=8)
    _seed_entry(wisdom_path, key, "matmul:bf16", precision_err=1e-7)
    dfft.clear_plan_cache()
    plan = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=np.complex64,
                                tune="measure")
    assert plan.executor == "matmul"  # exact rebuild, tier stripped


@needs_mesh
def test_measure_tournament_precision_axis_end_to_end(
        wisdom_path, fast_budget, metrics_on, monkeypatch):
    """Acceptance: a measure tournament over the joint
    (precision x wire x transport) space under a budget selects a
    winner, records its tier/errors, and an identically-keyed call
    replays it with ZERO timing executions."""
    monkeypatch.setenv("DFFT_TUNE_MAX", "12")
    monkeypatch.setenv("DFFT_AUTO_EXECUTORS", "xla,matmul")
    plan = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=np.complex64,
                                tune="measure", max_roundtrip_err=1e-2)
    assert m.counter_total("tune_tournaments") == 1
    entries, dropped = tuner.load_wisdom(wisdom_path)
    assert dropped == 0 and len(entries) == 1
    entry = list(entries.values())[0]
    timed = set(entry["times"])
    # The measured space really crossed precision with wire/transport.
    assert any(":bf16" in t for t in timed), timed
    assert any("+wbf16" in t for t in timed), timed
    assert any(t.split("/")[1] != "alltoall" for t in timed), timed
    lbl = tuner.tuned_label(plan)
    assert lbl in timed
    dfft.clear_plan_cache()
    m.metrics_reset()
    plan2 = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=np.complex64,
                                 tune="measure", max_roundtrip_err=1e-2)
    assert m.counter_total("tune_timing_executions") == 0
    assert m.counter_total("tune_tournaments") == 0
    assert tuner.tuned_label(plan2) == lbl
    x = tu.make_world_data(SHAPE, dtype=np.complex64)
    got = np.asarray(plan2(x))
    want = np.fft.fftn(x)
    assert np.max(np.abs(got - want)) / np.abs(want).max() < 1e-2


@needs_mesh
def test_explicit_tier_pin_isolated_in_wisdom(wisdom_path, fast_budget,
                                              metrics_on):
    k_open = tuner.wisdom_key(kind="c2c", shape=SHAPE, dtype=np.complex64,
                              direction=-1, ndev=8)
    k_pin = tuner.wisdom_key(kind="c2c", shape=SHAPE, dtype=np.complex64,
                             direction=-1, ndev=8, mm_precision="bf16")
    assert tuner._key_id(k_open) != tuner._key_id(k_pin)
    plan = dfft.plan_dft_c2c_3d(SHAPE, 8, dtype=np.complex64,
                                tune="measure", executor="matmul",
                                mm_precision="bf16")
    entries, _ = tuner.load_wisdom(wisdom_path)
    entry = list(entries.values())[0]
    assert entry["key"]["mm_precision"] == "bf16"
    # Every matmul-family candidate carried the pinned tier; no bare
    # matmul label entered the pinned tournament.
    assert all(":bf16" in t for t in entry["times"]
               if t.split("/")[2].startswith("matmul")), entry["times"]


# ----------------------------------------------- labels, stamps, gates

def test_winner_label_agreement_for_precision_tuples():
    c = tuner.Candidate("slab", "alltoall", "matmul:bf16", 2, "bf16")
    w = {"decomposition": "slab", "algorithm": "alltoall",
         "executor": "matmul:bf16", "overlap_chunks": 2,
         "wire_dtype": "bf16"}
    assert report._winner_label(w) == c.label
    # Out-of-band tier field (older/foreign entries) folds into the
    # executor term instead of silently never matching history rows.
    w2 = {"decomposition": "slab", "algorithm": "alltoall",
          "executor": "matmul", "overlap_chunks": 2,
          "wire_dtype": None, "mm_precision": "bf16"}
    assert report._winner_label(w2) == "slab/alltoall/matmul:bf16/ov2"


def test_regress_keys_precision_into_baseline_group():
    from distributedfft_tpu import regress

    base = {"metric": "fft3d_c2c_64_forward_gflops", "value": 10.0,
            "unit": "GFlops/s", "seconds": 0.1, "dtype": "complex64",
            "backend": "cpu", "devices": 8, "decomposition": "slab",
            "executor": "matmul"}
    exact = regress.normalize_bench_line(dict(base), source="t")
    tiered = regress.normalize_bench_line(dict(base, precision="bf16"),
                                          source="t")
    assert "precision" not in exact["config"]
    assert tiered["config"]["precision"] == "bf16"
    assert "precision=bf16" in regress.config_signature(tiered)
    assert regress.group_key(exact) != regress.group_key(tiered)


def test_bench_stamps_precision(tmp_path, monkeypatch):
    import bench

    class P:  # minimal plan stand-in
        class options:
            wire_dtype = None
            algorithm = "alltoall"
            mm_precision = "bf16"

    kw = bench._plan_wire_kw(P)
    assert kw["precision"] == "bf16"
    monkeypatch.setenv("DFFT_BENCH_HISTORY", "0")
    out = bench._emit(8, 0.5, 1e-6, "matmul:bf16", 1, "single",
                      {"matmul:bf16": 0.5}, **kw)
    assert out["precision"] == "bf16"
    out2 = bench._emit(8, 0.5, 1e-6, "xla", 1, "single", {"xla": 0.5},
                       wire_dtype=None, transport="alltoall",
                       precision=None)
    assert "precision" not in out2  # default rows keep the old schema


def test_speed3d_algorithm_label_mm_suffix():
    from benchmarks.speed3d import _algorithm_label, _executor_label

    assert _algorithm_label("alltoall", 1, mm="bf16") == "alltoall+mmbf16"
    assert _algorithm_label("alltoall", 1) == "alltoall"
    # A tiered label pins its own knobs: the env suffix must not lie.
    import os

    old = os.environ.get("DFFT_MM_PRECISION")
    os.environ["DFFT_MM_PRECISION"] = "high"
    try:
        assert _executor_label("matmul:bf16") == "matmul:bf16"
        assert "high" in _executor_label("matmul")
    finally:
        if old is None:
            os.environ.pop("DFFT_MM_PRECISION", None)
        else:
            os.environ["DFFT_MM_PRECISION"] = old


def test_explain_stamps_tier(metrics_on):
    plan = dfft.plan_dft_c2c_3d(SHAPE, None, executor="matmul:bf16",
                                dtype=np.complex64)
    rec = dfft.explain(plan, measure=False)
    assert rec["plan"]["mm_precision"] == "bf16"
    assert rec["plan"]["mm_tflops"] == tuner.MODEL_MM_TFLOPS["bf16"]
    assert rec["stages"]["t0"]["model"].get("mm_flops", 0) > 0


# --------------------------------------------------- thunk retirement

@needs_mesh
def test_thunk_guard_routes_poisoned_class_only():
    """conftest arms DFFT_THUNK_GUARD=matmul: the uneven inverse pencil
    class (the fft_thunk.cc:69 RET_CHECK geometry) plans through the
    matmul executor and executes CORRECTLY; everything outside the
    class keeps its requested executor."""
    mesh = dfft.make_mesh((2, 4))
    bwd = dfft.plan_dft_c2c_3d(UNEVEN, mesh, dtype=np.complex128,
                               direction=dfft.BACKWARD)
    assert bwd.executor == "matmul"
    assert bwd.options.executor == "matmul"
    x = tu.make_world_data(UNEVEN, dtype=np.complex128)
    got = np.asarray(bwd(x))
    want = np.fft.ifftn(x)
    assert np.max(np.abs(got - want)) / np.abs(want).max() < 1e-11
    # c2r over the same geometry is in the class too.
    c2r = dfft.plan_dft_c2r_3d(UNEVEN, mesh, dtype=np.complex128)
    assert c2r.executor == "matmul"
    # The starved MINOR-AXIS slab chain (input slabs on axis 2 with
    # zero-extent shards) is the second class.
    from jax.sharding import PartitionSpec as P

    sl = dfft.plan_dft_c2c_3d((8, 8, 6), dfft.make_mesh(7),
                              dtype=np.complex128,
                              in_spec=P(None, None, "slab"))
    assert sl.logic.slab_axes[0] == 2
    assert sl.executor == "matmul"
    xs = tu.make_world_data((8, 8, 6), dtype=np.complex128)
    got = np.asarray(sl(xs))
    want = np.fft.fftn(xs)
    assert np.max(np.abs(got - want)) / np.abs(want).max() < 1e-11
    # Outside the classes: forward uneven pencil, even inverse pencil,
    # and major-axis slab chains — starved or merely uneven — all
    # untouched (substituting there would break the executor-sensitive
    # bitwise-parity contracts for no protection).
    assert dfft.plan_dft_c2c_3d(UNEVEN, mesh,
                                dtype=np.complex128).executor == "xla"
    assert dfft.plan_dft_c2c_3d((16, 12, 20), mesh, dtype=np.complex128,
                                direction=dfft.BACKWARD).executor == "xla"
    assert dfft.plan_dft_c2c_3d(UNEVEN, dfft.make_mesh(8),
                                dtype=np.complex128,
                                direction=dfft.BACKWARD).executor == "xla"
    assert dfft.plan_dft_c2c_3d((14, 12, 9), dfft.make_mesh(4),
                                dtype=np.complex128,
                                direction=dfft.BACKWARD).executor == "xla"


@needs_mesh
def test_thunk_guard_off_leaves_planning_untouched(monkeypatch):
    monkeypatch.setenv("DFFT_THUNK_GUARD", "")
    dfft.clear_plan_cache()
    mesh = dfft.make_mesh((2, 4))
    # Build only — executing this plan would trip the fault and poison
    # the process for every later 8-device test (jit traces lazily, so
    # planning is safe).
    bwd = dfft.plan_dft_c2c_3d(UNEVEN, mesh, dtype=np.complex128,
                               direction=dfft.BACKWARD)
    assert bwd.executor == "xla"
    dfft.clear_plan_cache()


def test_env_default_escape_hatch(monkeypatch):
    monkeypatch.setenv("DFFT_EXECUTOR", "matmul")
    dfft.clear_plan_cache()
    plan = dfft.plan_dft_c2c_3d(SHAPE, None, dtype=np.complex64)
    assert plan.executor == "matmul"
    # An explicitly non-default executor always wins over the env.
    plan2 = dfft.plan_dft_c2c_3d(SHAPE, None, executor="xla_minor",
                                 dtype=np.complex64)
    assert plan2.executor == "xla_minor"
    monkeypatch.delenv("DFFT_EXECUTOR")
    dfft.clear_plan_cache()
    assert dfft.plan_dft_c2c_3d(SHAPE, None,
                                dtype=np.complex64).executor == "xla"


def test_plan_options_validates_tiers():
    assert PlanOptions(mm_precision="bf16").mm_precision == "bf16"
    assert PlanOptions(mm_precision="high").mm_precision == "f32"
    assert PlanOptions(mm_precision=" ").mm_precision is None
    assert PlanOptions(mm_complex="gauss").mm_complex == "gauss"
    with pytest.raises(ValueError, match="mm_precision"):
        PlanOptions(mm_precision="fast")
    with pytest.raises(ValueError, match="mm_complex"):
        PlanOptions(mm_complex="karatsuba")
