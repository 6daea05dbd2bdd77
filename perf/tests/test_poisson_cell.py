"""The ``poisson512-r2c`` cell, rehearsed on the CPU at ``TINY``: its run
with and without the trace, and ``correct`` coming out false for its
control and for each fault the cell can have, the multiplier left out
among them (the bare r2c/c2r pair would pass that one: its reference
is the input itself).
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

import distributedfft_tpu as dfft
from distributedfft_tpu import operators
from distributedfft_tpu.ops import dft_matmul
from perf import run as harness

from test_harness import (_answer_altered, _bf16_mxu_einsum, _half_left_out,
                          _identity)

CELL = "poisson512-r2c"


@pytest.fixture(autouse=True)
def fresh_plans():
    """Plans are memoized by the program; a fault planted in one test
    must not reach the next."""
    dfft.clear_plan_cache()
    jax.clear_caches()
    yield
    dfft.clear_plan_cache()
    jax.clear_caches()


def _run(root, trace=False, **kw):
    return harness.run_cell(harness.load_cell(CELL, root), 2**33 + 11, 0.3,
                            trace, **kw)


@pytest.mark.parametrize("trace", [False, True])
def test_poisson_cell_rehearsal(tiny_root, trace):
    line = _run(tiny_root, trace)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["checks"]) == {"rel_l2_sample", "rel_l2_last",
                                   "misplaced_outputs"}
    assert line["checks"]["rel_l2_last"]["value"] < 1e-5
    assert line["device"]["count"] == 1
    if trace:
        assert "plan_s" in line["metrics"] and "breakdown" in line
        assert not {"exchange_ms", "exchange_exposed_ms"} & set(
            line["metrics"])
    else:
        assert set(line["metrics"]) == {"gflops", "setup_s"}


def test_poisson_cell_runs_a_real_operator_plan(tiny_root):
    cell = harness.load_cell(CELL, tiny_root)
    plan = dfft.solve_poisson(tuple(cell.config["shape"]), None,
                              direction=-1, dtype=jnp.complex64,
                              **cell.config["planner_kwargs"])
    assert isinstance(plan, dfft.OpPlan3D) and plan.real
    assert plan.in_dtype == plan.out_dtype == jnp.float32
    assert plan.executor == "xla"


def _multiplier_left_out(monkeypatch):
    """The solve with G = 1: an r2c and a c2r around nothing."""
    unit = operators.custom("unit", lambda i0, i1, i2: jnp.float32(1.0))

    def solve(shape, mesh=None, **kw):
        return operators.plan_spectral_op(shape, mesh, op=unit, **kw)

    monkeypatch.setattr(dfft, "solve_poisson", solve)


def _wrap(breaks):
    def plant(monkeypatch):
        planner = dfft.solve_poisson

        def broken(*a, **k):
            plan = planner(*a, **k)
            return dataclasses.replace(plan, fn=breaks(plan.fn))

        monkeypatch.setattr(dfft, "solve_poisson", broken)
    return plant


@pytest.mark.parametrize("plant", [
    _wrap(_identity), _wrap(_half_left_out), _wrap(_answer_altered),
    _multiplier_left_out],
    ids=["state_unchanged", "half_left_out", "answer_altered",
         "multiplier_left_out"])
def test_poisson_fault_is_not_correct(tiny_root, monkeypatch, plant):
    plant(monkeypatch)
    line = _run(tiny_root)
    assert not line["correct"] and line["failed"] >= 1


def test_poisson_control_is_not_correct(tiny_root, monkeypatch):
    """The configuration's ``control``, the program's own bf16 path, run
    through the harness: the check refuses it by more than twice the
    limit."""
    monkeypatch.setattr(dft_matmul.jnp, "einsum",
                        _bf16_mxu_einsum(jnp.einsum))
    c = harness.load_cell(CELL, tiny_root)
    line = harness.run_cell(c, 13, 0.3, overrides=c.config["control"])
    assert not line["correct"]
    assert line["checks"]["rel_l2_last"]["value"] > 2 * c.config["tolerance"]
