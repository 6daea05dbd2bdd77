"""The harness, rehearsed on the CPU at a tiny size: each cell's run,
cells and traffic found by name, no result off the TPU, and ``correct``
coming out false for the control and for each fault the cells can have.

These runs skip the harness's look for a chip (``run.prepare``) and
drive the rest of a run (plans, window, check) on four virtual devices.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import distributedfft_tpu as dfft
from distributedfft_tpu.ops import dft_matmul
from perf import run as harness

from conftest import ROOT

CELLS = ("c2c512-fwd", "c2c1024-slab4")
E2E = {"gflops", "setup_s"}  # peak_hbm_gib: the CPU reports no peak


@pytest.fixture(autouse=True)
def fresh_plans():
    """Plans are memoized by the program; a fault planted in one test
    must not reach the next."""
    dfft.clear_plan_cache()
    jax.clear_caches()
    yield
    dfft.clear_plan_cache()
    jax.clear_caches()


def _run(root, cell, trace=False, **kw):
    return harness.run_cell(harness.load_cell(cell, root), 2**33 + 7, 0.3,
                            trace, **kw)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(tiny_root, cell, trace):
    line = _run(tiny_root, cell, trace)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"rel_l2_sample", "rel_l2_last",
                                   "misplaced_outputs"}
    assert line["device"]["count"] == (4 if "slab4" in cell else 1)
    if trace:
        assert "plan_s" in line["metrics"] and "breakdown" in line
        assert line["device"]["window_s"] > 0
    else:
        assert set(line["metrics"]) == E2E


def test_same_seed_same_input(tiny_root):
    a = _run(tiny_root, "c2c512-fwd")["checks"]["rel_l2_last"]["value"]
    b = _run(tiny_root, "c2c512-fwd")["checks"]["rel_l2_last"]["value"]
    assert a == b


def test_new_traffic_cell_and_metric_found_by_name(tiny_root):
    """A later PR adds a traffic file, a metric reader and a cell entry;
    the harness finds them by name, and no other file changes."""
    before = {p: p.read_bytes() for p in tiny_root.rglob("*") if p.is_file()}
    traffic = tiny_root / "perf" / "traffic" / "fwd-loop-serial.json"
    traffic.write_text(json.dumps({"chain": ["forward"], "in_flight": 1,
                                   "warmup": 1, "sample_within": 2}))
    reader = tiny_root / "perf" / "metrics" / "steps_done.py"
    reader.write_text("def read(run):\n    return float(run.steps)\n")
    bench_path = tiny_root / "BENCHMARK.json"
    bench = json.loads(bench_path.read_text())
    bench["workloads"].append({"name": "c2c512-serial",
                               "config": "speed3d-c2c-512",
                               "traffic": "fwd-loop-serial", "chips": 1,
                               "why": "one in flight"})
    bench["end_to_end"].append({"name": "steps_done", "unit": "steps",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["c2c512-serial"]})
    bench_path.write_text(json.dumps(bench))
    after = {p: p.read_bytes() for p in tiny_root.rglob("*") if p.is_file()}
    changed = {p for p in after if before.get(p) != after[p]}
    assert changed == {traffic, reader, bench_path}

    cell = harness.load_cell("c2c512-serial", tiny_root)
    assert cell.traffic["in_flight"] == 1
    line = harness.run_cell(cell, 3, 0.3)
    assert line["correct"]
    assert line["metrics"]["steps_done"]["value"] == line["attempted"]
    # the committed cells do not see the new metric
    assert "steps_done" not in _run(tiny_root, "c2c512-fwd")["metrics"]


def test_no_result_off_the_tpu(tmp_path):
    """``run.py`` on the CPU prints nothing on stdout and exits non-zero,
    for every cell."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for cell in CELLS:
        p = subprocess.run(
            [sys.executable, "perf/run.py", "--workload", cell, "--seed",
             "1", "--seconds", "1", "--trace", "0"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
        assert p.returncode != 0 and p.stdout == "", (cell, p.stderr[-500:])
        assert "No result" in p.stderr


# --- controls and faults: each must turn `correct` false --------------------


def _wrap_planner(monkeypatch, breaks):
    """Plant ``breaks(fn) -> fn`` under every plan the planner returns."""
    planner = dfft.plan_dft_c2c_3d

    def broken(*a, **k):
        plan = planner(*a, **k)
        return dataclasses.replace(plan, fn=breaks(plan.fn))

    monkeypatch.setattr(dfft, "plan_dft_c2c_3d", broken)


def _identity(fn):
    """A step that returns its state unchanged."""
    return lambda x: x


def _half_left_out(fn):
    """Half of the input's planes left out, the rest counted double."""
    def f(x):
        keep = jnp.arange(x.shape[0]) < x.shape[0] // 2
        return fn(jnp.where(keep[:, None, None], 2 * x, 0))
    return f


def _answer_altered(fn):
    """The answer altered where it is produced: its first plane negated."""
    return lambda x: fn(x).at[0].multiply(-1)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_identity, _half_left_out, _answer_altered],
                         ids=["state_unchanged", "half_left_out",
                              "answer_altered"])
def test_fault_is_not_correct(tiny_root, monkeypatch, cell, fault):
    _wrap_planner(monkeypatch, fault)
    line = _run(tiny_root, cell)
    assert not line["correct"] and line["failed"] >= 1


def test_exchange_left_out_is_not_correct(tiny_root, monkeypatch):
    """The t2 all-to-all replaced by each chip reordering its own block:
    shapes as before, nothing crosses chips."""
    def local(x, axis_name, split_axis, concat_axis, tiled=False, **_):
        parts = jnp.split(x, jax.lax.axis_size(axis_name), axis=split_axis)
        return jnp.concatenate(parts, axis=concat_axis)

    monkeypatch.setattr(jax.lax, "all_to_all", local)
    line = _run(tiny_root, "c2c1024-slab4")
    assert not line["correct"]


def _bf16_mxu_einsum(einsum):
    """What the TPU's MXU does at DEFAULT precision, which the CPU does
    not: operands rounded to bfloat16, products accumulated in float32."""
    def to_bf16(a):
        a = jnp.asarray(a)
        if jnp.iscomplexobj(a):
            return jax.lax.complex(to_bf16(a.real), to_bf16(a.imag))
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    def f(pat, *ops, precision=None, **k):
        if precision in (None, jax.lax.Precision.DEFAULT):
            ops = [to_bf16(o) for o in ops]
        return einsum(pat, *ops, precision=precision, **k)
    return f


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, monkeypatch, cell):
    """Each configuration's ``control``, the program's own bf16 path, run
    through the harness: the check refuses it."""
    monkeypatch.setattr(dft_matmul.jnp, "einsum",
                        _bf16_mxu_einsum(jnp.einsum))
    c = harness.load_cell(cell, tiny_root)
    line = harness.run_cell(c, 11, 0.3, overrides=c.config["control"])
    assert not line["correct"]
    assert line["checks"]["rel_l2_last"]["value"] > 2 * c.config["tolerance"]
