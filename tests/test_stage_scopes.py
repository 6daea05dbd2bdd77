"""Stage scopes: the chain's t0..t3 names reach the compiled program.

``utils.trace.stage_scope`` is a ``jax.named_scope``, so every HLO op
traced under a stage carries the stage in its ``op_name``; the profiler
reports that as the device op's ``tf_op``, and ``utils.xplane`` reads it
back from the ``.xplane.pb`` and maps it to its stage. These tests pin
the names in the compiled HLO of small slab plans on virtual CPU devices,
the scope's host-side behavior, the stage rule, and the reader on a
capture recorded on a TPU v5e.
"""

import json
import re
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

import distributedfft_tpu as dfft
from distributedfft_tpu.explain import parse_device_trace
from distributedfft_tpu.utils import trace as tr
from distributedfft_tpu.utils import xplane

needs_4 = pytest.mark.skipif(len(jax.devices()) < 4,
                             reason="needs 4 (virtual) devices")

#: A capture of ``perf/run.py --workload c2c512-fwd --seconds 1 --trace 1``
#: on a TPU v5e, recorded before the chain named its stages.
RECORDED = (Path(__file__).resolve().parent.parent / "perf" / "tests"
            / "data" / "c2c512-fwd.xplane.pb")
#: Four whole transforms of a ``perf/run.py --workload c2c1024-slab4
#: --trace 1`` capture on four TPU v5e chips, with stage scopes: the
#: device ops of each chip as ``utils.xplane.device_ops`` read them.
SLAB4 = Path(__file__).resolve().parent / "data" / "c2c1024-slab4-scoped.ops.json"


def _op_names(fn, *args) -> dict[str, list[str]]:
    """``{opcode: [op_name, ...]}`` of the compiled HLO of ``fn``."""
    out: dict[str, list[str]] = {}
    for line in fn.lower(*args).compile().as_text().splitlines():
        m = re.search(r'= .*? ([a-z][a-z0-9-]*)\(.*op_name="([^"]*)"', line)
        if m:
            out.setdefault(m.group(1), []).append(m.group(2))
    return out


def _plan_ops(shape, **kw):
    plan = dfft.plan_dft_c2c_3d(shape, dfft.make_mesh(4),
                                dtype=jnp.complex64, **kw)
    return _op_names(plan.fn, jnp.zeros(shape, jnp.complex64))


@needs_4
def test_slab_plan_ops_carry_stage_scopes():
    ops = _plan_ops((16, 16, 16))
    ffts = ops["fft"]
    assert len(ffts) == 2
    assert any("/t0_fft_yz/" in n for n in ffts)
    assert any("/t3_fft_x/" in n for n in ffts)
    assert ops["all-to-all"]
    assert all("/t2_exchange_slab/" in n for n in ops["all-to-all"])
    assert all(xplane.op_stage(n) for n in ffts + ops["all-to-all"])


@needs_4
def test_uneven_slab_pack_carries_t1_scope():
    """18 does not split 4 ways: the t1 pack pads it to 20, under its
    own scope (an even grid's pack is a no-op and leaves no op)."""
    ops = _plan_ops((16, 18, 16))
    pads = [n for n in ops.get("pad", []) if "/t1_pack/" in n]
    assert pads and {xplane.op_stage(n) for n in pads} == {"t1"}


@needs_4
def test_overlapped_plan_chunks_carry_scopes():
    ops = _plan_ops((16, 16, 16), overlap_chunks=2)
    for k in range(2):
        assert any(f"/t2_exchange_slab[{k}]/" in n
                   for n in ops["all-to-all"]), k
        assert any(f"/t3_fft_x[{k}]/" in n for n in ops["fft"]), k
    assert {xplane.op_stage(n) for n in ops["all-to-all"]} == {"t2"}


@needs_4
def test_slab_operator_midpoint_maps_to_t_mid():
    """A fused slab operator's midpoint (final forward X FFT, multiply,
    first inverse X FFT) is attributed to ``t_mid``, apart from the
    t0/t3 FFTs around it."""
    shape = (16, 16, 16)
    plan = dfft.solve_poisson(shape, dfft.make_mesh(4), dtype=jnp.complex64)
    ops = _op_names(plan.fn, jnp.zeros(shape, jnp.complex64))
    stages = [xplane.op_stage(n) for n in ops["fft"]]
    # t0 YZ | t_mid forward X, inverse X | t3 inverse Y, inverse Z
    assert sorted(stages) == ["t0", "t3", "t3", "t_mid", "t_mid"]
    mults = [n for n in ops.get("multiply", []) if "t_mid_pointwise" in n]
    assert mults and {xplane.op_stage(n) for n in mults} == {"t_mid"}


def _slab_stages(shape):
    from distributedfft_tpu.parallel.slab import build_slab_stages

    return build_slab_stages(dfft.make_mesh(4), shape)[0]


def _single_stages(shape):
    from distributedfft_tpu.parallel.staged import build_single_stages

    return build_single_stages(shape)


def _dd_single_stages(shape):
    from distributedfft_tpu.parallel.ddslab import build_dd_single_stages

    return build_dd_single_stages(shape)


@needs_4
@pytest.mark.parametrize("build", [_slab_stages, _single_stages,
                                   _dd_single_stages])
def test_staged_pipeline_stage_jits_carry_their_name(build):
    """The staged pipelines (``dfft.explain``'s per-stage view) name each
    stage jit's ops after the stage, so the device timeline attributes
    them."""
    shape = (16, 16, 16)
    stages = build(shape)
    cur = jnp.zeros(shape, jnp.complex64)
    if build is _dd_single_stages:
        cur = (cur, jnp.zeros_like(cur))
    for name, fn in stages:
        names = [n for ns in _op_names(fn.__wrapped__, cur).values()
                 for n in ns]
        assert any(f"/{name}/" in n for n in names), name
        cur = fn(cur)


def test_stage_scope_records_no_session_event(tmp_path, monkeypatch):
    monkeypatch.setenv("DFFT_TRACE_NATIVE", "0")
    tr.init_tracing(str(tmp_path / "scope"))
    try:
        with tr.stage_scope("t0_fft_yz"):
            with tr.add_trace("host_work"):
                pass
        names = [e[0] for e in tr._events]
    finally:
        tr.finalize_tracing()
    assert names == ["host_work"]


def test_stage_scope_fills_capture_buffer():
    with tr.capture_events() as buf:
        jax.eval_shape(
            lambda v: _scoped_double(v), jax.ShapeDtypeStruct((4,), "f4"))
    assert [n for n, _, _ in buf] == ["t3_fft_x[1]", "t2_exchange_slab"]
    assert all(stop >= start for _, start, stop in buf)


def _scoped_double(v):
    with tr.stage_scope("t2_exchange_slab"):
        with tr.stage_scope("t3_fft_x[1]"):
            return v * 2


def test_stage_scope_names_the_ops():
    text = jax.jit(_scoped_double).lower(jnp.ones(4)).as_text(
        debug_info=True)
    assert "t2_exchange_slab/t3_fft_x[1]/mul" in text


@pytest.mark.parametrize("tf_op, stage", [
    ("jit(fn)/shard_map/t0_fft_yz/jit(fft)/fft:", "t0"),
    ("jit(fn)/shard_map/t1_pack/pad:", "t1"),
    ("jit(fn)/shard_map/t2_exchange_slab[1]/all_to_all:", "t2"),
    ("jit(fn)/shard_map/t2_exchange_x/t2a_exchange_ici[0]/all_to_all:",
     "t2"),
    ("jit(fn)/shard_map/cc1:t3_fft_x[0]/transpose:", "t3"),
    # the outermost stage segment wins: one op is never split
    ("jit(t2_all_to_all)/t2_all_to_all/shard_map/t3_fft_x/fft:", "t2"),
    ("jit(<lambda>)/jit(fft):", None),
    ("x:", None),
    ("", None),
    # an operator's multiply counts for the t_mid around it
    ("jit(fn)/t_mid[0]/t_mid_pointwise/mul:", "t_mid"),
    ("jit(_single_real)/t_mid/t_mid_pointwise/mul:", "t_mid"),
    ("jit(fn)/t_mid_pointwise/mul:", None),
    # a real operator's real-axis edges and complex axes
    ("jit(_single_real)/t0_r2c_z/jit(fft)/fft:", "t0"),
    ("jit(_single_real)/t0_c2r_z/reshape:", "t0"),
    ("jit(_single_real)/t3_fft_xy/jit(fft)/fft:", "t3"),
    ("jit(_single_real)/t3_ifft_xy/jit(fft)/fft:", "t3"),
])
def test_op_stage_rule(tf_op, stage):
    assert xplane.op_stage(tf_op) == stage


def test_xplane_reader_on_recorded_chip_capture():
    ops = xplane.device_ops(RECORDED)
    assert list(ops) == ["/device:TPU:0"]
    tf_op = {inst: t for inst, t, _, _ in ops["/device:TPU:0"]}
    assert tf_op["fusion.160"] == "jit(<lambda>)/jit(fft):"
    assert tf_op["custom-call.1"] == "x:"
    assert sum(1 for inst, *_ in ops["/device:TPU:0"]
               if inst == "custom-call.2") == 15
    assert all(end >= start for *_, start, end in ops["/device:TPU:0"])
    # Recorded before the chain named its stages: nothing to attribute.
    assert parse_device_trace(ops) is None


#: Modules whose chain code ``jax.jit`` traces: they name stages with
#: ``stage_scope`` and never open a host span (``add_trace``), which
#: would time Python tracing once per compile.
TRACED_MODULES = ("stagegraph.py", "operators.py", "parallel/exchange.py",
                  "parallel/ddslab.py", "parallel/slab.py",
                  "parallel/pencil.py", "parallel/staged.py")


@pytest.mark.parametrize("rel", TRACED_MODULES)
def test_traced_chain_code_opens_no_host_span(rel):
    import ast

    src = (Path(dfft.__file__).parent / rel).read_text()
    used = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            used.add(node.attr)
        elif isinstance(node, ast.ImportFrom):
            used |= {a.name for a in node.names}
    assert "add_trace" not in used


def test_stage_split_of_recorded_four_chip_capture():
    ops = {d: [tuple(e) for e in evs] for d, evs in
           json.loads(SLAB4.read_text()).items()}
    assert len(ops) == 4
    parsed = parse_device_trace(ops, iters=4)
    assert parsed["device"] == "/device:TPU:1"
    ms = {k: [1e3 * v for v in vs] for k, vs in parsed["samples"].items()}
    # per transform: the YZ 2D FFT, the exchange, the X 1D FFT; the pack
    # pads nothing at 1024^3 on four chips, so t1 has no op
    assert set(ms) == {"t0", "t2", "t3"}
    assert ms["t0"] == pytest.approx([94.039615156, 94.03822539,
                                      94.030016172, 94.042702578])
    assert ms["t2"] == pytest.approx([31.28312125, 31.227767656,
                                      31.248673594, 31.250955])
    assert ms["t3"] == pytest.approx([42.5779725, 42.619762188,
                                      42.571876172, 42.584764328])
    evs = ops["/device:TPU:1"]
    busy = sum(e - s for *_, s, e in evs) / 1e9 / 4
    a2a = sum(e - s for n, _, s, e in evs if n.startswith("all_to_all"))
    assert a2a / 1e9 / 4 == pytest.approx(24.683342129)
    assert min(ms["t2"]) > a2a / 1e9 / 4
    # ops under no stage scope: the input's X64 split keeps the
    # parameter's op_name "x", the complex64 assembly the op_name
    # "custom-call.2", and the async copies carry none
    unscoped = {n for n, t, _, _ in evs if xplane.op_stage(t) is None}
    assert unscoped == {"x.0", "x.2", "custom-call.4"} | {
        f"copy-{k}{s}" for k in ("start", "done")
        for s in ("", ".1", ".2", ".3", ".4", ".5")}
    rest = busy - sum(sum(v) / 4 for v in ms.values())
    assert rest == pytest.approx(24.771376386)
