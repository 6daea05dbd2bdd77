"""Which executor a plan reports and which label its builders run.

Every planner resolves its executor once: an executor named by argument
or by ``options``, then ``DFFT_EXECUTOR`` (which also replaces a named
``xla``), then the fft-thunk guard, then the plane-pass default for
unbatched complex64 c2c plans on the TPU, else ``xla``. The table pins
the pair ``(reported, run)`` for each class of plan: ``reported`` is
``plan.executor``, ``run`` the label the plan's builders were given
(the default ``pallas`` plan runs ``pallas:highest``). The TPU rows
plan with the backend predicate turned on; the kernels still interpret.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import distributedfft_tpu as dfft
from distributedfft_tpu import api, operators, plan_logic
from distributedfft_tpu.utils import metrics as m

C64, C128 = jnp.complex64, jnp.complex128
CUBE = (128, 128, 128)

# id: (on the TPU, DFFT_EXECUTOR, planner, shape, mesh, planner kwargs,
#      (reported, run))
CASES = {
    "single-tpu": (True, None, "c2c", CUBE, None, {},
                   ("pallas", "pallas:highest")),
    "single-tpu-bwd": (True, None, "c2c", CUBE, None,
                       {"direction": dfft.BACKWARD},
                       ("pallas", "pallas:highest")),
    "single-tpu-96": (True, None, "c2c", (96,) * 3, None, {},
                      ("xla", "xla")),
    "single-cpu": (False, None, "c2c", CUBE, None, {}, ("xla", "xla")),
    "batched-tpu": (True, None, "c2c", CUBE, None, {"batch": 2},
                    ("xla", "xla")),
    "complex128-tpu": (True, None, "c2c", CUBE, None, {"dtype": C128},
                       ("xla", "xla")),
    "slab-tpu": (True, None, "c2c", CUBE, 4, {},
                 ("pallas", "pallas:highest")),
    "slab-tpu-bwd": (True, None, "c2c", CUBE, 4,
                     {"direction": dfft.BACKWARD},
                     ("pallas", "pallas:highest")),
    "slab-tpu-96": (True, None, "c2c", (128, 128, 96), 4, {},
                    ("xla", "xla")),
    "slab-hierarchical-tpu": (True, None, "c2c", CUBE, "hybrid",
                              {"algorithm": "hierarchical"},
                              ("xla", "xla")),
    "pencil-tpu": (True, None, "c2c", CUBE, (2, 2), {}, ("xla", "xla")),
    "options-tpu": (True, None, "c2c", CUBE, None,
                    {"options": dfft.PlanOptions()}, ("xla", "xla")),
    "r2c-tpu": (True, None, "r2c", CUBE, None, {}, ("xla", "xla")),
    "op-tpu": (True, None, "op", CUBE, None, {}, ("xla", "xla")),
    "op-slab-tpu": (True, None, "op", CUBE, 4, {}, ("xla", "xla")),
    "real-op-tpu": (True, None, "op", CUBE, None, {"real": True},
                    ("xla", "xla")),
    "env-tpu": (True, "matmul", "c2c", CUBE, None, {},
                ("matmul", "matmul")),
    "env-replaces-named-xla": (True, "matmul", "c2c", CUBE, None,
                               {"executor": "xla"}, ("matmul", "matmul")),
    "env-loses-to-named": (True, "matmul", "c2c", CUBE, None,
                           {"executor": "xla_minor"},
                           ("xla_minor", "xla_minor")),
    "env-r2c": (True, "matmul", "r2c", CUBE, None, {},
                ("matmul", "matmul")),
    "env-op": (True, "matmul", "op", CUBE, None, {}, ("matmul", "matmul")),
    "explicit-pallas-tpu": (True, None, "c2c", CUBE, None,
                            {"executor": "pallas"}, ("pallas", "pallas")),
    "explicit-tier": (True, None, "c2c", CUBE, None,
                      {"executor": "pallas", "mm_precision": "bf16"},
                      ("pallas:bf16", "pallas:bf16")),
    "tier-alias": (False, None, "c2c", CUBE, None,
                   {"executor": "matmul:high"},
                   ("matmul:f32", "matmul:f32")),
    "explicit-fuse": (False, None, "c2c", CUBE, None,
                      {"executor": "pallas", "fuse": True},
                      ("pallas:fuse", "pallas:fuse")),
    "thunk-guard-cpu": (False, None, "c2c", (10, 9, 7), (2, 2),
                        {"direction": dfft.BACKWARD},
                        ("matmul", "matmul")),
}

# The module-level names each planner hands its executor label to.
_SEAMS = ((api, "get_executor"), (api, "build_slab_fft3d"),
          (api, "build_pencil_fft3d"), (api, "build_slab_rfft3d"),
          (api, "build_pencil_rfft3d"), (operators, "get_executor"),
          (operators, "build_slab_spectral_op"),
          (operators, "build_pencil_spectral_op"))


@pytest.fixture
def seen(monkeypatch):
    """Record every executor label a planner passes to a builder."""
    labels = []

    def spy(fn, positional):
        def wrapped(*a, **kw):
            labels.append(a[0] if positional else kw["executor"])
            return fn(*a, **kw)
        return wrapped

    for mod, name in _SEAMS:
        monkeypatch.setattr(mod, name,
                            spy(getattr(mod, name), name == "get_executor"))
    dfft.clear_plan_cache()
    m.metrics_reset()
    m.enable_metrics()
    yield labels
    m.enable_metrics(False)
    m.metrics_reset()
    dfft.clear_plan_cache()


def _mesh(spec):
    if spec is None:
        return None
    if spec == "hybrid":
        return Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("dcn", "ici"))
    return dfft.make_mesh(spec)


@pytest.mark.parametrize("case", list(CASES))
def test_planner_resolves_executor(seen, monkeypatch, case):
    tpu, env, planner, shape, mesh, kw, (name, label) = CASES[case]
    monkeypatch.setattr(plan_logic, "_on_tpu", lambda: tpu)
    if env is None:
        monkeypatch.delenv("DFFT_EXECUTOR", raising=False)
    else:
        monkeypatch.setenv("DFFT_EXECUTOR", env)
    kw = {"dtype": C64, **kw}
    if planner == "c2c":
        plan = dfft.plan_dft_c2c_3d(shape, _mesh(mesh), **kw)
    elif planner == "r2c":
        plan = dfft.plan_dft_r2c_3d(shape, _mesh(mesh), **kw)
    else:
        plan = dfft.solve_poisson(shape, _mesh(mesh), **kw)
    assert plan.executor == name
    assert plan.options.executor == name
    assert plan.logic.options.executor == name
    assert seen and set(seen) == {label}, seen
    assert m.counter_total("c2c_default_pallas") == float(
        label == "pallas:highest")
