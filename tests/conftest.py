"""Test harness configuration.

Multi-device tests run on a virtual 8-device CPU mesh — the TPU-framework
analog of heFFTe's "multiple MPI ranks on one machine" CI strategy
(``heffte/heffteBenchmark/test/CMakeLists.txt:1-7``). x64 is enabled so the
double-precision 1e-11 tolerance tier (``test/test_common.h:138``) is
meaningful; the real-TPU benchmark path runs complex64 (TPU has no C128).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The calibrated hardware profile is machine-local mutable state (and
# every measured tournament persists model-correction ratios into it);
# reading a developer's real profile — or writing into it — would make
# model-ranking tests nondeterministic across machines. Disabled here;
# the profile tests point DFFT_HW_PROFILE at their own tmp files.
os.environ.setdefault("DFFT_HW_PROFILE", "0")
# fft-thunk retirement opt-in: the environment's XLA:CPU has a known
# fft-thunk layout bug (fft_thunk.cc:69 RET_CHECK on uneven inverse
# pencil chains) whose INTERNAL error permanently poisons the process's
# sharded dispatch stream — for years the single fault cascaded into
# ~177 collateral tier-1 failures. The guard routes exactly those
# chain geometries through the matmul executor (dot_generals never
# touch the FFT thunk; plan_logic._thunk_guard documents the class),
# so the fault never fires and every downstream 8-device test sees a
# clean backend. Unset outside the suite: default planning is
# HLO-identical to the unguarded build.
os.environ.setdefault("DFFT_THUNK_GUARD", "matmul")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: the dd tier's programs are hundreds of
# matmuls and dominate suite wall time on a small box; repeat runs (the
# driver's test gate, local iteration) hit the cache and skip those
# compiles entirely. It lives where the library keeps it
# (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
from distributedfft_tpu.utils.cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (big-compile duplicates and "
             "deep parameterizations)")


@pytest.fixture
def chaos():
    """Deterministic fault injection scoped to one test: yields ``arm``,
    a callable that sets ``DFFT_FAULT_INJECT`` to a spec (see
    docs/ROBUSTNESS.md for the grammar) with fresh counters/seeds.
    Teardown restores the prior env value and resets every armed fault —
    even when the test fails — so chaos can never leak into the next
    test (the tier-1 suite depends on the default path staying clean)."""
    from distributedfft_tpu import faults

    old = os.environ.get("DFFT_FAULT_INJECT")

    def arm(spec: str) -> None:
        os.environ["DFFT_FAULT_INJECT"] = spec
        faults.reset()  # fresh counters: each arm starts sequence #1

    try:
        yield arm
    finally:
        if old is None:
            os.environ.pop("DFFT_FAULT_INJECT", None)
        else:
            os.environ["DFFT_FAULT_INJECT"] = old
        faults.reset()


def pytest_collection_modifyitems(config, items):
    # An explicit file/node selection on the command line orders items
    # by the invocation, deliberately — the convention governs the
    # alphabetical DIRECTORY collection the tier-1 suite runs with.
    if not any(a.endswith(".py") or "::" in a for a in config.args):
        _check_poison_collection_order(items)
    if config.getoption("--runslow"):
        return
    # config.args holds only the positional selectors (never option
    # values like --deselect's), so a "::" here is a real node ID.
    if any("::" in a for a in config.args):
        return  # an explicitly-named node ID always runs
    skip = pytest.mark.skip(reason="slow tier: pass --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


#: Filename convention of the clean-backend test tier: files whose
#: 8-device executions require an unpoisoned dispatch stream are named
#: ``test_a2<letter>_*.py`` so alphabetical collection places them before
#: ``test_alltoallv.py`` (the first file whose chains may trip the
#: XLA:CPU fft-thunk fault when the guard above is off). One conftest
#: check derives the rule from the convention — PRs add a file matching
#: the pattern and are covered automatically, instead of hand-extending
#: a name list every time (the pre-PR-12 maintenance rule in
#: test_explain.py).
CLEAN_BACKEND_PATTERN = "test_a2"
POISON_FILE = "test_alltoallv.py"


def clean_backend_files() -> list[str]:
    """Every committed clean-backend-tier test file (the convention the
    collection-order check below and test_explain's guard both derive
    from)."""
    tests = os.path.dirname(os.path.abspath(__file__))
    return sorted(n for n in os.listdir(tests)
                  if n.startswith(CLEAN_BACKEND_PATTERN)
                  and n.endswith(".py"))


def _check_poison_collection_order(items) -> None:
    """Fail the run loudly at collection when any clean-backend-tier
    file would collect after the poison file — a renamed file silently
    breaking the convention used to resurface as hundreds of mysterious
    downstream failures."""
    first_poison = None
    for idx, item in enumerate(items):
        name = os.path.basename(str(getattr(item, "fspath", "")))
        if name == POISON_FILE and first_poison is None:
            first_poison = idx
        elif (name.startswith(CLEAN_BACKEND_PATTERN)
              and first_poison is not None):
            raise pytest.UsageError(
                f"{name} collected after {POISON_FILE}: the clean-"
                f"backend tier (files named {CLEAN_BACKEND_PATTERN}*) "
                f"must collect first — rename the file to keep the "
                f"alphabetical convention")
