"""Env-knob lint — every ``DFFT_*`` knob must land documented and keyed.

PRs 4-7 grew knobs piecemeal (tune budget, wisdom path, profile file,
correction opt-out, device timing, ...) and each one had to be chased
into the docs env tables and — when it changes what a planner call
compiles — into ``api._PLAN_ENV_KNOBS`` (the plan-cache key) by hand.
This pure test (no jax import) closes that loop mechanically:

1. every ``DFFT_*`` name referenced anywhere in the package source must
   appear in the docs env tables (OBSERVABILITY.md or TUNING.md);
2. every knob in the curated plan-affecting list below must be in
   ``api._PLAN_ENV_KNOBS`` (parsed textually from api.py — the tuple is
   a pure literal, and importing api would drag in jax).

A knob that fails 1 was added without documentation; a knob that fails
2 can serve a stale memoized plan after the env changes.
"""

import ast
import os
import re

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)
PKG = os.path.join(REPO, "distributedfft_tpu")
DOC_FILES = (
    os.path.join(REPO, "docs", "OBSERVABILITY.md"),
    os.path.join(REPO, "docs", "TUNING.md"),
    # Robustness knobs (DFFT_FAULT_*/DFFT_RETRY_*/the fallback executor)
    # live in their own doc; the lint holds them to its tables the same
    # way. Index-sensitive consumers below keep using DOC_FILES[1] for
    # TUNING.md — append only.
    os.path.join(REPO, "docs", "ROBUSTNESS.md"),
    # Multi-tenant QoS knobs (DFFT_QOS*) live in the serving-QoS doc;
    # none are plan-affecting (tenancy never changes what a plan
    # compiles to), so none are plan-cache-keyed.
    os.path.join(REPO, "docs", "SERVING_QOS.md"),
)

#: Knobs whose value changes what a planner call builds/compiles — these
#: MUST be part of the plan-cache key. Grow this list when adding such a
#: knob (the docs check below will already have flagged it).
PLAN_AFFECTING = {
    "DFFT_AUTO_EXECUTORS", "DFFT_MM_PRECISION", "DFFT_MM_COMPLEX",
    "DFFT_MM_SPLIT", "DFFT_MM_DIRECT_MAX", "DFFT_DD_DEPTH",
    "DFFT_PALLAS_PACK", "DFFT_PALLAS_SPLIT", "DFFT_PALLAS_TILE",
    "DFFT_PALLAS_TILE_STRIDED",
    "DFFT_XLA_REAL", "DFFT_FORCE_REAL_LOWERING", "DFFT_OVERLAP",
    "DFFT_TUNE", "DFFT_WISDOM", "DFFT_TUNE_ITERS", "DFFT_TUNE_MAX",
    "DFFT_HW_PROFILE", "DFFT_TUNE_CORRECTION", "DFFT_WIRE_DTYPE",
    "DFFT_FUSE",
}

_KNOB = re.compile(r"DFFT_[A-Z0-9_]*[A-Z0-9]")


def _knobs_in(text: str) -> set[str]:
    """Full DFFT_* names in ``text``. A match directly followed by an
    underscore is a prose prefix fragment ("the DFFT_MM_* knobs"), not a
    knob reference, and is skipped."""
    out = set()
    for m in _KNOB.finditer(text):
        if text[m.end():m.end() + 1] == "_":
            continue
        out.add(m.group())
    return out


def _package_knobs() -> set[str]:
    knobs: set[str] = set()
    for root, dirs, files in os.walk(PKG):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    knobs |= _knobs_in(f.read())
    return knobs


def _documented_knobs() -> set[str]:
    knobs: set[str] = set()
    for path in DOC_FILES:
        with open(path) as f:
            knobs |= _knobs_in(f.read())
    return knobs


def _plan_env_knobs_literal() -> set[str]:
    """``api._PLAN_ENV_KNOBS`` parsed from source (pure — no jax)."""
    with open(os.path.join(PKG, "api.py")) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "_PLAN_ENV_KNOBS"
                for t in node.targets):
            return set(ast.literal_eval(node.value))
    raise AssertionError("api._PLAN_ENV_KNOBS not found")


def test_every_package_knob_is_documented():
    missing = _package_knobs() - _documented_knobs()
    assert not missing, (
        f"DFFT_* knobs referenced by the package but absent from the "
        f"docs env tables (OBSERVABILITY.md / TUNING.md): "
        f"{sorted(missing)} — document them where they were added")


def test_plan_affecting_knobs_are_plan_cache_keyed():
    keyed = _plan_env_knobs_literal()
    missing = PLAN_AFFECTING - keyed
    assert not missing, (
        f"plan-affecting knobs missing from api._PLAN_ENV_KNOBS "
        f"(the plan-cache key): {sorted(missing)} — a cached plan "
        f"would go stale when one of these changes")
    # The keyed tuple must itself stay within the referenced/known set:
    # a key entry for a knob nothing reads is dead weight that silently
    # fragments the plan cache.
    unknown = keyed - _package_knobs()
    assert not unknown, (
        f"api._PLAN_ENV_KNOBS entries no code references: "
        f"{sorted(unknown)}")


# --------------------------------------------- metrics registry <-> docs
#
# PR 16's monitor exports every registered series to Prometheus; an
# undocumented series is an unnamed dashboard line, and a documented
# series nothing emits is a phantom row operators will grep for in vain.
# Same mechanical closure as the knob lint: the series table in
# OBSERVABILITY.md ("Registered series") must match the literal series
# names the package emits, in both directions.

_SERIES_EMIT = re.compile(r'\b(?:inc|set_gauge|observe)\(\s*"([a-z0-9_]+)"')
_SERIES_NAME = re.compile(r"`([a-z][a-z0-9_]+)`")
_SERIES_TYPES = {"counter", "gauge", "histogram"}


def _emitted_series() -> set[str]:
    """Literal series names at every ``inc``/``set_gauge``/``observe``
    call site in the package (the registry's emit API — call sites pass
    pure string literals by convention, enforced here by omission: a
    computed name would dodge the docs lint and the Prometheus naming
    audit with it)."""
    series: set[str] = set()
    for root, dirs, files in os.walk(PKG):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    series |= set(_SERIES_EMIT.findall(f.read()))
    return series


def _documented_series() -> set[str]:
    """Series named in OBSERVABILITY.md's metrics table: backticked
    names from the first cell of every row whose type cell is
    counter/gauge/histogram (slash-joined families like
    ``plan_cache_hits`` / ``plan_cache_misses`` contribute each name)."""
    series: set[str] = set()
    with open(DOC_FILES[0]) as f:
        for line in f:
            if not line.lstrip().startswith("|"):
                continue
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            if len(cells) < 2:
                continue
            if cells[1].split(" ")[0] not in _SERIES_TYPES:
                continue
            series |= set(_SERIES_NAME.findall(cells[0]))
    return series


def test_every_emitted_series_is_documented():
    missing = _emitted_series() - _documented_series()
    assert not missing, (
        f"metric series the package emits but OBSERVABILITY.md's "
        f"'Registered series' table does not document: {sorted(missing)}"
        f" — add a row (name, type, labels, meaning) where the series "
        f"was added")


def test_every_documented_series_is_emitted():
    phantom = _documented_series() - _emitted_series()
    assert not phantom, (
        f"OBSERVABILITY.md documents metric series nothing in the "
        f"package emits: {sorted(phantom)} — stale rows mislead anyone "
        f"building dashboards on the Prometheus export")


def test_plan_affecting_list_matches_docs_claim():
    """TUNING.md's env tables claim their knobs are plan-cache-keyed;
    hold the claim to the tuple (cache-lifecycle knobs that never change
    what a plan compiles to are the documented exceptions)."""
    exceptions = {
        "DFFT_NO_COMPILE_CACHE",  # cache lifecycle
    }
    with open(DOC_FILES[1]) as f:
        tuning = _knobs_in(f.read())
    keyed = _plan_env_knobs_literal()
    # Driver-tier knobs (bench.py's DFFT_BENCH_* family) are read by the
    # benchmark orchestrator, never by a planner call.
    tuning = {k for k in tuning if not k.startswith("DFFT_BENCH")}
    unkeyed = tuning - keyed - exceptions
    assert not unkeyed, (
        f"TUNING.md documents knobs that are neither plan-cache-keyed "
        f"nor listed cache-lifecycle exceptions: {sorted(unkeyed)}")
