"""Pluggable local FFT executors.

The reference keeps several interchangeable compute backends side by side —
``setFFTPlans`` builds hipfft, rocfft, *and* templateFFT plans and picks one
(``3dmpifft_opt/include/fft_mpi_3d_api.cpp:318-429``); heFFTe abstracts the
same idea as the ``one_dim_backend`` trait over {stock,fftw,mkl,cufft,rocfft,
onemkl} (``heffte/heffteBenchmark/include/heffte_common.h:97-127,275``).

The TPU-native equivalent is a registry of *jit-traceable callables*: each
executor maps ``(x, axes, forward) -> y`` with pure functional semantics, so
any of them can be dropped into the distributed pipeline under ``jit`` /
``shard_map``. Backends:

- ``"xla"``    — ``jnp.fft``; XLA's built-in FFT lowering (default).
- ``"matmul"`` — mixed-radix DFT-by-matrix-multiply on the MXU
  (:mod:`distributedfft_tpu.ops.dft_matmul`), the TPU-idiomatic analog of
  templateFFT's runtime-generated Stockham kernels.
- ``"pallas"`` — the Pallas kernels
  (:mod:`distributedfft_tpu.ops.pallas_fft`): a complex64 operand is split
  into float32 planes once, each axis is one in-place plane pass staged
  through VMEM (one HBM read/write per axis), and the planes are combined
  once (``pallas_fft.fft_planes``). Axes the plane passes cannot take,
  and other dtypes, run the balanced-split kernels, or the ``"matmul"``
  fallback where those cannot.

Which executor a plan runs is decided once, at plan time, by
:func:`..plan_logic.resolve_executor`; the label grammar below (tiers,
the fuse flag) is normalized by :func:`normalize_label`.
"""

from __future__ import annotations

import enum
import functools
import math
from typing import Callable, Sequence

import jax.numpy as jnp

Array = jnp.ndarray
ExecutorFn = Callable[..., Array]  # (x, axes, forward=True) -> y

_REGISTRY: dict[str, ExecutorFn] = {}

# --- precision-tiered executor labels -----------------------------------
#
# "matmul:bf16" / "matmul:f32" / "matmul:highest" (and the ":gauss"
# complex-product mode) are DISTINCT executor names: the suffix scopes the
# MXU contraction precision over the base executor's trace
# (:func:`.dft_matmul.mm_scope`), so the accuracy tier is per-plan state
# — plan-cache keyed, wisdom-recorded, two tiers coexisting in one
# process — instead of the process-global trace-time DFFT_MM_PRECISION /
# DFFT_MM_COMPLEX env read (which stays as the *default* for bare names).

#: Accuracy tiers of the matmul-family executors, in descending-error
#: order: ``bf16`` = one bf16 MXU pass (lax DEFAULT), ``f32`` = the
#: 3-pass bf16 refinement (HIGH), ``highest`` = f32-exact multi-pass
#: (HIGHEST — the bare executor's default tier).
MM_TIERS = ("bf16", "f32", "highest")

#: Tier label -> lax precision name (the :func:`.dft_matmul.mm_precision`
#: table key the scope pins).
TIER_PRECISION = {"bf16": "default", "f32": "high", "highest": "highest"}

#: Accepted lax-name spellings of the tiers (the grammar bench.py's
#: executor menus used before the tiers were plan-scoped:
#: ``matmul:high`` == ``matmul:f32``). Normalized to the canonical MXU
#: names by :func:`split_executor`.
TIER_ALIASES = {"default": "bf16", "high": "f32"}

#: Base executors whose contractions consult the DFFT_MM_* knobs — the
#: only bases a tier suffix is meaningful for (speed3d's
#: ``_executor_label`` gates on the same family).
MM_EXECUTOR_BASES = ("matmul", "pallas")

#: Complex-product modes accepted as a suffix (``native`` is the bare
#: default; only ``gauss`` changes the trace).
MM_COMPLEX_MODES = ("native", "gauss")


#: The stage-fusion flag token: ``pallas:fuse`` asks the stage-graph
#: compiler's fusion pass (``stagegraph.plan_fusion``) to fuse the wire
#: codec's encode/decode into the adjacent stage computes — Pallas
#: mega-kernels where the shapes are eligible, the pure-JAX codec mirror
#: otherwise. Orthogonal to the precision tiers: the flag never changes
#: the local executor callable (``get_executor("pallas:fuse")`` is the
#: plain pallas executor), it is plan-level state the compiler consumes.
FUSE_SUFFIX = "fuse"

#: Bases the fuse flag composes with (the fused kernels are Pallas
#: specializations; other bases have no fused engine to dispatch to).
FUSE_BASES = ("pallas",)


def split_fuse(name: str) -> tuple[str, bool]:
    """Strip the ``:fuse`` flag off an executor label: ``"pallas:fuse"
    -> ("pallas", True)``, ``"pallas:bf16:fuse" -> ("pallas:bf16",
    True)``; unfused labels return ``(name, False)``. Validates the flag
    only rides a :data:`FUSE_BASES` base and appears at most once. Pure
    label algebra — the fusion pass and the planner normalization share
    this one parse."""
    if ":" not in name:
        return name, False
    base, *mods = name.split(":")
    hits = mods.count(FUSE_SUFFIX)
    if hits == 0:
        return name, False
    if hits > 1:
        raise ValueError(f"executor {name!r} repeats the fuse flag")
    if base not in FUSE_BASES:
        raise ValueError(
            f"the :fuse flag applies to {FUSE_BASES} executors, "
            f"got {name!r}")
    rest = [m for m in mods if m != FUSE_SUFFIX]
    return ":".join([base] + rest), True


def fused_name(name: str, fuse: bool | None = None) -> str:
    """Compose/normalize the fuse flag onto a label (the fuse analog of
    :func:`tiered_name`). ``fuse=None`` keeps the label's own flag;
    ``True`` adds it (idempotent; validates the base); ``False`` with a
    label that already pins ``:fuse`` raises — a plan asking for
    ``executor="pallas:fuse", fuse=False`` is a bug, not a precedence
    question. The canonical composed form carries ``:fuse`` last:
    ``pallas:bf16:fuse``."""
    bare, have = split_fuse(name)
    if fuse is None:
        fuse = have
    elif have and not fuse:
        raise ValueError(
            f"executor {name!r} already pins the fuse flag; "
            f"conflicting request fuse=False")
    if not fuse:
        return bare
    if bare.split(":", 1)[0] not in FUSE_BASES:
        raise ValueError(
            f"the fuse tier applies to {FUSE_BASES} executors, "
            f"got {name!r}")
    return bare + f":{FUSE_SUFFIX}"


def split_executor(name: str) -> tuple[str, str | None, str | None]:
    """Parse a (possibly tiered) executor label into
    ``(base, precision_tier, complex_mode)`` — e.g. ``"matmul:bf16:gauss"
    -> ("matmul", "bf16", "gauss")``; bare names return ``(name, None,
    None)``. Lax-name tier spellings normalize to the canonical MXU
    names (``matmul:high -> ("matmul", "f32", None)`` — the bench menu
    grammar). Validates suffix vocabulary and that the base consults the
    precision knobs at all; does NOT require the base to be registered
    (pure label algebra, shared with the tuner's candidate space)."""
    name, _ = split_fuse(name)  # the fuse flag is orthogonal label state
    if ":" not in name:
        return name, None, None
    base, *mods = name.split(":")
    tier: str | None = None
    cmode: str | None = None
    for m in mods:
        if m in MM_TIERS or m in TIER_ALIASES:
            if tier is not None:
                raise ValueError(
                    f"executor {name!r} names two precision tiers")
            tier = TIER_ALIASES.get(m, m)
        elif m in MM_COMPLEX_MODES:
            if cmode is not None:
                raise ValueError(
                    f"executor {name!r} repeats the complex mode")
            cmode = m
        else:
            raise ValueError(
                f"unknown executor suffix {m!r} in {name!r}; tiers: "
                f"{MM_TIERS} (or lax spellings {sorted(TIER_ALIASES)}), "
                f"complex modes: {MM_COMPLEX_MODES}")
    if not base.startswith(MM_EXECUTOR_BASES):
        raise ValueError(
            f"executor {base!r} does not consult the matmul precision "
            f"knobs; tier suffixes apply to {MM_EXECUTOR_BASES}")
    return base, tier, cmode


def tiered_name(base: str, precision: str | None = None,
                complex_mode: str | None = None) -> str:
    """Compose the canonical tiered executor label from a base name and
    plan-level tier choices (``PlanOptions.mm_precision`` /
    ``mm_complex``). Idempotent: a base that already carries a suffix
    merges with the requested one — and conflicts raise (a plan asking
    for ``executor="matmul:bf16", mm_precision="highest"`` is a bug, not
    a precedence question). ``None`` tiers leave the bare name (the env
    defaults keep governing that plan's trace)."""
    base, have_fuse = split_fuse(base)
    b, have_tier, have_cmode = (split_executor(base) if ":" in base
                                else (base, None, None))
    if precision is not None:
        precision = TIER_ALIASES.get(precision, precision)
    for what, have, want in (("precision tier", have_tier, precision),
                             ("complex mode", have_cmode, complex_mode)):
        if have is not None and want is not None and have != want:
            raise ValueError(
                f"executor {base!r} already pins {what} {have!r}; "
                f"conflicting request {want!r}")
    tier = precision if precision is not None else have_tier
    cmode = complex_mode if complex_mode is not None else have_cmode
    if tier is not None and tier not in MM_TIERS:
        raise ValueError(
            f"mm_precision must be one of {MM_TIERS} or None, got {tier!r}")
    if cmode is not None and cmode not in MM_COMPLEX_MODES:
        raise ValueError(
            f"mm_complex must be one of {MM_COMPLEX_MODES} or None, "
            f"got {cmode!r}")
    if cmode == "native":
        cmode = None  # the bare default — not a distinct label
    if tier is None and cmode is None:
        return fused_name(b, have_fuse) if have_fuse else b
    name = b + (f":{tier}" if tier else "") + (f":{cmode}" if cmode else "")
    split_executor(name)  # one validation path for every composed label
    return fused_name(name, have_fuse) if have_fuse else name


def normalize_label(opts):
    """Compose a plan's accuracy tier and fusion flag into its canonical
    executor label: ``mm_precision``/``mm_complex`` and ``fuse`` join the
    executor name (``matmul`` + ``bf16`` -> ``matmul:bf16``, ``pallas``
    + ``fuse=True`` -> ``pallas:fuse``), and a label that already carries
    them fills the option fields back in. After this, ``opts.executor``
    and the fields are two views of one choice: the label is what the
    plan cache, the wisdom store and benchmark stamps key, the fields
    what drivers read. Options with neither come back unchanged.

    A tier on a family that never reads it raises unless the plan is
    tuned (the tier then pins the tuner's precision axis, and the base
    executor is only the search's starting point). ``fuse=True`` on a
    family without a fusion tier raises; the ``DFFT_FUSE`` env default
    is a preference and is ignored there, so a global ``DFFT_FUSE=1``
    does not break ``xla`` plans. Whether fusion activates is then the
    stage-graph gate (``stagegraph.plan_fusion``)."""
    from dataclasses import replace

    from ..plan_logic import resolve_fuse, resolve_tune_mode

    ex = opts.executor
    if opts.mm_precision is None and opts.mm_complex is None:
        if ":" in ex:
            base, tier, cmode = split_executor(ex)  # validates the label
            # Canonical spelling ("matmul:high" -> "matmul:f32", the
            # ":fuse" flag last): one label per tier across cache keys,
            # wisdom, and stamps.
            opts = replace(
                opts, mm_precision=tier, mm_complex=cmode,
                executor=fused_name(tiered_name(base, tier, cmode),
                                    split_fuse(ex)[1] or None))
    elif ex.split(":", 1)[0].startswith(MM_EXECUTOR_BASES):
        name = tiered_name(ex, opts.mm_precision, opts.mm_complex)
        _, tier, cmode = (split_executor(name) if ":" in name
                          else (name, None, None))
        opts = replace(opts, executor=name, mm_precision=tier,
                       mm_complex=cmode)
    elif resolve_tune_mode(opts.tune) == "off":
        raise ValueError(
            f"mm_precision/mm_complex scope the matmul-family executors "
            f"{MM_EXECUTOR_BASES}; executor={ex!r} never consults them "
            f"(use tune='measure'/'wisdom' to search the tiered "
            f"candidate axis instead)")

    ex = opts.executor
    pinned = split_fuse(ex)[1] if ":" in ex else False
    if opts.fuse is False and pinned:
        raise ValueError(
            f"executor {ex!r} already pins the fuse flag; fuse=False "
            f"conflicts (drop one of the two spellings)")
    if resolve_fuse(opts.fuse) and not pinned:
        if ex.split(":", 1)[0] in FUSE_BASES:
            ex = fused_name(ex, True)
            pinned = True
        elif opts.fuse is not None:
            raise ValueError(
                f"fuse=True scopes the Pallas-family executors "
                f"{FUSE_BASES}; executor={ex!r} has no fusion tier "
                f"(the DFFT_FUSE env default is ignored there)")
    if ex == opts.executor and bool(opts.fuse) == pinned:
        return opts
    return replace(opts, executor=ex, fuse=pinned)


#: Executor bases that lower through XLA's FFT ops — the family the
#: fft-thunk guard below may substitute away from.
THUNK_BASES = ("xla", "xla_minor")


def thunk_guard_substitute(executor, *, decomposition: str, forward: bool,
                           uneven: bool, starved: bool = False):
    """The XLA:CPU fft-thunk retirement predicate, shared by the planners
    (``plan_logic.resolve_executor``) and the staged pipeline builders:
    with ``DFFT_THUNK_GUARD`` armed (an executor name, normally
    ``matmul``), an XLA-family executor on the CPU backend building one
    of the known-poisoned chain classes —

    - an *inverse pencil chain with uneven (ceil-padded) shards*, whose
      irfft/ifft feeds the fft thunk a non-major layout, or
    - a *starved minor-axis slab chain* (input slabs on the minor axis
      with its extent smaller than the part count — zero-extent shards;
      the caller passes this condition as ``starved``), whose t0 FFT
      over the non-minor axes gets the same non-major layout

    — both tripping the ``fft_thunk.cc:69`` RET_CHECK, an INTERNAL error
    that permanently poisons the process's sharded dispatch stream — is
    replaced by the substitute, which never touches the FFT thunk (every
    matmul stage is a dot_general). Anything outside those classes, any
    non-string executor (the dd tier's callables), and every call with
    the knob unset (the default) returns ``executor`` untouched."""
    import os

    guard = os.environ.get("DFFT_THUNK_GUARD", "").strip()
    if not guard or guard in ("0", "none"):
        return executor
    if not isinstance(executor, str):
        return executor
    if executor.split(":", 1)[0] not in THUNK_BASES:
        return executor
    poisoned = ((decomposition == "pencil" and not forward and uneven)
                or (decomposition == "slab" and starved))
    if not poisoned:
        return executor
    import jax

    if jax.default_backend() != "cpu":
        return executor
    return guard


#: Tiers below the exact default — the ones that cost accuracy and must
#: be admitted against a plan's ``max_roundtrip_err`` budget
#: (``highest`` IS the bare default's tier: exact by the suite's
#: convention, like the exact wire).
REDUCED_TIERS = ("bf16", "f32")

_EXEC_ERR_CACHE: dict = {}


def executor_roundtrip_error(name: str, dtype, n: int = 256, *,
                             sample=None) -> float:
    """Measured relative round-trip error of one forward+inverse DFT
    pass of a *reduced-precision* tiered executor at ``dtype`` (``max
    |ifft(fft(x)) - x| / max |x|`` over a seeded standard-normal block)
    — the precision analog of
    :func:`..parallel.exchange.wire_roundtrip_error`, and the number the
    tuner's error-budget filter admits a ``matmul:bf16`` candidate
    against. Deterministic (fixed seed) and cached per (label, dtype,
    n), so per-candidate pruning never re-measures. 0.0 for bare labels
    and exact tiers (``highest``/``gauss``) — the accuracy baseline the
    budget is declared relative to. Measured on the RUNNING backend: on
    CPU every lax precision collapses to the native f64/f32 kernels (the
    tiers genuinely cost nothing there); on TPU the bf16 tier's MXU
    pass shows its real ~1e-2/1e-3 cost.

    ``sample`` (an ``(8, n)``-reshapeable block) measures on
    caller-supplied data instead of the seeded Gaussian, cached by
    content digest — the wire-side kwarg's precision analog."""
    if ":" not in name:
        return 0.0
    _, tier, _ = split_executor(name)
    if tier not in REDUCED_TIERS:
        return 0.0
    import hashlib

    import numpy as _np

    if sample is not None:
        x = _np.asarray(sample, dtype=_np.dtype(dtype)).reshape(8, -1)
        digest = hashlib.sha256(x.tobytes()).hexdigest()[:16]
        key = (name, str(_np.dtype(dtype)), x.shape[1], digest)
    else:
        x = None
        key = (name, str(_np.dtype(dtype)), int(n))
    hit = _EXEC_ERR_CACHE.get(key)
    if hit is not None:
        return hit
    if x is None:
        rng = _np.random.default_rng(0)
        x = (rng.standard_normal((8, n))
             + 1j * rng.standard_normal((8, n))).astype(_np.dtype(dtype))
    fn = get_executor(name)
    y = _np.asarray(fn(fn(jnp.asarray(x), (1,), True), (1,), False))
    err = float(_np.max(_np.abs(y - x)) / _np.max(_np.abs(x)))
    _EXEC_ERR_CACHE[key] = err
    return err


def _scoped(fn: Callable, tier: str | None, cmode: str | None) -> Callable:
    """Wrap an executor-family callable so its trace runs under the
    tier's :func:`.dft_matmul.mm_scope` — the point where a tiered label
    becomes baked-in jaxpr precision instead of an env read."""
    from . import dft_matmul

    prec = TIER_PRECISION[tier] if tier is not None else None

    @functools.wraps(fn)
    def scoped(*args, **kw):
        with dft_matmul.mm_scope(precision=prec, complex_mode=cmode):
            return fn(*args, **kw)

    return scoped


class Scale(enum.Enum):
    """Result scaling, mirroring heFFTe's ``scale`` enum none/full/symmetric
    (``heffte_fft3d.h:84-91``) and the roc backend's explicit 1/N
    normalization kernel (``3dmpifft_roc/include/kernel_func.cpp``
    ``scale_element``)."""

    NONE = "none"
    FULL = "full"
    SYMMETRIC = "symmetric"


def scale_factor(scale: Scale, world_size: int) -> float:
    if scale == Scale.NONE:
        return 1.0
    if scale == Scale.FULL:
        return 1.0 / world_size
    return 1.0 / math.sqrt(world_size)


def apply_scale(x: Array, scale: Scale, world_size: int) -> Array:
    s = scale_factor(scale, world_size)
    return x if s == 1.0 else x * jnp.asarray(s, dtype=x.real.dtype)


def register_executor(name: str, fn: ExecutorFn) -> None:
    _REGISTRY[name] = fn


def get_executor(name: str) -> ExecutorFn:
    if ":" in name:
        base, tier, cmode = split_executor(name)
        return _scoped(get_executor(base), tier, cmode)
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown executor {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def available_executors() -> list[str]:
    return sorted(_REGISTRY)


def _xla_executor(x: Array, axes: Sequence[int], forward: bool = True) -> Array:
    """XLA built-in FFT over ``axes`` (complex-to-complex, unnormalized
    forward / 1/N inverse, matching numpy conventions)."""
    axes = tuple(axes)
    if forward:
        return jnp.fft.fftn(x, axes=axes)
    return jnp.fft.ifftn(x, axes=axes)


register_executor("xla", _xla_executor)


def _matmul_executor(x: Array, axes: Sequence[int], forward: bool = True) -> Array:
    from . import dft_matmul

    for ax in tuple(axes):
        x = dft_matmul.fft_along_axis(x, ax, forward=forward)
    return x


register_executor("matmul", _matmul_executor)


def _xla_minor_executor(x: Array, axes: Sequence[int],
                        forward: bool = True) -> Array:
    """XLA FFT with the transformed axis explicitly rotated to the minor
    (lane) dimension first — a layout experiment for the executor
    tournament: TPU vector lanes run over the minor-most dim, and a
    leading-axis FFT otherwise leaves the layout choice to XLA's internal
    fft expansion. Mathematically identical to ``xla``; only the
    transpose placement differs (XLA fuses adjacent transposes, so the
    cost model is decided by the compiler, measured by the tournament —
    the role of the reference's side-by-side backend plans,
    ``fft_mpi_3d_api.cpp:318-429``)."""
    fft = jnp.fft.fft if forward else jnp.fft.ifft
    for ax in tuple(axes):
        if ax == x.ndim - 1 or ax == -1:
            x = fft(x, axis=-1)
        else:
            x = jnp.moveaxis(fft(jnp.moveaxis(x, ax, -1), axis=-1), -1, ax)
    return x


register_executor("xla_minor", _xla_minor_executor)


# --- real <-> complex transforms (the heFFTe r2c/c2r executor surface,
# ``heffte_backend_rocm.h:567`` ``rocfft_executor_r2c``; geometry shrink
# ``box3d::r2c``, ``heffte_geometry.h:94``). Each executor may register its
# own pair; unregistered executors fall back to the XLA implementation.

_R2C_REGISTRY: dict[str, Callable] = {}
_C2R_REGISTRY: dict[str, Callable] = {}


def register_real_executor(name: str, r2c: Callable, c2r: Callable) -> None:
    _R2C_REGISTRY[name] = r2c
    _C2R_REGISTRY[name] = c2r


def slice_r2c(x: Array, axis: int) -> Array:
    """r2c via full complex FFT + slice — no native RFFT HLO. Twice the
    flops of a native rfft but immune to backend RFFT bugs."""
    import jax.lax as lax

    n = x.shape[axis]
    y = jnp.fft.fft(x.astype(_ctype_for(x.dtype)), axis=axis)
    return lax.slice_in_dim(y, 0, n // 2 + 1, axis=axis)


def rows_c2r(y: Array, n: int, axis: int) -> Array:
    """c2r via the native IRFFT on a 2D ``[rows, n//2+1]`` batch. The TPU
    backend computes IRFFT wrongly (relative L2 error ~0.34) on a rank-3
    operand at 512^3, and inside one program with the preceding inverse
    FFT at 256^3, but correctly on a 2D row batch (chip runs of PR 21;
    the mirror + complex-ifft route fails the same way). Same flops as
    the native route."""
    y = jnp.moveaxis(y, axis, -1)
    lead = y.shape[:-1]
    x = jnp.fft.irfft(y.reshape(-1, y.shape[-1]), n=n, axis=-1)
    return jnp.moveaxis(x.reshape(lead + (n,)), -1, axis)


def _ctype_for(rdtype):
    return (jnp.complex128
            if jnp.dtype(rdtype) == jnp.float64 else jnp.complex64)


def _xla_real_mode() -> str:
    """How the xla executor runs real transforms: ``native`` (RFFT/IRFFT
    HLOs as traced) or ``safe`` (fft+slice / IRFFT on 2D row batches,
    :func:`rows_c2r`). ``auto`` (default) resolves per backend: on the
    TPU the native c2r fails its gate at 256^3 and 512^3 (relative L2
    0.34, chip runs of PR 21) while the safe route passes, so auto =
    safe on TPU, native elsewhere. ``DFFT_XLA_REAL=native|safe``
    overrides."""
    import os

    mode = os.environ.get("DFFT_XLA_REAL", "auto")
    if mode in ("native", "safe"):
        return mode
    import jax

    return "safe" if jax.default_backend() == "tpu" else "native"


def _xla_r2c(x: Array, axis: int) -> Array:
    """Real-to-complex DFT along ``axis``: output extent n//2+1,
    unnormalized."""
    if _xla_real_mode() == "safe":
        return slice_r2c(x, axis)
    return jnp.fft.rfft(x, axis=axis)


def _xla_c2r(y: Array, n: int, axis: int) -> Array:
    """Complex-to-real inverse DFT along ``axis`` back to true extent ``n``;
    scaled by 1/n (numpy convention)."""
    if _xla_real_mode() == "safe":
        return rows_c2r(y, n, axis)
    return jnp.fft.irfft(y, n=n, axis=axis)


register_real_executor("xla", _xla_r2c, _xla_c2r)


def _matmul_r2c(x: Array, axis: int) -> Array:
    from . import dft_matmul
    from .realfft import r2c_via_half_complex

    n = x.shape[axis]
    if n % 2 == 0 and n > 2 and not jnp.issubdtype(
            jnp.dtype(x.dtype), jnp.complexfloating):
        # Half-length packed transform: half the flops of the promote-and-
        # slice path (the native-r2c discipline of rocfft_executor_r2c,
        # heffte_backend_rocm.h:567).
        return r2c_via_half_complex(x, axis, dft_matmul.fft_along_axis)
    y = dft_matmul.fft_along_axis(x, axis, forward=True)
    import jax.lax as lax

    return lax.slice_in_dim(y, 0, n // 2 + 1, axis=axis)


def _matmul_c2r(y: Array, n: int, axis: int) -> Array:
    from . import dft_matmul
    import jax.lax as lax

    from .realfft import c2r_via_half_complex

    if n % 2 == 0 and n > 2:
        return c2r_via_half_complex(y, n, axis, dft_matmul.fft_along_axis)
    # Odd n: rebuild the full hermitian spectrum from the non-redundant
    # half, then a plain complex inverse; imaginary residue is dropped.
    from .ddfft import mirror_half_spectrum

    full = mirror_half_spectrum(y, n, axis=axis)
    x = dft_matmul.fft_along_axis(full, axis, forward=False)
    return jnp.real(x)


register_real_executor("matmul", _matmul_r2c, _matmul_c2r)


def _pallas_executor(x: Array, axes: Sequence[int], forward: bool = True) -> Array:
    import jax.lax as lax

    from . import pallas_fft

    axes = tuple(axes)
    if jnp.dtype(x.dtype) != jnp.complex64 or x.size == 0:
        for ax in axes:
            x = pallas_fft.fft_along_axis(x, ax, forward=forward)
        return x
    if not axes:
        return x
    # complex64: split into float32 planes once, transform every axis on
    # the planes, combine once.
    re, im = pallas_fft.fft_planes(jnp.real(x), jnp.imag(x), axes,
                                   forward=forward)
    return lax.complex(re, im)


register_executor("pallas", _pallas_executor)


def _pallas_r2c(x: Array, axis: int) -> Array:
    import jax.lax as lax

    from . import pallas_fft
    from .realfft import r2c_via_half_complex

    n = x.shape[axis]
    if n % 2 == 0 and n > 2 and not jnp.issubdtype(
            jnp.dtype(x.dtype), jnp.complexfloating):
        # Half-length packed transform (see _matmul_r2c). f32 input packs
        # to complex64 and runs the fused kernel; f64 packs to complex128,
        # which the kernel's dtype gate routes to the matmul fallback —
        # still the packed half-length work, just not the fused engine.
        return r2c_via_half_complex(x, axis, pallas_fft.fft_along_axis)
    # Odd n: promote real input up front — the kernel's dtype gate only
    # admits complex64, so a float32 operand would silently take the
    # fallback.
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        wide = jnp.dtype(x.dtype).itemsize >= 8
        x = x.astype(jnp.complex128 if wide else jnp.complex64)
    y = pallas_fft.fft_along_axis(x, axis, forward=True)
    return lax.slice_in_dim(y, 0, n // 2 + 1, axis=axis)


def _pallas_c2r(y: Array, n: int, axis: int) -> Array:
    import jax.lax as lax

    from . import pallas_fft
    from .realfft import c2r_via_half_complex

    if n % 2 == 0 and n > 2:
        return c2r_via_half_complex(y, n, axis, pallas_fft.fft_along_axis)
    from .ddfft import mirror_half_spectrum

    full = mirror_half_spectrum(y, n, axis=axis)
    return jnp.real(pallas_fft.fft_along_axis(full, axis, forward=False))


register_real_executor("pallas", _pallas_r2c, _pallas_c2r)


def get_r2c(name: str) -> Callable:
    if ":" in name:
        base, tier, cmode = split_executor(name)
        return _scoped(get_r2c(base), tier, cmode)
    return _R2C_REGISTRY.get(name, _xla_r2c)


def get_c2r(name: str) -> Callable:
    if ":" in name:
        base, tier, cmode = split_executor(name)
        return _scoped(get_c2r(base), tier, cmode)
    return _C2R_REGISTRY.get(name, _xla_c2r)
