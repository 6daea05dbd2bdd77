"""Spectral-operator subsystem — fused FFT -> pointwise -> iFFT plans.

The transform layer below only *transforms*; the workloads users
actually run are operators: Poisson solves, spectral derivatives,
Gaussian filtering, large-kernel convolution (AccFFT's operator tier,
arXiv 1506.07933 — and "Large-Scale DFT on TPUs", arXiv 2002.03260,
keeps the pointwise stage on-device between the transform halves for
the same reason). This module plans those operators as ONE jitted
program: a forward chain that stops in the *transposed* midpoint
layout, a symbolically-specified wavenumber-indexed multiplier
generated per shard (and per overlap chunk) right there, and an
inverse chain that retraces the exchanges back to the input layout.

Why fuse at the transposed midpoint: the multiplier is diagonal
(pointwise) in wavenumber space, so it does not care which layout the
spectrum lives in. A natural-layout unfused composition — forward
transform, reshard the spectrum back to the caller's input layout,
multiply, reshard again for the inverse — pays a cancelling pair of
global transposes around the multiply. The fused chain applies the
multiplier where the forward half already is and skips that pair
entirely: the classic pruned-spectral-solver trick, compiling exactly
HALF the all-to-all collectives of the natural-layout pair (pinned in
``tests/test_a2h_operators.py``) and roughly halving t2 wire bytes per
solve.

Everything composes with the existing chain axes: ``batch=B`` rides
every collective as a bystander dim (B solves, one collective latency),
``overlap_chunks=K`` pipelines both exchange legs with the multiplier
generated per chunk through the midpoint bounds hook,
``wire_dtype="bf16"`` compresses each leg's wire (the multiplier
applies on the DECODED payload), and ``algorithm="hierarchical"`` runs
each leg as the two-leg ICI/DCN transport on a hybrid mesh. Operator
plans are plan-cache memoized, get their own wisdom kind
(``op:<name>`` — transform winners never cross-replay), and carry a
``t_mid`` stage through the model (:func:`..plan_logic
.model_stage_seconds`), the flight recorder (``t_mid``/
``t_mid_pointwise`` spans), and ``dfft.explain``.

Wavenumber convention: the unit torus — ``k_d = 2*pi*f_d`` with
``f_d`` the signed integer frequency of axis ``d`` (numpy ``fftfreq``
indexing, times ``n``). Scale the operator parameters for other box
lengths (e.g. a physical Poisson solve on ``[0, L)^3`` divides the
result by ``(2*pi/L)^-2`` — equivalently pre-scale ``f``).

A real field takes ``real=True`` (one device): r2c, the multiplier on
the half spectrum, c2r, in one program at half the work of the complex
route (:func:`plan_spectral_op`).

See ``docs/OPERATORS.md`` for the operator menu and the fusion model.
"""

from __future__ import annotations

import functools
import hashlib
import math
from dataclasses import dataclass, field, replace as _dc_replace
from typing import Any, Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import api as _api
from .api import FORWARD, OpPlan3D, prepare_plan
from .geometry import world_box
from .ops.executors import get_c2r, get_executor, get_r2c
from .parallel.pencil import build_pencil_spectral_op
from .parallel.slab import apply_multiplier, build_slab_spectral_op
from .plan_logic import (
    logic_plan3d, resolve_options, resolve_tune_mode, stage_layouts,
)
from .utils import metrics as _metrics
from .utils.trace import stage_scope

__all__ = [
    "SpectralOp",
    "poisson",
    "biharmonic",
    "helmholtz",
    "gradient",
    "gaussian",
    "convolve",
    "custom",
    "chain",
    "named_op",
    "OP_NAMES",
    "multiplier_grid",
    "plan_spectral_op",
    "solve_poisson",
    "spectral_gradient",
    "gaussian_filter",
    "fft_convolve",
]


@dataclass(frozen=True)
class SpectralOp:
    """Symbolic pointwise spectral multiplier — the operator a fused
    plan applies at its transposed midpoint.

    ``kind`` names the operator family; ``params`` is the hashable
    parameter tuple (the plan-cache and wisdom identity — two ops that
    could generate different multipliers must never compare equal);
    ``payload`` carries non-hashable data (a convolution kernel, a
    custom multiplier callable) excluded from equality — its identity
    lives in ``params`` (a content digest for kernels, the callable id
    for custom ops). Build instances through the constructors below."""

    kind: str
    params: tuple = ()
    payload: Any = field(default=None, compare=False, repr=False)

    @property
    def name(self) -> str:
        """Short label for metric/CSV stamping (``poisson``,
        ``gradient0``, ...)."""
        if self.kind == "gradient":
            return f"gradient{self.params[0]}"
        if self.kind == "helmholtz":
            return f"helmholtz{self.params[0]:g}"
        if self.kind == "chain":
            return "chain(" + "+".join(o.name for o in self.payload) + ")"
        return self.kind


def poisson() -> SpectralOp:
    """Poisson solve ``laplacian(u) = f`` on the unit torus: multiplier
    ``-1/|k|^2`` with the zero mode nulled (the solution is mean-free —
    the k=0 compatibility convention every spectral solver uses)."""
    return SpectralOp("poisson")


def biharmonic() -> SpectralOp:
    """Biharmonic solve ``laplacian(laplacian(u)) = f`` on the unit
    torus: multiplier ``1/|k|^4`` with the zero mode nulled (the symbol
    of the squared Laplacian is ``|k|^4``; the solution is mean-free).
    Exactly the composition of two Poisson solves —
    ``biharmonic == chain([poisson, poisson])`` multiplier-for-
    multiplier (the parity pin of ``tests/test_a2h_operators.py``) —
    but priced and fused as ONE t_mid multiply."""
    return SpectralOp("biharmonic")


def helmholtz(shift: float) -> SpectralOp:
    """Helmholtz solve ``(shift - laplacian) u = f`` on the unit torus:
    multiplier ``1/(shift + |k|^2)``. ``shift > 0`` is the screened
    (modified) Helmholtz operator — well-posed at every mode, identity
    parity ``(shift + |k|^2) * multiplier == 1``. ``shift == 0``
    degenerates to the negative Poisson solve (zero mode nulled, the
    mean-free convention)."""
    s = float(shift)
    if not s >= 0.0:
        raise ValueError(f"helmholtz shift must be >= 0, got {shift!r}")
    return SpectralOp("helmholtz", (s,))


def chain(ops: Sequence["SpectralOp"]) -> SpectralOp:
    """Operator chaining: compose N diagonal multipliers into ONE
    fused plan — one forward transform, the *product* of the
    multipliers at the single t_mid midpoint, one inverse transform
    per set. Because every op is pointwise-diagonal in wavenumber
    space, composition is just multiplication — the chained plan
    compiles exactly the collective count of a single-op fused plan
    (pinned), where running the ops as separate plans would pay the
    full exchange round trip per op.

    Identity lives in the member ops' identities (kind + params in
    order — chains over different kernels/callables never collide)."""
    ops = tuple(ops)
    if not ops:
        raise ValueError("chain() takes at least one SpectralOp")
    for o in ops:
        if not isinstance(o, SpectralOp):
            raise TypeError(
                f"chain() composes SpectralOp instances, got {o!r}")
    if len(ops) == 1:
        return ops[0]
    return SpectralOp("chain", tuple((o.kind, o.params) for o in ops),
                      payload=ops)


def gradient(axis: int = 0) -> SpectralOp:
    """Spectral derivative along ``axis``: multiplier ``i*k_axis``."""
    if axis not in (0, 1, 2):
        raise ValueError(f"gradient axis must be 0, 1, or 2; got {axis}")
    return SpectralOp("gradient", (int(axis),))


def gaussian(sigma: float = 1.0) -> SpectralOp:
    """Gaussian low-pass filter: multiplier ``exp(-|k|^2 sigma^2 / 2)``
    (sigma in unit-torus length units)."""
    if not sigma > 0:
        raise ValueError(f"gaussian sigma must be > 0, got {sigma}")
    return SpectralOp("gaussian", (float(sigma),))


def convolve(kernel) -> SpectralOp:
    """Circular convolution with ``kernel`` (a world-shaped array):
    multiplier ``FFT(kernel)``, precomputed at plan time (numpy on
    host) and gathered per shard. The kernel spectrum is replicated per
    device — suited to kernels that fit device memory; the *data* stays
    fully distributed. Identity: ``convolve(delta at 0) == roundtrip``.

    The op's cache/wisdom identity is the kernel's content digest, so
    two plans over different kernels never share a compiled program."""
    arr = np.asarray(kernel)
    digest = hashlib.sha256(arr.tobytes()).hexdigest()[:16]
    return SpectralOp("convolve", (digest, arr.shape), payload=arr)


def custom(name: str, fn: Callable) -> SpectralOp:
    """A caller-supplied multiplier generator: ``fn(i0, i1, i2)`` takes
    broadcastable int32 GLOBAL index grids of the three spatial axes
    (already offset for the executing shard/chunk) and returns the
    pointwise factor (real or complex, broadcastable). Plan-cache
    identity is ``(name, id(fn))`` — stable within a process."""
    if not callable(fn):
        raise TypeError("custom() takes a callable multiplier generator")
    return SpectralOp("custom", (str(name), id(fn)), payload=fn)


#: Driver-tier operator menu (``speed3d -op``, ``DFFT_BENCH_OP``).
OP_NAMES = ("poisson", "grad", "gauss", "biharm", "helmholtz")


def named_op(name: str, **kw) -> SpectralOp:
    """The driver-tier operator spelled by name: ``poisson``,
    ``grad``/``gradient`` (axis via ``axis=``, default 0), ``gauss``/
    ``gaussian`` (``sigma=``, default 1.0), ``biharm``/``biharmonic``,
    ``helmholtz`` (``shift=``, default 1.0)."""
    n = name.strip().lower()
    if n == "poisson":
        return poisson()
    if n in ("grad", "gradient"):
        return gradient(kw.pop("axis", 0))
    if n in ("gauss", "gaussian"):
        return gaussian(kw.pop("sigma", 1.0))
    if n in ("biharm", "biharmonic"):
        return biharmonic()
    if n == "helmholtz":
        return helmholtz(kw.pop("shift", 1.0))
    raise ValueError(
        f"unknown operator {name!r}; expected one of {OP_NAMES}")


# ------------------------------------------------------- multiplier gen

def _multiplier_fn(op: SpectralOp, shape, cdtype) -> Callable:
    """The per-shard multiplier generator of one op at one world shape:
    ``fn(i0, i1, i2)`` over broadcastable int32 global index grids.
    Wavenumbers are computed at the chain's real component precision
    (f64 under a c128 plan) so the accuracy tier is not silently
    degraded by f32 constants."""
    shape = tuple(int(s) for s in shape)
    rdt = (jnp.float64 if np.dtype(cdtype) == np.complex128
           else jnp.float32)
    two_pi = 2.0 * math.pi

    def k_of(i, n):
        # Signed integer frequency (numpy fftfreq * n), then angular.
        f = jnp.where(i < (n + 1) // 2, i, i - n).astype(rdt)
        return f * rdt(two_pi)

    if op.kind == "poisson":

        def mult(i0, i1, i2):
            k0, k1, k2 = (k_of(i0, shape[0]), k_of(i1, shape[1]),
                          k_of(i2, shape[2]))
            ksq = k0 * k0 + k1 * k1 + k2 * k2
            nz = ksq > 0
            return jnp.where(nz, -1.0 / jnp.where(nz, ksq, 1.0), 0.0)

        return mult
    if op.kind == "biharmonic":

        def mult(i0, i1, i2):
            k0, k1, k2 = (k_of(i0, shape[0]), k_of(i1, shape[1]),
                          k_of(i2, shape[2]))
            ksq = k0 * k0 + k1 * k1 + k2 * k2
            nz = ksq > 0
            return jnp.where(
                nz, 1.0 / jnp.where(nz, ksq * ksq, 1.0), 0.0)

        return mult
    if op.kind == "helmholtz":
        shift = op.params[0]

        def mult(i0, i1, i2):
            k0, k1, k2 = (k_of(i0, shape[0]), k_of(i1, shape[1]),
                          k_of(i2, shape[2]))
            ksq = shift + k0 * k0 + k1 * k1 + k2 * k2
            if shift > 0:
                return 1.0 / ksq
            nz = ksq > 0  # shift==0: the mean-free Poisson convention
            return jnp.where(nz, 1.0 / jnp.where(nz, ksq, 1.0), 0.0)

        return mult
    if op.kind == "chain":
        # Diagonal ops compose by multiplication: ONE t_mid multiply
        # carries the whole set (one forward, one inverse per set).
        fns = [_multiplier_fn(o, shape, cdtype) for o in op.payload]

        def mult(i0, i1, i2):
            m = fns[0](i0, i1, i2)
            for f in fns[1:]:
                m = m * f(i0, i1, i2)
            return m

        return mult
    if op.kind == "gradient":
        axis = op.params[0]

        def mult(i0, i1, i2):
            k = k_of((i0, i1, i2)[axis], shape[axis])
            return (1j * k).astype(np.dtype(cdtype))

        return mult
    if op.kind == "gaussian":
        sigma = op.params[0]

        def mult(i0, i1, i2):
            k0, k1, k2 = (k_of(i0, shape[0]), k_of(i1, shape[1]),
                          k_of(i2, shape[2]))
            ksq = k0 * k0 + k1 * k1 + k2 * k2
            return jnp.exp(rdt(-0.5 * sigma * sigma) * ksq)

        return mult
    if op.kind == "convolve":
        kernel = np.asarray(op.payload)
        if kernel.shape != shape:
            raise ValueError(
                f"convolve kernel shape {kernel.shape} != world {shape}")
        # Host-side FFT at plan time (numpy — never the backend's fft
        # thunk), replicated per device; the chain gathers its shard's
        # slice through the global index grids.
        khat = jnp.asarray(np.fft.fftn(kernel).astype(np.dtype(cdtype)))

        def mult(i0, i1, i2):
            return khat[i0, i1, i2]

        return mult
    if op.kind == "custom":
        return op.payload
    raise ValueError(f"unknown SpectralOp kind {op.kind!r}")


def _full_grids(shape, n2_kept: int | None = None) -> tuple:
    """Broadcastable int32 index grids of the world spectrum; with
    ``n2_kept`` only the first ``n2_kept`` indices of axis 2 (the half
    spectrum ``n2//2 + 1`` of a real field)."""
    n0, n1, n2 = (int(s) for s in shape)
    return (jnp.arange(n0, dtype=jnp.int32)[:, None, None],
            jnp.arange(n1, dtype=jnp.int32)[None, :, None],
            jnp.arange(n2 if n2_kept is None else n2_kept,
                       dtype=jnp.int32)[None, None, :])


def multiplier_grid(op: SpectralOp, shape, dtype=None):
    """The op's full world-shaped multiplier array — the reference the
    unfused composition (and the parity tests, and the bench verify
    gate) multiplies the natural-layout spectrum by."""
    cdtype = _api._default_cdtype(dtype)
    return _multiplier_fn(op, shape, cdtype)(*_full_grids(shape))


# ------------------------------------------------------------- planner

def plan_spectral_op(
    shape: Sequence[int],
    mesh=None,
    *,
    op: SpectralOp,
    direction: int = FORWARD,
    real: bool = False,
    decomposition: str | None = None,
    executor: str | None = None,
    dtype: Any = None,
    donate: bool = False,
    algorithm: str = "alltoall",
    overlap_chunks: int | str | None = None,
    tune: str | None = None,
    wire_dtype: str | None = None,
    max_roundtrip_err: float | None = None,
    fuse: bool | str | None = None,
    options=None,
    batch: int | None = None,
) -> OpPlan3D:
    """Plan one fused spectral operator: FFT -> pointwise ``op`` ->
    iFFT as ONE jitted program, I/O in the chain's canonical input
    layout on BOTH sides (in == out sharding; a unit multiplier is the
    identity, forward unnormalized x inverse 1/N). An operator runs its
    forward transform first: ``direction`` is ``FORWARD`` or a
    ``ValueError``.

    ``real=True`` plans the operator on a real field (one device only;
    a mesh raises ``ValueError``): ``dtype`` stays the complex working
    dtype and the plan takes and returns its real counterpart (float32
    for complex64). The program is the executor's r2c along axis 2
    (``t0_r2c_z``), the forward complex axes 0 and 1 (``t3_fft_xy``),
    the multiplier on the half-spectrum grid ``(n0, n1, n2//2 + 1)``
    (``t_mid``), the inverse complex axes (``t3_ifft_xy``) and the c2r
    back to ``n2`` with numpy's 1/N (``t0_c2r_z``): numpy's
    ``irfftn(m_half * rfftn(x), s=shape)``. The half grid indexes axis
    2 as ``0..n2//2``, with the Nyquist index of an even ``n2`` at its
    signed frequency ``-n2/2``, as on the full grid. For a multiplier
    with ``m(-k) = conj(m(k))`` (every even real one: poisson,
    biharmonic, helmholtz, gaussian) the result is the complex plan's
    on the same field. For an odd one such as ``gradient`` the
    ``irfftn`` convention decides the Nyquist planes: the c2r keeps
    only the real part of the DC and Nyquist planes of axis 2, so
    ``gradient(2)`` contributes nothing at the Nyquist plane.

    The chain runs the canonical forward decomposition, stops at the
    transposed midpoint (slab: Y-slab layout after the t2 exchange;
    pencil: the x-pencil layout after both exchanges), applies the
    wavenumber-diagonal multiplier there (the ``t_mid`` stage — indices
    are generated per shard and per overlap chunk, so the multiplier
    never materializes globally), and retraces the exchanges back —
    skipping the cancelling transpose pair a natural-layout unfused
    composition pays (half its all-to-alls; see the module docstring).

    All :func:`..api.plan_dft_c2c_3d` knobs compose: ``batch=B``
    coalesces B solves into one program, ``overlap_chunks`` pipelines
    both exchange legs, ``wire_dtype`` compresses each leg's wire,
    ``algorithm="hierarchical"`` takes the two-leg transport on a
    hybrid mesh, and ``tune="wisdom"|"measure"`` runs the measured
    planner under the operator's own wisdom kind (``op:<name>`` —
    transform winners never cross-replay; see ``docs/TUNING.md``).
    """
    if direction != FORWARD:
        raise ValueError(
            f"an operator plan runs its forward transform first: direction "
            f"must be FORWARD ({FORWARD}), got {direction!r}")
    shape, _ = _api._check_direction(shape, FORWARD)
    if isinstance(op, (list, tuple)):
        # Operator chaining: a sequence composes its diagonal
        # multipliers at ONE t_mid — one forward, one inverse per SET
        # (collective count pinned equal to a single-op fused plan).
        op = chain(op)
    if not isinstance(op, SpectralOp):
        raise TypeError(
            f"op must be a SpectralOp (poisson(), gradient(), ...) or "
            f"a sequence of them (operator chaining); got {op!r}")
    batch = _api._norm_batch(batch)
    knobs = dict(decomposition=decomposition, algorithm=algorithm,
                 donate=donate, overlap_chunks=overlap_chunks, tune=tune,
                 wire_dtype=wire_dtype, max_roundtrip_err=max_roundtrip_err,
                 fuse=fuse)
    cdtype = _api._default_cdtype(dtype)
    real = bool(real)
    if real:
        check, _ = resolve_options(executor, options, **knobs)
        if not jnp.issubdtype(cdtype, jnp.complexfloating):
            raise ValueError(
                f"real operator plans take the complex working dtype, got "
                f"{cdtype}; the real field's dtype is derived from it")
        if logic_plan3d(shape, mesh, check, forward=True,
                        batch=batch).decomposition != "single":
            raise ValueError(
                "real=True operator plans run on one device; on a mesh "
                "plan the complex operator (real=False)")
    plan_kw = dict(dtype=dtype, batch=batch, real=real)
    resolved = prepare_plan(
        functools.partial(plan_spectral_op, op=op), shape, mesh, executor,
        options, knobs, plan_kw, transform="r2c" if real else "c2c",
        op=op.name, tuned=functools.partial(_tuned_op_plan, shape, mesh, op))
    if isinstance(resolved, OpPlan3D):
        return resolved
    lp, label = resolved
    opts = lp.options
    mult = _multiplier_fn(op, shape, cdtype)
    bo = 0 if batch is None else 1
    io_dtype = cdtype

    if real:
        ex = get_executor(label)
        r2c, c2r = get_r2c(label), get_c2r(label)
        n2 = shape[2]
        grids = _full_grids(shape, n2 // 2 + 1)
        cplx_axes = (bo, 1 + bo)

        def _single_real(x):
            with stage_scope("t0_r2c_z"):
                y = r2c(x, 2 + bo)
            with stage_scope("t3_fft_xy"):
                y = ex(y, cplx_axes, True)
            with stage_scope("t_mid"), stage_scope("t_mid_pointwise"):
                y = apply_multiplier(y, mult(*grids))
            with stage_scope("t3_ifft_xy"):
                y = ex(y, cplx_axes, False)
            with stage_scope("t0_c2r_z"):
                return c2r(y, n2, 2 + bo)

        # Real field and half spectrum differ in dtype and size, so no
        # buffer can be donated (the plan_dft_r2c_3d policy).
        fn = jax.jit(_single_real)
        spec = None
        io_dtype = jnp.dtype(jnp.float64 if cdtype == jnp.complex128
                             else jnp.float32)
    elif lp.decomposition == "single":
        ex = get_executor(label)
        fft_axes = tuple(a + bo for a in range(3))
        grids = _full_grids(shape)

        def _single(x):
            y = ex(x, fft_axes, True)
            with stage_scope("t_mid"), stage_scope("t_mid_pointwise"):
                y = apply_multiplier(y, mult(*grids))
            return ex(y, fft_axes, False)

        fn = jax.jit(_single, donate_argnums=(0,) if opts.donate else ())
        spec = None
    elif lp.decomposition == "slab":
        fn, spec = build_slab_spectral_op(
            lp.mesh, shape, mult,
            axis_name=_api._slab_axis_name(lp.mesh),
            executor=label, donate=opts.donate,
            algorithm=opts.algorithm,
            overlap_chunks=opts.overlap_chunks, batch=batch,
            wire_dtype=opts.wire_dtype)
    else:
        row, col = lp.mesh.axis_names[:2]
        fn, spec = build_pencil_spectral_op(
            lp.mesh, shape, mult, row_axis=row, col_axis=col,
            executor=label, donate=opts.donate,
            algorithm=opts.algorithm,
            overlap_chunks=opts.overlap_chunks, batch=batch,
            wire_dtype=opts.wire_dtype)

    # I/O sharding and boxes are the chain's INPUT side on both ends —
    # the operator's whole point is that the caller's layout round trip
    # disappears.
    if spec is None or lp.mesh is None:
        in_sh = None
    else:
        from jax.sharding import NamedSharding

        from .parallel.slab import batch_pspec

        pspec = (spec.in_pspec if hasattr(spec, "in_pspec")
                 else spec.in_spec)
        in_sh = NamedSharding(lp.mesh, batch_pspec(pspec, batch))
    boxes = list(stage_layouts(
        lp.decomposition, lp.mesh, world_box(shape),
        slab_axes=lp.slab_axes, pencil_perm=lp.pencil_perm,
        pencil_order=lp.pencil_order)[0][1])
    io_shape = shape if batch is None else (batch,) + shape
    _metrics.inc("spectral_op_plans", op=op.name, real=real,
                 decomposition=lp.decomposition)
    return OpPlan3D(
        shape=shape, direction=FORWARD, dtype=cdtype,
        decomposition=lp.decomposition, executor=opts.executor,
        mesh=lp.mesh, fn=fn, spec=spec,
        in_sharding=in_sh, out_sharding=in_sh,
        in_boxes=boxes, out_boxes=list(boxes),
        in_shape=io_shape, out_shape=io_shape,
        in_dtype=io_dtype, out_dtype=io_dtype, real=real, batch=batch,
        options=lp.options, logic=lp,
        op=op.name, op_spec=op, multiplier=mult,
    )


plan_spectral_op = _api._plan_cached("op", plan_spectral_op)


def solve_poisson(shape, mesh=None, **kw) -> OpPlan3D:
    """Fused Poisson solver plan: ``plan(f)`` returns the mean-free u
    with ``laplacian(u) = f - mean(f)`` on the unit torus (multiplier
    ``-1/|k|^2``, zero mode nulled). ``real=True`` solves for a real
    field on the half spectrum (:func:`plan_spectral_op`)."""
    return plan_spectral_op(shape, mesh, op=poisson(), **kw)


def spectral_gradient(shape, mesh=None, *, axis: int = 0,
                      **kw) -> OpPlan3D:
    """Fused spectral-derivative plan along ``axis`` (multiplier
    ``i*k_axis``)."""
    return plan_spectral_op(shape, mesh, op=gradient(axis), **kw)


def gaussian_filter(shape, mesh=None, *, sigma: float = 1.0,
                    **kw) -> OpPlan3D:
    """Fused Gaussian filter plan (multiplier
    ``exp(-|k|^2 sigma^2 / 2)``)."""
    return plan_spectral_op(shape, mesh, op=gaussian(sigma), **kw)


def fft_convolve(shape, mesh=None, *, kernel, **kw) -> OpPlan3D:
    """Fused circular-convolution plan with a world-shaped ``kernel``
    (multiplier ``FFT(kernel)``, precomputed host-side at plan time)."""
    return plan_spectral_op(shape, mesh, op=convolve(kernel), **kw)


# ------------------------------------------------------- tuned planning

def _build_op_candidate(shape, mesh, op, base, plan_kw, cand, *,
                        donate: bool) -> OpPlan3D:
    opts = _dc_replace(
        base, tune="off", decomposition=cand.decomposition,
        algorithm=cand.algorithm, executor=cand.executor,
        overlap_chunks=int(cand.overlap_chunks), donate=donate,
        wire_dtype=cand.wire_dtype or "none")
    return plan_spectral_op(shape, mesh, op=op, options=opts, **plan_kw)


def _tuned_op_plan(shape, mesh, op: SpectralOp, options, plan_kw: dict):
    """The tuned tier of :func:`plan_spectral_op` — the transform
    tuner's wisdom/measure flow under the operator's OWN wisdom kind
    (``op:<name>``): a winner measured for a fused Poisson chain (two
    exchange legs, midpoint compute between them) moves the
    transport/overlap crossovers, so transform winners and operator
    winners must never cross-replay."""
    from . import tuner
    from .parallel.multihost import is_hybrid_mesh

    mode = resolve_tune_mode(options.tune)
    base = _dc_replace(options, tune="off", donate=False)
    heuristic = _dc_replace(options, tune="off")
    ndev, mesh_dims = tuner._mesh_context(mesh)
    if ndev <= 1:
        return plan_spectral_op(shape, mesh, op=op, options=heuristic,
                                **plan_kw)
    dtype = _api._default_cdtype(plan_kw.get("dtype"))
    batch = plan_kw.get("batch")
    err_budget = options.max_roundtrip_err
    kind = f"op:{op.name}" + (":real" if plan_kw.get("real") else "")
    key = tuner.wisdom_key(
        kind=kind, shape=shape, dtype=dtype, direction=FORWARD,
        ndev=ndev, mesh_dims=mesh_dims, batch=batch,
        err_budget=err_budget)
    path = tuner.default_wisdom_path()

    entry = tuner.lookup_wisdom(key, path) if path is not None else None
    if entry is not None:
        _metrics.inc("tune_wisdom_hits", kind=kind)
        wd = entry["winner"].get("wire_dtype")
        if wd is not None:
            rec_err = entry.get("compression_err")
            if rec_err is None:
                from .parallel.exchange import wire_roundtrip_error

                rec_err = wire_roundtrip_error(dtype, wd)
            if err_budget is None or rec_err > err_budget:
                wd = None
        cand = tuner.Candidate(
            decomposition=str(entry["winner"]["decomposition"]),
            algorithm=str(entry["winner"]["algorithm"]),
            executor=str(entry["winner"]["executor"]),
            overlap_chunks=int(entry["winner"]["overlap_chunks"]),
            wire_dtype=wd)
        return _build_op_candidate(shape, mesh, op, base, plan_kw, cand,
                                   donate=options.donate)
    _metrics.inc("tune_wisdom_misses", kind=kind)
    if mode == "wisdom":
        return plan_spectral_op(shape, mesh, op=op, options=heuristic,
                                **plan_kw)

    itemsize = np.dtype(dtype).itemsize
    wire_dtypes: tuple = (None,)
    if err_budget is not None:
        wire_dtypes = (None, "bf16")
    cands = tuner.prune_candidates(
        tuner.enumerate_candidates(
            shape, ndev, mesh_dims=mesh_dims, itemsize=itemsize,
            batch=batch, hybrid=is_hybrid_mesh(mesh),
            wire_dtypes=wire_dtypes),
        shape, mesh, itemsize=itemsize, batch=batch,
        max_err=err_budget, dtype=dtype)
    _metrics.set_gauge("tune_candidates", len(cands), kind=kind,
                       stage="pruned")
    by_label = {c.label: c for c in cands}
    _metrics.inc("tune_tournaments", kind=kind)
    iters, repeats = tuner.tune_budget()

    def build(label: str):
        return _build_op_candidate(shape, mesh, op, base, plan_kw,
                                   by_label[label], donate=False)

    def measure(plan) -> float:
        from .utils.timing import time_fn_amortized

        x = _api.alloc_local(plan)
        t, _ = time_fn_amortized(plan.fn, x, iters=iters,
                                 repeats=repeats)
        return t

    winner, built, times = tuner.measured_select(
        list(by_label), build, measure, what=f"{kind} tune candidate")
    tuner._log_model_divergence(by_label, times, winner, shape, mesh,
                                itemsize=itemsize, batch=batch)
    tuner.record_wisdom(key, by_label[winner], times[winner], path=path,
                        times=times)
    if options.donate:
        return _build_op_candidate(shape, mesh, op, base, plan_kw,
                                   by_label[winner], donate=True)
    return built[winner]
