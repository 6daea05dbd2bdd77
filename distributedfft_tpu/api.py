"""Plan/execute API — the framework's public surface.

Mirrors the reference's FFTW-MPI-style C API
(``3dmpifft_opt/include/fft_mpi_3d_api.h:68-74``):

    fft_mpi_init                  -> :func:`distributedfft_tpu.parallel.make_mesh`
    fft_mpi_plan_dft_c2c_3d       -> :func:`plan_dft_c2c_3d`
    fft_mpi_execute_dft_3d_c2c    -> :func:`execute` / ``Plan3D.__call__``
    fft_mpi_alloc_local_memory    -> :func:`alloc_local`
    fft_mpi_destroy_plan          -> :func:`destroy_plan` (a no-op: buffers
                                     are GC'd, plans are immutable)

plus the heFFTe-style r2c pair (``heffte_fft3d_r2c.h``):
:func:`plan_dft_r2c_3d` / :func:`plan_dft_c2r_3d`.

A plan captures everything the reference resolves at plan time — geometry,
exchange tables, compiled kernels (``setFFTPlans``,
``fft_mpi_3d_api.cpp:318-429``; hipRTC compilation,
``templateFFT.cpp:5621-5712``) — as jit-compiled XLA executables; execution
only replays them, exactly as ``launchFFTKernel`` only replays precomputed
launches (``templateFFT.cpp:6212-6260``). Decomposition/mesh/algorithm
decisions live in :mod:`.plan_logic` (the ``plan_operations`` analog).

Transform convention is numpy's: forward unnormalized, inverse scaled by
1/N. heFFTe-style ``Scale`` options are applied on top (see
:class:`distributedfft_tpu.ops.Scale`).
"""

from __future__ import annotations

import functools
import math
import os
import time
from dataclasses import dataclass, field
from typing import Any, Sequence, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .utils import metrics as _metrics
from .utils.trace import add_trace

from . import faults as _faults
from . import geometry as geo
from .geometry import Box3, world_box
from .ops.executors import (
    Scale, apply_scale, get_c2r, get_executor, get_r2c, scale_factor,
)
from .plan_logic import (
    DEFAULT_OPTIONS,
    LogicPlan,
    PlanOptions,
    io_boxes,
    logic_plan3d,
    resolve_executor,
    resolve_options,
    resolve_overlap_chunks,
    resolve_tune_mode,
    spec_entries as _spec_entries_impl,
)
from .parallel.pencil import PencilSpec, build_pencil_fft3d, build_pencil_rfft3d
from .parallel.slab import (
    SlabSpec,
    batch_pspec,
    build_slab_fft3d,
    build_slab_rfft3d,
    build_slab_stages,
    check_batch,
)

# FFTW sign convention (FFTW_FORWARD = -1, FFTW_BACKWARD = +1); single
# definition lives in .local, re-exported here as the public surface.
from .local import BACKWARD, FORWARD  # noqa: E402


@dataclass
class Plan3D:
    """A compiled distributed 3D FFT plan (one direction).

    The analog of the reference's plan struct
    (``fft_mpi_3d_api.h:11-66``): owns the decomposition geometry, the
    input/output shardings, and the compiled transform.
    """

    shape: tuple[int, int, int]
    direction: int
    dtype: Any
    decomposition: str            # "single" | "slab" | "pencil"
    executor: str
    mesh: Mesh | None
    fn: Callable
    spec: SlabSpec | PencilSpec | None
    in_sharding: NamedSharding | None
    out_sharding: NamedSharding | None
    in_boxes: list[Box3] = field(default_factory=list)
    out_boxes: list[Box3] = field(default_factory=list)
    # r2c/c2r plans transform between different shapes/dtypes; c2c plans leave
    # these as the world shape / complex dtype (set in __post_init__).
    in_shape: tuple[int, int, int] | None = None
    out_shape: tuple[int, int, int] | None = None
    in_dtype: Any = None
    out_dtype: Any = None
    real: bool = False
    # The halved axis of an r2c/c2r plan (heFFTe ``r2c_direction``).
    # Stored explicitly because shape inference is ambiguous when the
    # halved extent is 1 or 2 (N//2+1 == N there).
    r2c_axis: int = 2
    # Leading batch axis of a coalesced multi-request plan: B independent
    # same-shape transforms executed as ONE device program with one
    # shared exchange per t2 stage (in/out shapes carry the [B, ...]
    # prefix; boxes stay per-transform). None = unbatched (batch=1 plans
    # normalize here — byte-identical HLO to an unadorned plan).
    batch: int | None = None
    options: PlanOptions = DEFAULT_OPTIONS
    # The resolved plan skeleton (axis assignment, stage chain, device-count
    # negotiation record) — surfaced by plan_info.
    logic: LogicPlan | None = None
    # Brick-I/O plans: the two overlap-map ring edges (in->chain, chain->out)
    # with their payload/wire accounting (BrickSpec pair); None otherwise.
    brick_edges: tuple | None = None

    def __post_init__(self) -> None:
        if self.in_shape is None:
            self.in_shape = self.shape
        if self.out_shape is None:
            self.out_shape = self.shape
        if self.in_dtype is None:
            self.in_dtype = self.dtype
        if self.out_dtype is None:
            self.out_dtype = self.dtype

    @property
    def forward(self) -> bool:
        return self.direction == FORWARD

    @property
    def graph(self):
        """The declarative :class:`~.stagegraph.StageGraph` this plan's
        chain was compiled from (rides the compiled callable), or None
        for plans below the IR tier (single-device, dd, brick-wrapped,
        user-layout-wrapped chains) — the feature-detection hook of
        :func:`~.stagegraph.schedule_concurrent` and the serving tier's
        multi-group flush."""
        from .stagegraph import graph_of

        return graph_of(self.fn)

    @property
    def world_size(self) -> int:
        return math.prod(self.shape)

    def __call__(self, x, *, scale: Scale = Scale.NONE):
        return execute(self, x, scale=scale)

    def compile(self) -> "Plan3D":
        """Eagerly compile (and warm every cache for) this plan's
        transform, so later executes only replay — the reference's
        plan-time discipline: all hipRTC compilation happens inside
        ``setFFTPlans``/``initializeFFT`` and ``launchFFTKernel`` only
        replays precomputed launches (``templateFFT.cpp:5621-5712,
        6212-6260``). Runs one throwaway zero-filled execution; returns
        ``self`` for chaining."""
        from .utils.timing import sync

        _faults.check("compile", self.executor)
        t0 = time.perf_counter()
        sync(self.fn(alloc_local(self)))
        self._warm = True  # the compile fault point fired (or passed)
        if _metrics._enabled:
            _metrics.observe(
                "compile_seconds", time.perf_counter() - t0,
                decomposition=self.decomposition, executor=self.executor)
        return self

    def flops(self) -> float:
        return geo.fft_flops(self.shape)


@dataclass
class OpPlan3D(Plan3D):
    """A compiled fused spectral-operator plan (:mod:`.operators`):
    FFT -> pointwise multiplier -> iFFT as one program, I/O in the
    chain's canonical input layout on both sides (``in_sharding ==
    out_sharding``). ``op`` is the operator label ("poisson", ...),
    ``op_spec`` the symbolic :class:`~.operators.SpectralOp`, and
    ``multiplier`` the per-shard wavenumber-indexed generator (kept so
    the explain layer can rebuild the staged ``t_mid`` pipeline).
    Execution via ``plan(x)`` / :func:`execute` exactly like a
    transform plan."""

    op: str = ""
    op_spec: Any = None
    multiplier: Any = None


def _check_direction(shape, direction) -> tuple[tuple[int, int, int], bool]:
    shape = tuple(int(s) for s in shape)
    if len(shape) != 3:
        raise ValueError("3D plans require a 3D shape")
    if direction not in (FORWARD, BACKWARD):
        raise ValueError("direction must be FORWARD (-1) or BACKWARD (+1)")
    return shape, direction == FORWARD


def _default_cdtype(dtype):
    if dtype is None:
        return jnp.dtype(
            jnp.complex128 if jax.config.jax_enable_x64 else jnp.complex64
        )
    return jnp.dtype(dtype)


def _norm_batch(batch) -> int | None:
    """Planner ``batch`` argument -> None (unbatched) or an int >= 2.

    ``batch=1`` IS the unbatched plan: same chain, same plan-cache entry
    family, byte-identical HLO to an unadorned call (the acceptance
    pin) — the serving tier executes singleton groups through the plain
    plan instead of a [1, ...] program."""
    batch = check_batch(batch)
    return None if batch == 1 else batch


def _slab_axis_name(mesh: Mesh):
    """The slab chain's mesh-axis spec: the single 1D axis name, or the
    (dcn, ici) tuple of a hierarchical plan's hybrid mesh (the combined
    axis in row-major linearization)."""
    names = mesh.axis_names
    return names[0] if len(names) == 1 else tuple(names)


def _shardings(lp: LogicPlan, spec, batch: int | None = None):
    """Input/output NamedShardings of the built chain — taken from the
    builder's own spec object (direction-true), so they reflect generalized
    axis assignments. ``batch`` prepends the replicated leading batch
    entry of a batched chain."""
    if lp.mesh is None or spec is None:
        return None, None
    if hasattr(spec, "in_pspec"):  # SlabSpec
        return (NamedSharding(lp.mesh, batch_pspec(spec.in_pspec, batch)),
                NamedSharding(lp.mesh, batch_pspec(spec.out_pspec, batch)))
    return (NamedSharding(lp.mesh, batch_pspec(spec.in_spec, batch)),
            NamedSharding(lp.mesh, batch_pspec(spec.out_spec, batch)))


def _boxes(lp: LogicPlan, world_in: Box3, world_out: Box3):
    """Per-device input/output boxes of this plan's own orientation; r2c
    plans pass a shrunk complex-side world. Delegates to
    :func:`.plan_logic.io_boxes` (one source of truth with ``lp.stages``)."""
    return io_boxes(lp, world_in, world_out)


def _spec_entries(mesh: Mesh, spec: P, ndim: int) -> tuple:
    """Validate a user PartitionSpec (rank, axis names) and return it padded
    to ``ndim`` entries (shared with the planner's layout classifier)."""
    return _spec_entries_impl(mesh, spec, ndim)


def _layout_boxes(mesh: Mesh, spec: P, world: Box3) -> list[Box3]:
    """Per-device boxes of a mesh-expressible layout, ordered to match
    ``mesh.devices.flat`` (the same device order as the canonical
    ``io_boxes``) — the ``ioboxes`` view of a PartitionSpec, derived from
    the sharding's own index map so box metadata can never diverge from
    what XLA actually places on each device."""
    _spec_entries(mesh, spec, 3)
    shape = tuple(h - lo for lo, h in zip(world.low, world.high))
    index_map = NamedSharding(mesh, spec).devices_indices_map(shape)
    boxes = []
    for dev in mesh.devices.flat:
        idxs = index_map[dev]
        low = tuple(world.low[d] + (ix.start or 0) for d, ix in enumerate(idxs))
        high = tuple(
            world.low[d] + (ix.stop if ix.stop is not None else shape[d])
            for d, ix in enumerate(idxs)
        )
        boxes.append(Box3(low, high))
    return boxes


def _spec_divides(mesh: Mesh, spec: P, shape) -> bool:
    """True when every sharded dim of ``shape`` divides by its mesh-axis
    product (the equal-shard requirement of jit-level shardings)."""
    for d, entry in enumerate(_spec_entries(mesh, spec, len(shape))):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        k = math.prod(mesh.shape[nm] for nm in names)
        if shape[d] % k:
            return False
    return True


def _wrap_user_layout(
    fn: Callable,
    mesh: Mesh,
    canonical_in: NamedSharding,
    canonical_out: NamedSharding,
    in_spec: P | None,
    out_spec: P | None,
    donate: bool,
    in_shape,
    out_shape,
) -> tuple[Callable, NamedSharding, NamedSharding]:
    """Compose user brick layouts around a canonical-layout transform — the
    heFFTe brick-in/brick-out capability (arbitrary ``box3d`` per rank,
    ``heffte_fft3d.h:105-115``) restricted to mesh-expressible bricks: the
    reshard into the canonical layout is the plan's first (and last)
    reshape, exactly how heFFTe's planner prepends/appends reshapes for
    non-pencil input (``heffte_plan_logic.cpp:162-245``). XLA emits the
    collectives for both reshards and fuses them into the program.

    User bricks require evenly-divisible extents (TPU equal-shard rule);
    uneven *canonical* layouts are fine — the inner plan pads/crops itself,
    so sharding hints are simply omitted where they would not divide.
    """
    for label, spec, shp in (("in_spec", in_spec, in_shape),
                             ("out_spec", out_spec, out_shape)):
        if spec is not None and not _spec_divides(mesh, spec, shp):
            raise ValueError(
                f"{label}={spec} does not evenly divide extents {tuple(shp)} "
                f"over the mesh; brick layouts need divisible shards"
            )
    user_in = NamedSharding(mesh, in_spec) if in_spec is not None else canonical_in
    user_out = NamedSharding(mesh, out_spec) if out_spec is not None else canonical_out

    # User specs were just validated; only the canonical fallbacks (uneven
    # extents the inner plan pads/crops itself) can fail to divide here.
    canon_in_fits = _spec_divides(mesh, canonical_in.spec, in_shape)
    jit_kw: dict = {"donate_argnums": 0} if donate else {}
    if in_spec is not None or canon_in_fits:
        jit_kw["in_shardings"] = user_in
    out_fits = out_spec is not None or _spec_divides(
        mesh, canonical_out.spec, out_shape
    )
    if out_fits:
        jit_kw["out_shardings"] = user_out

    @functools.partial(jax.jit, **jit_kw)
    def wrapped(x):
        if canon_in_fits:
            x = jax.lax.with_sharding_constraint(x, canonical_in)
        y = fn(x)
        if out_fits:
            y = jax.lax.with_sharding_constraint(y, user_out)
        return y

    return wrapped, user_in, user_out


def prepare_plan(planner: Callable, shape, mesh, executor: str | None,
                 options: PlanOptions | None, knobs: dict, plan_kw: dict,
                 *, forward: bool = True, transform: str = "c2c",
                 op: str | None = None, tuned: Callable | None = None,
                 layouts: tuple = (None, None)):
    """The planners' shared preamble: resolve the options
    (:func:`.plan_logic.resolve_options`), hand a tuned or
    ``executor="auto"`` request to its measured selector, build the
    logic plan, and resolve the executor
    (:func:`.plan_logic.resolve_executor`).

    ``planner`` is the public planner itself, re-entered by the
    selectors with ``options=`` and ``plan_kw``; ``knobs`` are its
    option keywords. ``transform`` is ``"c2c"`` or ``"r2c"``, ``op`` an
    operator plan's op name (set on the logic plan), ``tuned`` the
    operator planners' own tuned tier (``tuned(opts, plan_kw)``; the
    transforms use :func:`.tuner.tuned_plan`), and ``layouts`` the
    ``(in_spec, out_spec)`` the logic plan may absorb. Returns the
    selectors' finished plan, else ``(lp, label)``: ``lp.options``
    names the executor the plan reports, ``label`` the one its builders
    run."""
    import dataclasses

    opts, named = resolve_options(executor, options, **knobs)
    if resolve_tune_mode(opts.tune) != "off":
        if tuned is None:
            from . import tuner

            return tuner.tuned_plan(transform, shape, mesh, opts, plan_kw)
        return tuned(opts, plan_kw)
    if opts.executor == "auto":
        return _auto_plan(functools.partial(planner, shape, mesh), opts,
                          **plan_kw)
    if transform == "r2c" and opts.algorithm == "hierarchical":
        raise ValueError(
            "hierarchical transport supports the c2c chains; r2c/c2r "
            "plans run the flat transports")
    lp = logic_plan3d(shape, mesh, opts, forward=forward,
                      in_spec=layouts[0], out_spec=layouts[1],
                      batch=plan_kw.get("batch"))
    if layouts != (None, None) and lp.mesh is None:
        raise ValueError("in_spec/out_spec require a mesh")
    if op is not None:
        lp = dataclasses.replace(lp, op=op)
    name, label = resolve_executor(
        opts.executor if named else None, lp, transform=transform,
        dtype=_default_cdtype(plan_kw.get("dtype")))
    if name != lp.options.executor:
        lp = dataclasses.replace(
            lp, options=dataclasses.replace(lp.options, executor=name))
    return lp, label


def plan_dft_c2c_3d(
    shape: Sequence[int],
    mesh: Mesh | int | None = None,
    *,
    direction: int = FORWARD,
    decomposition: str | None = None,
    executor: str | None = None,
    dtype: Any = None,
    donate: bool = False,
    algorithm: str = "alltoall",
    overlap_chunks: int | str | None = None,
    tune: str | None = None,
    wire_dtype: str | None = None,
    max_roundtrip_err: float | None = None,
    mm_precision: str | None = None,
    mm_complex: str | None = None,
    fuse: bool | str | None = None,
    options: PlanOptions | None = None,
    in_spec: P | None = None,
    out_spec: P | None = None,
    batch: int | None = None,
) -> Plan3D:
    """Create a distributed 3D complex-to-complex FFT plan.

    ``executor=None`` (the default) is ``DFFT_EXECUTOR`` when set, else,
    on the TPU, ``pallas`` for an unbatched complex64 plan on one device
    or a slab whose local blocks the plane passes take (run at HIGHEST),
    and ``xla`` elsewhere, pencils included
    (:func:`.plan_logic.resolve_executor`); ``plan.executor`` names the
    one resolved.

    ``mesh`` may be a :class:`jax.sharding.Mesh` (1D -> slab, 2D -> pencil),
    an int (decomposition chosen by :func:`~.plan_logic.choose_decomposition`
    and the mesh built to fit), or None (single device). ``direction`` uses
    the FFTW sign convention (-1 forward).

    cf. ``fft_mpi_plan_dft_c2c_3d`` (``fft_mpi_3d_api.cpp:41``), which also
    fixes direction at plan time and builds one plan per direction.

    ``in_spec`` / ``out_spec`` accept any mesh-expressible brick layout for
    the plan's input/output. Slab/pencil-shaped layouts are *absorbed* into
    the stage chain itself (heFFTe's reshape minimization,
    ``heffte_plan_logic.cpp:162-245,265-408``); other layouts get an edge
    reshard (:func:`_wrap_user_layout`). With both None the canonical chain
    runs (X-slabs <-> Y-slabs, z-pencils <-> x-pencils). NOTE: when only
    ``in_spec`` is given, the output layout follows the re-axed chain's
    natural endpoint, which may differ from canonical — read
    ``plan.out_sharding`` (pass ``out_spec`` to pin a specific layout).

    ``donate=True`` makes execution consume its input buffer (the analog of
    the reference's bufferDev ping-pong, halving HBM footprint for big
    grids) at the cost of repeat-execution on the same array; the default
    keeps FFTW-style repeatable-execute semantics.

    ``overlap_chunks`` enables the pipelined exchange/compute overlap
    (int K, ``"auto"``, or None -> ``DFFT_OVERLAP`` env; see
    :class:`~.plan_logic.PlanOptions`). K=1 is today's monolithic chain.

    ``tune`` selects measured planning (:mod:`.tuner`): ``"measure"``
    runs the pruned multi-axis tournament (decomposition x transport x
    executor x overlap K) on a wisdom miss and records the winner;
    ``"wisdom"`` only consults the persistent store and falls back to
    these static heuristics on a miss; default ``"off"`` (or the
    ``DFFT_TUNE`` env var) plans exactly as before.

    ``batch=B`` coalesces B independent same-shape transforms into ONE
    device program: I/O is ``[B, N0, N1, N2]`` (``plan.in_shape``), the
    chain runs batched FFT stages, and every exchange is one shared
    collective with the batch riding as a bystander dim — B transforms
    pay one collective latency, the whole throughput play of the serving
    tier (:mod:`.serving`). ``batch=1``/``None`` is the unbatched plan
    (byte-identical HLO). Batched plans are plan-cache- and wisdom-keyed
    by B; ``in_spec``/``out_spec`` layouts take the unbatched path only.

    ``wire_dtype`` compresses the t2 exchange payload on the wire with
    a registered codec (``"bf16"``: component pairs, half the c64 wire
    bytes; ``"int8"``: block-scaled component planes with a tiny f32
    scale sidecar, ~quarter the c64 wire bytes — each at a bounded,
    measured precision cost; ``None`` defers to ``DFFT_WIRE_DTYPE``,
    unset = exact wire, byte-identical HLO). ``algorithm="hierarchical"``
    runs the two-leg
    ICI/DCN transport over a hybrid 2D (dcn x ici) mesh
    (:func:`~.parallel.exchange.hierarchical_all_to_all`).
    ``max_roundtrip_err`` declares the plan's error budget — the gate
    under which the tuner may pick (or replay) compressed and
    reduced-precision candidates (the errors compose; one budget
    governs the sum).

    ``mm_precision="bf16"|"f32"|"highest"`` scopes the matmul-family
    executors' MXU contraction tier to THIS plan (the executor label
    becomes ``matmul:bf16`` etc. — a distinct, plan-cache-keyed
    executor; two tiers coexist in one process). ``None`` defers to the
    ``DFFT_MM_PRECISION`` env default at trace time, byte-identical to
    today. ``mm_complex="gauss"`` likewise scopes the 3-real-matmul
    complex product (env default ``DFFT_MM_COMPLEX``).

    ``fuse=True`` requests the Pallas fusion tier (executor label
    ``pallas:fuse`` — the same choice spelled as a kwarg): adjacent
    stage/codec pairs around each compressed exchange collapse into one
    shape-specialized mega-kernel when the stage-graph gate passes
    (``wire_dtype`` set, ``overlap_chunks=1``); ineligible graphs and
    shapes fall back to the unfused chain, counted and explain-visible,
    never an error. ``None`` defers to ``DFFT_FUSE`` (unset = off,
    byte-identical HLO). See docs/TUNING.md, "Pallas fusion tier".
    """
    shape, forward = _check_direction(shape, direction)
    batch = _norm_batch(batch)
    if batch is not None and (in_spec is not None or out_spec is not None):
        raise ValueError("batched plans take the canonical chain layouts; "
                         "in_spec/out_spec require batch=None (or 1)")
    resolved = prepare_plan(
        plan_dft_c2c_3d, shape, mesh, executor, options,
        dict(decomposition=decomposition, algorithm=algorithm,
             donate=donate, overlap_chunks=overlap_chunks, tune=tune,
             wire_dtype=wire_dtype, max_roundtrip_err=max_roundtrip_err,
             mm_precision=mm_precision, mm_complex=mm_complex, fuse=fuse),
        dict(direction=direction, dtype=dtype, in_spec=in_spec,
             out_spec=out_spec, batch=batch),
        forward=forward, layouts=(in_spec, out_spec))
    if isinstance(resolved, Plan3D):
        return resolved
    lp, label = resolved
    opts = lp.options
    dtype = _default_cdtype(dtype)
    world = world_box(shape)

    if lp.decomposition == "single":
        ex = get_executor(label)
        fft_axes = (0, 1, 2) if batch is None else (1, 2, 3)
        fn = jax.jit(lambda x: ex(x, fft_axes, forward))
        spec = None
    elif lp.decomposition == "slab":
        fn, spec = build_slab_fft3d(
            lp.mesh, shape, axis_name=_slab_axis_name(lp.mesh),
            executor=label, forward=forward, donate=opts.donate,
            algorithm=opts.algorithm,
            in_axis=lp.slab_axes[0], out_axis=lp.slab_axes[1],
            overlap_chunks=opts.overlap_chunks, batch=batch,
            wire_dtype=opts.wire_dtype,
        )
    else:
        row, col = lp.mesh.axis_names[:2]
        fn, spec = build_pencil_fft3d(
            lp.mesh, shape, row_axis=row, col_axis=col,
            executor=label, forward=forward, donate=opts.donate,
            algorithm=opts.algorithm,
            perm=lp.pencil_perm, order=lp.pencil_order,
            overlap_chunks=opts.overlap_chunks, batch=batch,
            wire_dtype=opts.wire_dtype,
        )

    in_sh, out_sh = _shardings(lp, spec, batch)
    in_boxes, out_boxes = _boxes(lp, world, world)
    # Edge reshards only for layouts the chain could not absorb — absorbed
    # layouts ARE the chain's own endpoints (heFFTe's reshape minimization,
    # heffte_plan_logic.cpp:162-245,265-408).
    wrap_in = in_spec if (in_spec is not None and not lp.in_absorbed) else None
    wrap_out = out_spec if (out_spec is not None and not lp.out_absorbed) else None
    if wrap_in is not None or wrap_out is not None:
        fn, in_sh, out_sh = _wrap_user_layout(
            fn, lp.mesh, in_sh, out_sh, wrap_in, wrap_out, opts.donate,
            shape, shape,
        )
    # Absorbed layouts ARE the chain endpoints, so the chain's own (ceil-
    # split, possibly uneven) boxes already describe them; _layout_boxes is
    # only for wrapped layouts (validated divisible by _wrap_user_layout).
    if wrap_in is not None:
        in_boxes = _layout_boxes(lp.mesh, in_spec, world)
    if wrap_out is not None:
        out_boxes = _layout_boxes(lp.mesh, out_spec, world)
    io_shape = shape if batch is None else (batch,) + shape
    return Plan3D(
        shape=shape, direction=direction, dtype=dtype,
        decomposition=lp.decomposition, executor=opts.executor, mesh=lp.mesh,
        fn=fn, spec=spec, in_sharding=in_sh, out_sharding=out_sh,
        in_boxes=in_boxes, out_boxes=out_boxes,
        in_shape=io_shape, out_shape=io_shape, batch=batch,
        options=lp.options, logic=lp,
    )


#: Executor candidates tried by ``executor="auto"`` (override with the
#: DFFT_AUTO_EXECUTORS env var, comma-separated).
_AUTO_CANDIDATES = ("xla", "xla_minor", "pallas", "matmul")


def _autotune(make_plan: Callable[[str], Plan3D]) -> Plan3D:
    """Plan every candidate executor, time one execution of each, keep the
    fastest — the reference's plan-and-pick discipline (``setFFTPlans``
    builds hipfft, rocfft, AND templateFFT plans side by side and selects
    one, ``fft_mpi_3d_api.cpp:318-429``). Candidates that fail to compile
    or execute are skipped, never fatal.

    Timing uses a zero-filled input (FFT cost is data-independent) and
    pays one compile per candidate at plan time — the same cost profile
    as the reference's plan-time hipRTC compilation of every backend.
    The tournament itself (multi-host candidate-set agreement, lockstep
    timing, winner decided from the allgathered time matrix so a
    candidate that failed timing on any process can never win) is
    :func:`.tuner.measured_select` — the same engine behind the
    multi-axis ``tune="measure"`` tournament; timing budget via
    ``DFFT_TUNE_ITERS`` (:func:`.tuner.tune_budget`).
    """
    import os

    from .tuner import measured_select, tune_budget
    from .utils.timing import time_fn_amortized

    names = [e.strip() for e in os.environ.get(
        "DFFT_AUTO_EXECUTORS", ",".join(_AUTO_CANDIDATES)).split(",")
        if e.strip() and e.strip() != "auto"]  # 'auto' itself would recurse
    iters, repeats = tune_budget()

    def measure(plan: Plan3D) -> float:
        x = alloc_local(plan)
        t, _ = time_fn_amortized(plan.fn, x, iters=iters, repeats=repeats)
        return t

    best, plans, _ = measured_select(
        names, make_plan, measure, what="auto executor candidate")
    return plans[best]


def _auto_plan(plan_fn: Callable, opts: PlanOptions, **kw) -> Plan3D:
    """Shared ``executor="auto"`` dispatch for every plan family: run the
    tournament donation-free (a donated buffer cannot be re-executed for
    timing), then rebuild the winner with the caller's donation flag."""
    import dataclasses

    def mk(ex: str, don: bool) -> Plan3D:
        o = dataclasses.replace(opts, executor=ex, donate=don)
        return plan_fn(options=o, **kw)

    best = _autotune(lambda ex: mk(ex, False))
    return mk(best.executor, opts.donate) if opts.donate else best


def _even_fallback_spec(mesh: Mesh, pref: P, shape) -> P:
    """``pref`` if it divides ``shape`` evenly over the mesh, else the first
    mesh-expressible layout (using every mesh axis) that does."""
    import itertools

    if _spec_divides(mesh, pref, shape):
        return pref
    names = list(mesh.axis_names)
    cands = []
    if len(names) == 1:
        for d in range(3):
            e: list = [None, None, None]
            e[d] = names[0]
            cands.append(P(*e))
    else:
        for da, db in itertools.permutations(range(3), 2):
            e = [None, None, None]
            e[da], e[db] = names[0], names[1]
            cands.append(P(*e))
        for d in range(3):  # both axes merged onto one dim
            e = [None, None, None]
            e[d] = tuple(names)
            cands.append(P(*e))
    for c in cands:
        if _spec_divides(mesh, c, shape):
            return c
    raise ValueError(
        f"no mesh-expressible layout of {tuple(shape)} divides evenly over "
        f"mesh axes {dict(mesh.shape)}; brick plans need at least one even "
        f"intermediate layout"
    )


def plan_brick_dft_c2c_3d(
    shape: Sequence[int],
    mesh: Mesh | int | None,
    in_boxes: Sequence[Box3],
    out_boxes: Sequence[Box3],
    *,
    direction: int = FORWARD,
    decomposition: str | None = None,
    executor: str = "xla",
    dtype: Any = None,
    donate: bool = False,
    algorithm: str = "alltoall",
    options: PlanOptions | None = None,
) -> Plan3D:
    """3D C2C plan with *arbitrary* per-device input/output boxes.

    The full heFFTe brick capability (``fft3d(inbox, outbox, comm)``,
    ``heffte_fft3d.h:105-115``): ``in_boxes``/``out_boxes`` are any
    non-overlapping decompositions of the world — uneven, non-grid,
    axis-swapped — one ``Box3`` per device in ``mesh.devices.flat`` order.
    The plan brackets the canonical stage chain with the overlap-map ring
    reshapes of :mod:`.parallel.bricks` (the ``reshape3d_alltoallv``
    analog, ``src/heffte_reshape3d.cpp:375``).

    I/O travels as *brick stacks*: ``[P, *pad]`` arrays sharded one brick
    per device (see :func:`~.parallel.bricks.scatter_bricks` /
    ``gather_bricks``); ``plan.in_shape``/``plan.out_shape`` give the stack
    shapes. Boxes may declare per-rank storage axis orders
    (``Box3.order`` — heFFTe ``box3d::order``/``use_reorder``,
    ``heffte_geometry.h:67-92``): each brick then travels in its declared
    order and the plan's order edge canonicalizes/restores it on device.
    The canonical chain endpoints must divide the world evenly over
    the mesh (pick a mesh whose axis sizes divide the extents); the user
    boxes themselves carry no such restriction.
    """
    shape, _ = _check_direction(shape, direction)
    dtype = _default_cdtype(dtype)
    inner = plan_dft_c2c_3d(
        shape, mesh, direction=direction, decomposition=decomposition,
        executor=executor, dtype=dtype, donate=donate, algorithm=algorithm,
        options=options,
    )
    return _wrap_brick_io(inner, in_boxes, out_boxes)


def plan_brick_dft_r2c_3d(
    shape: Sequence[int],
    mesh: Mesh | int | None,
    in_boxes: Sequence[Box3],
    out_boxes: Sequence[Box3],
    *,
    direction: int = FORWARD,
    r2c_axis: int = 2,
    decomposition: str | None = None,
    executor: str = "xla",
    dtype: Any = None,
    donate: bool = False,
    algorithm: str = "alltoall",
    options: PlanOptions | None = None,
) -> Plan3D:
    """Real<->complex 3D plan with arbitrary per-device boxes — the brick
    tier of heFFTe's ``fft3d_r2c`` (``heffte_fft3d_r2c.h``; r2c box shrink
    ``box3d::r2c``, ``heffte_geometry.h:94``).

    Forward: ``in_boxes`` partition the real-space world ``shape``,
    ``out_boxes`` the world shrunk to ``N//2+1`` along ``r2c_axis``
    (heFFTe ``r2c_direction``, default 2); backward swaps the roles.
    See :func:`plan_brick_dft_c2c_3d` for the stack I/O convention."""
    shape, _ = _check_direction(shape, direction)
    inner = plan_dft_r2c_3d(
        shape, mesh, direction=direction, r2c_axis=r2c_axis,
        decomposition=decomposition, executor=executor, dtype=dtype,
        donate=donate, algorithm=algorithm, options=options,
    )
    return _wrap_brick_io(inner, in_boxes, out_boxes)


def plan_brick_dft_c2r_3d(shape, mesh, in_boxes, out_boxes, **kw) -> Plan3D:
    """Convenience alias: the inverse of :func:`plan_brick_dft_r2c_3d`."""
    kw.setdefault("direction", BACKWARD)
    return plan_brick_dft_r2c_3d(shape, mesh, in_boxes, out_boxes, **kw)


def _build_brick_edges(m, in_boxes, out_boxes, in_world, out_world,
                       in_spec, out_spec, algorithm: str):
    """Shared edge construction for every brick planner (c64 and dd):
    validate world coverage, target the nearest *even* mesh layout, and
    build the (edge_in, edge_out) stack<->canonical callables plus their
    BrickSpec accounting pair.

    The ring lands an even mesh layout; when the chain endpoint itself
    is uneven (ceil-split), the chain's own sharding constraints move
    data the rest of the way (one extra XLA reshard — the same
    prepend/append reshape heFFTe's planner emits for non-matching
    layouts, heffte_plan_logic.cpp:162-245). ``algorithm="alltoallv"``
    selects the exact-count ragged transport for the brick edges (wire
    == payload); other PlanOptions algorithms keep the padded ppermute
    ring. Per-box storage orders (heFFTe box3d::order / use_reorder)
    are honored: the caller's bricks arrive/leave in their declared
    axis order; the order edge canonicalizes before the ring and
    permutes back after."""
    from .parallel.bricks import (
        plan_bricks_to_spec, plan_spec_to_bricks, reorder_stack,
    )

    _check_brick_algorithm(algorithm)
    _check_world_coverage(in_boxes, out_boxes, in_world, out_world)
    in_target = _even_fallback_spec(m, in_spec, in_world)
    out_target = _even_fallback_spec(m, out_spec, out_world)
    brick_alg = "a2av" if algorithm == "alltoallv" else "ring"
    to_canon, in_bspec = plan_bricks_to_spec(m, in_boxes, in_target,
                                             algorithm=brick_alg)
    from_canon, out_bspec = plan_spec_to_bricks(m, out_target, out_boxes,
                                                algorithm=brick_alg)
    in_reorder = reorder_stack(m, in_boxes, to_canonical=True)
    out_reorder = reorder_stack(m, out_boxes, to_canonical=False)

    def edge_in(stack):
        if in_reorder is not None:
            stack = in_reorder(stack)
        return to_canon(stack)

    def edge_out(y):
        y = from_canon(y)
        return out_reorder(y) if out_reorder is not None else y

    return (edge_in, edge_out, (in_bspec, out_bspec),
            (in_reorder, to_canon, from_canon, out_reorder))


def _check_brick_algorithm(algorithm: str) -> None:
    if algorithm not in ("alltoall", "alltoallv", "ppermute"):
        raise ValueError(
            f"unknown algorithm {algorithm!r} for a brick plan; "
            f"expected alltoall|alltoallv|ppermute")


def _check_world_coverage(in_boxes, out_boxes, in_world, out_world):
    """Both box lists must tile their side's world (shared by the
    distributed and single-device brick edge builders)."""
    from .geometry import find_world

    for label, boxes, want in (("in_boxes", in_boxes, in_world),
                               ("out_boxes", out_boxes, out_world)):
        got = find_world(boxes).shape
        if got != tuple(want):
            raise ValueError(
                f"{label} cover a {got} world; this plan's side is "
                f"{tuple(want)}")


def _single_brick_edges(in_boxes, out_boxes, in_world, out_world):
    """Degenerate (1-device) brick edges: the world is ONE brick per side,
    possibly order-permuted — heFFTe brick plans run fine on a single rank
    (``fft3d(inbox, outbox, comm)`` with a self communicator). No
    collectives; the edge is crop + storage-order permutation only."""
    from .parallel.bricks import _inv_perm

    for label, boxes in (("in_boxes", in_boxes), ("out_boxes", out_boxes)):
        if len(boxes) != 1:
            raise ValueError(
                f"single-device brick plans take exactly one box per side; "
                f"{label} has {len(boxes)}")
    _check_world_coverage(in_boxes, out_boxes, in_world, out_world)
    bi, bo = in_boxes[0], out_boxes[0]

    def edge_in(stack):
        x = stack[0]
        if bi.order != (0, 1, 2):
            x = jnp.transpose(x, _inv_perm(bi.order))
        return x

    def edge_out(y):
        if bo.order != (0, 1, 2):
            y = jnp.transpose(y, bo.order)
        return y[None]

    return edge_in, edge_out


def _wrap_brick_io_single(
    inner: Plan3D, in_boxes: Sequence[Box3], out_boxes: Sequence[Box3]
) -> Plan3D:
    """Single-device tier of :func:`_wrap_brick_io` (inner plan has no
    mesh): same ``[1, *pad]`` stack I/O convention as the distributed
    tier, so callers are decomposition-agnostic."""
    from .parallel.bricks import stack_pad_for
    from .stagegraph import BrickEdgeGraph, compile_brick_io

    edge_in, edge_out = _single_brick_edges(
        in_boxes, out_boxes, inner.in_shape, inner.out_shape)
    fn = compile_brick_io(
        BrickEdgeGraph(edge_in=(None, edge_in), edge_out=(edge_out, None),
                       donate=inner.options.donate,
                       meta={"decomposition": inner.decomposition}),
        inner.fn)

    return Plan3D(
        shape=inner.shape, direction=inner.direction, dtype=inner.dtype,
        decomposition=inner.decomposition, executor=inner.executor,
        mesh=None, fn=fn, spec=inner.spec, in_sharding=None,
        out_sharding=None,
        in_boxes=list(in_boxes), out_boxes=list(out_boxes),
        in_shape=(1,) + stack_pad_for(in_boxes),
        out_shape=(1,) + stack_pad_for(out_boxes),
        in_dtype=inner.in_dtype, out_dtype=inner.out_dtype,
        real=inner.real, r2c_axis=inner.r2c_axis,
        options=inner.options, logic=inner.logic,
    )


def _wrap_brick_io(
    inner: Plan3D, in_boxes: Sequence[Box3], out_boxes: Sequence[Box3]
) -> Plan3D:
    """Bracket a canonical-chain plan with the overlap-map ring reshapes
    (shared by the c2c and r2c brick planners). The wrapper program is
    declared as a :class:`..stagegraph.BrickEdgeGraph` and compiled by
    :func:`..stagegraph.compile_brick_io` — the PR 18 migration of the
    named IR remainder (byte-identical HLO, pinned)."""
    from .parallel.bricks import stack_pad_for
    from .stagegraph import BrickEdgeGraph, compile_brick_io

    if inner.mesh is None or inner.in_sharding is None:
        return _wrap_brick_io_single(inner, in_boxes, out_boxes)
    m = inner.mesh
    _, _, edges, pieces = _build_brick_edges(
        m, in_boxes, out_boxes, inner.in_shape, inner.out_shape,
        inner.in_sharding.spec, inner.out_sharding.spec,
        inner.options.algorithm)
    in_reorder, to_canon, from_canon, out_reorder = pieces
    fn = compile_brick_io(
        BrickEdgeGraph(edge_in=(in_reorder, to_canon),
                       edge_out=(from_canon, out_reorder),
                       donate=inner.options.donate, specs=edges,
                       meta={"decomposition": inner.decomposition,
                             "algorithm": inner.options.algorithm}),
        inner.fn)

    p = len(in_boxes)
    names = tuple(m.axis_names)
    stack_sh = NamedSharding(m, P(names, None, None, None))
    return Plan3D(
        shape=inner.shape, direction=inner.direction, dtype=inner.dtype,
        decomposition=inner.decomposition, executor=inner.executor, mesh=m,
        fn=fn, spec=inner.spec, in_sharding=stack_sh, out_sharding=stack_sh,
        in_boxes=list(in_boxes), out_boxes=list(out_boxes),
        in_shape=(p,) + stack_pad_for(in_boxes),
        out_shape=(p,) + stack_pad_for(out_boxes),
        in_dtype=inner.in_dtype, out_dtype=inner.out_dtype,
        real=inner.real, r2c_axis=inner.r2c_axis,
        options=inner.options, logic=inner.logic,
        brick_edges=edges,
    )


def plan_dft_r2c_3d(
    shape: Sequence[int],
    mesh: Mesh | int | None = None,
    *,
    direction: int = FORWARD,
    decomposition: str | None = None,
    executor: str | None = None,
    dtype: Any = None,
    donate: bool = False,
    algorithm: str = "alltoall",
    overlap_chunks: int | str | None = None,
    tune: str | None = None,
    wire_dtype: str | None = None,
    max_roundtrip_err: float | None = None,
    mm_precision: str | None = None,
    mm_complex: str | None = None,
    fuse: bool | str | None = None,
    options: PlanOptions | None = None,
    in_spec: P | None = None,
    out_spec: P | None = None,
    r2c_axis: int = 2,
    batch: int | None = None,
) -> Plan3D:
    """Create a distributed real-to-complex (forward) / complex-to-real
    (backward) 3D FFT plan — heFFTe ``fft3d_r2c`` parity
    (``heffte_fft3d_r2c.h``; r2c box shrink ``heffte_geometry.h:94``).

    ``shape`` is the *real-space* world shape. The complex side is shrunk
    along ``r2c_axis`` (default 2) to ``N//2+1`` — heFFTe's
    ``r2c_direction`` ctor argument (``heffte_fft3d_r2c.h:71-84``).
    Forward input is real; backward output is real with numpy 1/N
    scaling. Non-default ``r2c_axis`` runs the canonical chain on a
    transposed view (one extra device transpose per edge; the chain's
    collectives are unchanged). ``donate`` is accepted for API symmetry
    but is a no-op on r2c/c2r plans: real and half-spectrum buffers
    differ in dtype and size, so XLA can never alias them.

    ``batch=B`` coalesces B same-shape transforms into one device program
    with one shared exchange per batch (the :func:`plan_dft_c2c_3d`
    convention); canonical ``r2c_axis=2`` chains only.

    ``mm_precision`` / ``mm_complex`` scope the matmul-family executor's
    accuracy tier to this plan (the :func:`plan_dft_c2c_3d` convention).
    """
    batch = _norm_batch(batch)
    if r2c_axis != 2:
        if batch is not None:
            raise ValueError(
                "batched r2c plans run the canonical r2c_axis=2 chain; "
                "transpose the batch's world instead of passing r2c_axis")
        return _r2c_axis_wrapped(
            shape, mesh, r2c_axis, direction=direction,
            decomposition=decomposition, executor=executor, dtype=dtype,
            donate=donate, algorithm=algorithm,
            overlap_chunks=overlap_chunks, tune=tune,
            wire_dtype=wire_dtype, max_roundtrip_err=max_roundtrip_err,
            mm_precision=mm_precision, mm_complex=mm_complex, fuse=fuse,
            options=options, in_spec=in_spec, out_spec=out_spec,
        )
    if batch is not None and (in_spec is not None or out_spec is not None):
        raise ValueError("batched plans take the canonical chain layouts; "
                         "in_spec/out_spec require batch=None (or 1)")
    shape, forward = _check_direction(shape, direction)
    resolved = prepare_plan(
        plan_dft_r2c_3d, shape, mesh, executor, options,
        dict(decomposition=decomposition, algorithm=algorithm,
             donate=donate, overlap_chunks=overlap_chunks, tune=tune,
             wire_dtype=wire_dtype, max_roundtrip_err=max_roundtrip_err,
             mm_precision=mm_precision, mm_complex=mm_complex, fuse=fuse),
        dict(direction=direction, dtype=dtype, in_spec=in_spec,
             out_spec=out_spec, batch=batch),
        forward=forward, transform="r2c")
    if isinstance(resolved, Plan3D):
        return resolved
    lp, label = resolved
    if lp.options.donate:
        # r2c/c2r buffers can never alias (real world vs complex
        # half-spectrum differ in dtype and size), so donation would
        # only emit unusable-donation warnings per execute and skew the
        # plan_info memory estimate: accepted for API symmetry,
        # documented no-op (same policy as the dd tier).
        import dataclasses

        lp = dataclasses.replace(
            lp, options=dataclasses.replace(lp.options, donate=False))
    opts = lp.options
    dtype = _default_cdtype(dtype)
    if not jnp.issubdtype(dtype, jnp.complexfloating):
        raise ValueError(
            f"r2c plans take the complex working dtype, got {dtype}; the real "
            "side is derived from it"
        )
    rdtype = jnp.float64 if dtype == jnp.complex128 else jnp.float32
    n0, n1, n2 = shape
    cshape = (n0, n1, n2 // 2 + 1)
    # r2c chains keep the canonical axis assignment (the real axis must be
    # axis 2, device-local on the real side); user layouts go through edge
    # reshards below rather than chain re-axing.
    world, cworld = world_box(shape), world_box(cshape)
    bo = 0 if batch is None else 1

    if lp.decomposition == "single":
        ex = get_executor(label)
        r2c, c2r = get_r2c(label), get_c2r(label)
        if forward:
            fn = jax.jit(lambda x: ex(r2c(x, 2 + bo), (bo, 1 + bo), True))
        else:
            fn = jax.jit(
                lambda y: c2r(ex(y, (bo, 1 + bo), False), n2, 2 + bo))
        spec = None
    elif lp.decomposition == "slab":
        fn, spec = build_slab_rfft3d(
            lp.mesh, shape, axis_name=lp.mesh.axis_names[0],
            executor=label, forward=forward, donate=opts.donate,
            algorithm=opts.algorithm,
            overlap_chunks=opts.overlap_chunks, batch=batch,
            wire_dtype=opts.wire_dtype,
        )
    else:
        row, col = lp.mesh.axis_names[:2]
        fn, spec = build_pencil_rfft3d(
            lp.mesh, shape, row_axis=row, col_axis=col,
            executor=label, forward=forward, donate=opts.donate,
            algorithm=opts.algorithm,
            overlap_chunks=opts.overlap_chunks, batch=batch,
            wire_dtype=opts.wire_dtype,
        )

    if (in_spec is not None or out_spec is not None) and lp.mesh is None:
        raise ValueError("in_spec/out_spec require a mesh")
    in_sh, out_sh = _shardings(lp, spec, batch)
    in_world = world if forward else cworld
    out_world = cworld if forward else world
    in_boxes, out_boxes = _boxes(lp, in_world, out_world)
    if in_spec is not None or out_spec is not None:
        fn, in_sh, out_sh = _wrap_user_layout(
            fn, lp.mesh, in_sh, out_sh, in_spec, out_spec, opts.donate,
            shape if forward else cshape, cshape if forward else shape,
        )
        if in_spec is not None:
            in_boxes = _layout_boxes(lp.mesh, in_spec, in_world)
        if out_spec is not None:
            out_boxes = _layout_boxes(lp.mesh, out_spec, out_world)
    bpfx = () if batch is None else (batch,)
    return Plan3D(
        shape=shape, direction=direction, dtype=dtype,
        decomposition=lp.decomposition, executor=opts.executor, mesh=lp.mesh,
        fn=fn, spec=spec, in_sharding=in_sh, out_sharding=out_sh,
        in_boxes=in_boxes, out_boxes=out_boxes,
        in_shape=bpfx + (shape if forward else cshape),
        out_shape=bpfx + (cshape if forward else shape),
        in_dtype=rdtype if forward else dtype,
        out_dtype=dtype if forward else rdtype,
        real=True, batch=batch, options=lp.options, logic=lp,
    )


def plan_dft_c2r_3d(shape, mesh=None, **kw) -> Plan3D:
    """Convenience alias: the inverse of :func:`plan_dft_r2c_3d` (complex
    half-spectrum in, real out; heFFTe ``fft3d_r2c::backward``)."""
    kw.setdefault("direction", BACKWARD)
    return plan_dft_r2c_3d(shape, mesh, **kw)


def _swap_perm(axis: int) -> list[int]:
    """The self-inverse permutation swapping ``axis`` with 2 (one perm
    serves both directions of every transposed-view wrapper)."""
    perm = [0, 1, 2]
    perm[axis], perm[2] = perm[2], perm[axis]
    return perm


def _permute_spec3(s, perm):
    """Permute a (possibly short) 3-dim PartitionSpec by ``perm``."""
    if s is None:
        return None
    ent = tuple(s) + (None,) * (3 - len(tuple(s)))
    return P(*(ent[p] for p in perm))


def _permute_sharding3(sh, perm):
    return (None if sh is None
            else NamedSharding(sh.mesh, _permute_spec3(sh.spec, perm)))


def _chain_convention_note(e: Exception, axis: int) -> ValueError:
    return ValueError(
        f"{e} [note: r2c_axis={axis} plans run on a transposed view — "
        f"specs and extents in this message are in the chain "
        f"convention (axes {axis} and 2 swapped)]")


def _r2c_axis_wrapped(shape, mesh, axis: int, *, direction, decomposition,
                      executor, dtype, donate, algorithm, options, in_spec,
                      out_spec, overlap_chunks=None, tune=None,
                      wire_dtype=None, max_roundtrip_err=None,
                      mm_precision=None, mm_complex=None,
                      fuse=None) -> Plan3D:
    """r2c/c2r with the halved axis != 2 (heFFTe ``r2c_direction`` 0/1):
    the canonical chain (real axis = 2) runs on a transposed view.
    Caller-facing metadata — shapes, shardings, boxes — is permuted back
    to the caller's axis convention; ``spec``/``logic`` keep the inner
    chain's (transposed) convention, which ``plan_info`` labels. The
    swap permutation is its own inverse, so one ``perm`` serves both
    directions."""
    if axis not in (0, 1):
        raise ValueError(f"r2c_axis must be 0, 1, or 2; got {axis}")
    shape, forward = _check_direction(shape, direction)
    perm = _swap_perm(axis)
    pshape = tuple(shape[p] for p in perm)

    try:
        inner = plan_dft_r2c_3d(
            pshape, mesh, direction=direction, decomposition=decomposition,
            executor=executor, dtype=dtype, donate=donate,
            algorithm=algorithm, overlap_chunks=overlap_chunks, tune=tune,
            wire_dtype=wire_dtype, max_roundtrip_err=max_roundtrip_err,
            mm_precision=mm_precision, mm_complex=mm_complex, fuse=fuse,
            options=options,
            in_spec=_permute_spec3(in_spec, perm),
            out_spec=_permute_spec3(out_spec, perm),
        )
    except ValueError as e:
        raise _chain_convention_note(e, axis) from e

    inner_fn = inner.fn
    fn = jax.jit(
        lambda x: jnp.transpose(inner_fn(jnp.transpose(x, perm)), perm),
        donate_argnums=(0,) if inner.options.donate else (),
    )

    def permute_shape(s):
        return tuple(s[p] for p in perm)

    def permute_boxes(boxes):
        return [Box3(tuple(b.low[p] for p in perm),
                     tuple(b.high[p] for p in perm)) for b in boxes]

    return Plan3D(
        shape=shape, direction=direction, dtype=inner.dtype,
        decomposition=inner.decomposition, executor=inner.executor,
        mesh=inner.mesh, fn=fn, spec=inner.spec,
        in_sharding=_permute_sharding3(inner.in_sharding, perm),
        out_sharding=_permute_sharding3(inner.out_sharding, perm),
        in_boxes=permute_boxes(inner.in_boxes),
        out_boxes=permute_boxes(inner.out_boxes),
        in_shape=permute_shape(inner.in_shape),
        out_shape=permute_shape(inner.out_shape),
        in_dtype=inner.in_dtype, out_dtype=inner.out_dtype,
        real=True, r2c_axis=axis, options=inner.options, logic=inner.logic,
    )


@dataclass
class DDPlan3D:
    """A compiled 3D FFT plan at the emulated-f64 (double-double) tier.

    Same plan-owns-everything discipline as :class:`Plan3D`, but I/O is a
    (hi, lo) two-float pair — complex64 for c2c, float32 on the real side
    of r2c/c2r plans — carrying ~49 significand bits (the reference's
    f64 accuracy gate territory, ``test_common.h:138``; see
    :mod:`distributedfft_tpu.ops.ddfft`). Host conversion via
    ``dd_from_host`` / ``dd_to_host``.
    """

    shape: tuple[int, int, int]
    direction: int
    decomposition: str            # "single" | "slab" | "pencil"
    mesh: Mesh | None
    fn: Callable
    in_sharding: NamedSharding | None
    out_sharding: NamedSharding | None
    # Leading batch axis (both dd components carry it); None = unbatched.
    batch: int | None = None

    @property
    def forward(self) -> bool:
        return self.direction == FORWARD

    def __call__(self, hi, lo, *, scale: Scale = Scale.NONE):
        if _metrics._enabled:
            _metrics.inc("executes", kind="dd",
                         decomposition=self.decomposition, executor="dd")
        with add_trace(f"execute_dd_{self.decomposition}"):
            yh, yl = self.fn(hi, lo)
            if scale != Scale.NONE:
                yh, yl = _jitted_dd_scale()(
                    yh, yl, scale_factor(scale, math.prod(self.shape)))
        return yh, yl


@functools.lru_cache(maxsize=1)
def _jitted_dd_scale():
    """One compiled dd-scalar product per (shapes, scale) — scaled calls
    replay a fused kernel instead of eagerly dispatching the compensated
    chain (the plan-owns-everything discipline)."""
    from .ops import ddfft

    return jax.jit(ddfft.dd_scale, static_argnums=2)


def plan_dd_dft_c2c_3d(
    shape: Sequence[int],
    mesh: Mesh | int | None = None,
    *,
    direction: int = FORWARD,
    donate: bool = False,
    overlap_chunks: int | str | None = None,
    batch: int | None = None,
) -> DDPlan3D:
    """Create a 3D C2C FFT plan at the emulated double-precision tier.

    Single device (``mesh=None``) runs the dd engine whole-cube; a 1D
    mesh runs the dd slab pipeline, a 2D mesh the dd pencil pipeline
    (both dd components through the same collectives,
    :mod:`..parallel.ddslab`). The accuracy analog of the reference's
    f64 ``fft_mpi_plan_dft_c2c_3d`` on hardware without f64 (measured
    ~1e-13 forward / <1e-11 roundtrip). ``overlap_chunks`` pipelines
    each exchange under the downstream dd FFT exactly like the c64 tier
    (int K, ``"auto"``, or None -> ``DFFT_OVERLAP``). ``batch=B``
    coalesces B transforms into one device program with one shared pair
    of collectives per exchange (the :func:`plan_dft_c2c_3d` convention;
    both dd components carry the leading batch axis)."""
    from .ops import ddfft
    from .parallel.slab import batch_pspec as _bp

    shape, forward = _check_direction(shape, direction)
    batch = _norm_batch(batch)
    bo = 0 if batch is None else 1
    dn = (0, 1) if donate else ()
    if mesh is None:
        fn = jax.jit(
            functools.partial(ddfft.fftn_dd, axes=(bo, 1 + bo, 2 + bo),
                              forward=forward), donate_argnums=dn)
        return DDPlan3D(shape=shape, direction=direction,
                        decomposition="single", mesh=None, fn=fn,
                        in_sharding=None, out_sharding=None, batch=batch)
    if isinstance(mesh, int):
        from .parallel.mesh import make_mesh

        mesh = make_mesh(mesh)
    overlap = resolve_overlap_chunks(
        overlap_chunks, shape=shape, ndev=math.prod(mesh.devices.shape),
        itemsize=8 * (batch or 1))
    if len(mesh.axis_names) == 1:
        from .parallel.ddslab import build_dd_slab_fft3d

        fn, spec = build_dd_slab_fft3d(mesh, shape, forward=forward,
                                       axis_name=mesh.axis_names[0],
                                       donate=donate,
                                       overlap_chunks=overlap, batch=batch)
        return DDPlan3D(
            shape=shape, direction=direction, decomposition="slab",
            mesh=mesh, fn=fn,
            in_sharding=NamedSharding(mesh, _bp(spec.in_pspec, batch)),
            out_sharding=NamedSharding(mesh, _bp(spec.out_pspec, batch)),
            batch=batch,
        )
    if len(mesh.axis_names) == 2:
        from .parallel.ddslab import build_dd_pencil_fft3d

        row, col = mesh.axis_names[:2]
        fn, spec = build_dd_pencil_fft3d(
            mesh, shape, row_axis=row, col_axis=col, forward=forward,
            donate=donate, overlap_chunks=overlap, batch=batch)
        return DDPlan3D(
            shape=shape, direction=direction, decomposition="pencil",
            mesh=mesh, fn=fn,
            in_sharding=NamedSharding(mesh, _bp(spec.in_spec, batch)),
            out_sharding=NamedSharding(mesh, _bp(spec.out_spec, batch)),
            batch=batch,
        )
    raise ValueError("dd plans support single-device, 1D, or 2D meshes")


def plan_dd_brick_dft_c2c_3d(
    shape: Sequence[int],
    mesh: Mesh | int | None,
    in_boxes: Sequence[Box3],
    out_boxes: Sequence[Box3],
    *,
    direction: int = FORWARD,
    algorithm: str = "alltoall",
    donate: bool = False,
) -> DDPlan3D:
    """Arbitrary per-device brick I/O at the emulated-double tier —
    heFFTe's double-precision arbitrary-box capability
    (``heffte_fft3d.h:105-115`` at the f64 gate) on f32/bf16 hardware.

    Both dd components travel the same overlap-map transports as the
    c64 brick tier (each component is a complex64 stack), bracketing
    the distributed dd chain; ``Box3.order`` storage orders are honored
    on both sides. I/O is a pair of ``[P, *pad]`` stacks (use
    ``scatter_bricks`` on the host hi/lo parts from ``dd_from_host``).
    ``algorithm="alltoallv"`` selects the exact-count ragged transport
    for the brick edges."""
    shape, _ = _check_direction(shape, direction)
    inner = plan_dd_dft_c2c_3d(shape, mesh, direction=direction)
    return _dd_brick_wrap(inner, shape, shape, in_boxes, out_boxes,
                          algorithm, donate)


def plan_dd_brick_dft_r2c_3d(
    shape: Sequence[int],
    mesh: Mesh | int | None,
    in_boxes: Sequence[Box3],
    out_boxes: Sequence[Box3],
    *,
    direction: int = FORWARD,
    algorithm: str = "alltoall",
    donate: bool = False,
) -> DDPlan3D:
    """Real<->complex brick plan at the emulated double tier — heFFTe's
    ``fft3d_r2c`` arbitrary-box double capability. Forward: ``in_boxes``
    partition the real-space world ``shape`` (float32 dd stacks),
    ``out_boxes`` the axis-2-halved complex world; backward swaps the
    roles. Canonical ``r2c_axis=2`` only at this tier. ``donate`` is a
    documented no-op, as on every r2c plan: the real float32 and
    half-spectrum complex64 stacks can never alias."""
    del donate  # r2c buffers never alias (same contract as the non-brick
    #             dd r2c planner); donating would only warn per execute.
    shape, forward = _check_direction(shape, direction)
    half = tuple(shape[:2]) + (shape[2] // 2 + 1,)
    inner = plan_dd_dft_r2c_3d(shape, mesh, direction=direction)
    in_world, out_world = (shape, half) if forward else (half, shape)
    return _dd_brick_wrap(inner, in_world, out_world, in_boxes, out_boxes,
                          algorithm, donate=False)


def plan_dd_brick_dft_c2r_3d(shape, mesh, in_boxes, out_boxes,
                             **kw) -> DDPlan3D:
    """Convenience alias: the inverse of
    :func:`plan_dd_brick_dft_r2c_3d`."""
    kw.setdefault("direction", BACKWARD)
    return plan_dd_brick_dft_r2c_3d(shape, mesh, in_boxes, out_boxes, **kw)


def _dd_brick_wrap(inner: DDPlan3D, in_world, out_world, in_boxes,
                   out_boxes, algorithm: str, donate: bool) -> DDPlan3D:
    """Bracket a distributed dd plan with the brick edges (shared by the
    dd c2c and r2c brick planners; the dd analog of
    :func:`_wrap_brick_io`, sharing its edge construction)."""
    if inner.mesh is None or inner.in_sharding is None:
        _check_brick_algorithm(algorithm)
        edge_in, edge_out = _single_brick_edges(
            in_boxes, out_boxes, in_world, out_world)
        inner_fn1 = inner.fn

        @functools.partial(
            jax.jit, donate_argnums=(0, 1) if donate else ())
        def fn1(hi, lo):
            yh, yl = inner_fn1(edge_in(hi), edge_in(lo))
            return edge_out(yh), edge_out(yl)

        return DDPlan3D(
            shape=inner.shape, direction=inner.direction,
            decomposition=f"bricks-{inner.decomposition}", mesh=None,
            fn=fn1, in_sharding=None, out_sharding=None,
        )
    m = inner.mesh
    edge_in, edge_out, _, _ = _build_brick_edges(
        m, in_boxes, out_boxes, in_world, out_world,
        inner.in_sharding.spec, inner.out_sharding.spec, algorithm)
    inner_fn = inner.fn

    @functools.partial(
        jax.jit, donate_argnums=(0, 1) if donate else ())
    def fn(hi, lo):
        yh, yl = inner_fn(edge_in(hi), edge_in(lo))
        return edge_out(yh), edge_out(yl)

    names = tuple(m.axis_names)
    stack_sh = NamedSharding(m, P(names, None, None, None))
    return DDPlan3D(
        shape=inner.shape, direction=inner.direction,
        decomposition=f"bricks-{inner.decomposition}", mesh=m, fn=fn,
        in_sharding=stack_sh, out_sharding=stack_sh,
    )


def plan_dd_dft_r2c_3d(
    shape: Sequence[int],
    mesh: Mesh | int | None = None,
    *,
    direction: int = FORWARD,
    r2c_axis: int = 2,
    donate: bool = False,
    overlap_chunks: int | str | None = None,
    batch: int | None = None,
) -> DDPlan3D:
    """Real<->complex 3D plan at the emulated double tier — heFFTe's
    ``fft3d_r2c`` double gate on f32/bf16 hardware. ``shape`` is the
    real-space world; forward takes real float32 dd pairs and returns
    half-spectrum complex dd pairs (``r2c_axis`` — default 2, heFFTe's
    ``r2c_direction`` — shrunk to ``N//2+1``), backward inverts with
    numpy 1/N scaling. Single-device, 1D slab mesh, or 2D pencil mesh
    (the latter via ``build_dd_pencil_rfft3d``). Non-default
    ``r2c_axis`` runs the canonical chain on a transposed view of both
    dd components (the same discipline as :func:`plan_dft_r2c_3d`).
    ``donate`` is accepted for API symmetry but is a no-op here: real
    and half-spectrum buffers differ in dtype and size, so XLA can
    never alias them. ``batch=B`` coalesces B same-shape transforms
    into one program with one shared pair of collectives per exchange
    (the :func:`plan_dd_dft_c2c_3d` convention — both dd components
    carry the leading batch axis); canonical ``r2c_axis=2`` only."""
    from .ops import ddfft
    from .parallel.slab import batch_pspec as _bp

    batch = _norm_batch(batch)
    if r2c_axis != 2:
        if batch is not None:
            raise ValueError(
                "batched dd r2c plans run the canonical r2c_axis=2 chain; "
                "transpose the batch's world instead of passing r2c_axis")
        return _dd_r2c_axis_wrapped(shape, mesh, r2c_axis,
                                    direction=direction,
                                    overlap_chunks=overlap_chunks)
    shape, forward = _check_direction(shape, direction)
    # r2c/c2r buffers can never alias (f32 real world vs complex64
    # half-spectrum differ in dtype and size on every decomposition), so
    # donation would only emit unusable-donation warnings per execute:
    # accepted for API symmetry, documented no-op.
    del donate
    bo = 0 if batch is None else 1
    if mesh is None:
        if batch is None:
            if forward:
                fn = jax.jit(ddfft.rfftn_dd)
            else:
                fn = jax.jit(functools.partial(ddfft.irfftn_dd,
                                               n2=shape[2]))
        else:
            # Batched single-device tier: rfftn_dd/irfftn_dd transform
            # every leading axis, so the batched program spells the
            # spatial axes explicitly (same stage order — batch=1 and an
            # unadorned plan stay byte-identical via _norm_batch).
            h = shape[2] // 2 + 1

            def _rfft_b(hi, lo):
                from jax import lax as _lax

                chi = _lax.complex(hi, jnp.zeros_like(hi))
                clo = _lax.complex(lo, jnp.zeros_like(lo))
                chi, clo = ddfft.fft_axis_dd(chi, clo, 2 + bo)
                chi, clo = chi[..., :h], clo[..., :h]
                for ax in (bo, 1 + bo):
                    chi, clo = ddfft.fft_axis_dd(chi, clo, ax)
                return chi, clo

            def _irfft_b(hi, lo):
                for ax in (bo, 1 + bo):
                    hi, lo = ddfft.fft_axis_dd(hi, lo, ax, forward=False)
                hi, lo = ddfft.fft_axis_dd(
                    ddfft.mirror_half_spectrum(hi, shape[2], axis=2 + bo),
                    ddfft.mirror_half_spectrum(lo, shape[2], axis=2 + bo),
                    2 + bo, forward=False)
                return jnp.real(hi), jnp.real(lo)

            fn = jax.jit(_rfft_b if forward else _irfft_b)
        return DDPlan3D(shape=shape, direction=direction,
                        decomposition="single", mesh=None, fn=fn,
                        in_sharding=None, out_sharding=None, batch=batch)
    if isinstance(mesh, int):
        from .parallel.mesh import make_mesh

        mesh = make_mesh(mesh)
    overlap = resolve_overlap_chunks(
        overlap_chunks, shape=shape, ndev=math.prod(mesh.devices.shape),
        itemsize=8 * (batch or 1))
    if len(mesh.axis_names) == 1:
        from .parallel.ddslab import build_dd_slab_rfft3d

        fn, spec = build_dd_slab_rfft3d(mesh, shape, forward=forward,
                                        axis_name=mesh.axis_names[0],
                                        overlap_chunks=overlap,
                                        batch=batch)
        return DDPlan3D(
            shape=shape, direction=direction, decomposition="slab",
            mesh=mesh, fn=fn,
            in_sharding=NamedSharding(mesh, _bp(spec.in_pspec, batch)),
            out_sharding=NamedSharding(mesh, _bp(spec.out_pspec, batch)),
            batch=batch,
        )
    if len(mesh.axis_names) == 2:
        from .parallel.ddslab import build_dd_pencil_rfft3d

        row, col = mesh.axis_names[:2]
        fn, spec = build_dd_pencil_rfft3d(
            mesh, shape, row_axis=row, col_axis=col, forward=forward,
            overlap_chunks=overlap, batch=batch)
        return DDPlan3D(
            shape=shape, direction=direction, decomposition="pencil",
            mesh=mesh, fn=fn,
            in_sharding=NamedSharding(mesh, _bp(spec.in_spec, batch)),
            out_sharding=NamedSharding(mesh, _bp(spec.out_spec, batch)),
            batch=batch,
        )
    raise ValueError("dd r2c plans support single-device, 1D, or 2D meshes")


def plan_dd_dft_c2r_3d(shape, mesh=None, **kw) -> DDPlan3D:
    """Convenience alias: the inverse of :func:`plan_dd_dft_r2c_3d`."""
    kw.setdefault("direction", BACKWARD)
    return plan_dd_dft_r2c_3d(shape, mesh, **kw)


def _dd_r2c_axis_wrapped(shape, mesh, axis: int, *, direction,
                         overlap_chunks=None) -> DDPlan3D:
    """dd r2c/c2r with the halved axis != 2: the canonical chain runs on
    a transposed view of BOTH dd components; shapes and shardings are
    permuted back to the caller's convention (the
    :func:`_r2c_axis_wrapped` discipline at the dd tier)."""
    if axis not in (0, 1):
        raise ValueError(f"r2c_axis must be 0, 1, or 2; got {axis}")
    shape, _ = _check_direction(shape, direction)
    perm = _swap_perm(axis)
    pshape = tuple(shape[p] for p in perm)
    try:
        inner = plan_dd_dft_r2c_3d(pshape, mesh, direction=direction,
                                   overlap_chunks=overlap_chunks)
    except ValueError as e:
        raise _chain_convention_note(e, axis) from e

    inner_fn = inner.fn

    def fn(hi, lo):
        yh, yl = inner_fn(jnp.transpose(hi, perm), jnp.transpose(lo, perm))
        return jnp.transpose(yh, perm), jnp.transpose(yl, perm)

    return DDPlan3D(
        shape=shape, direction=direction, decomposition=inner.decomposition,
        mesh=inner.mesh, fn=jax.jit(fn),
        in_sharding=_permute_sharding3(inner.in_sharding, perm),
        out_sharding=_permute_sharding3(inner.out_sharding, perm),
    )


# ---------------------------------------------------------------- plan cache
# Plans are immutable (the reference's plan-owns-everything discipline) and
# expensive to build, so the public planners memoize on their full argument
# set. The key also carries every trace-time env knob that changes what a
# plan would compile to (DFFT_MM_*, DFFT_PALLAS_*, ...) plus the x64 flag —
# two calls that could compile different programs never share an entry.
# DFFT_PLAN_CACHE=0 disables; unhashable arguments bypass silently.

_PLAN_CACHE: dict = {}
_PLAN_CACHE_MAX = 128  # plans hold compiled executables; bound the HBM/host
_PLAN_ENV_KNOBS = (
    "DFFT_AUTO_EXECUTORS", "DFFT_MM_PRECISION", "DFFT_MM_COMPLEX",
    "DFFT_MM_SPLIT", "DFFT_MM_DIRECT_MAX", "DFFT_DD_DEPTH",
    "DFFT_PALLAS_PACK", "DFFT_PALLAS_SPLIT", "DFFT_PALLAS_TILE",
    "DFFT_PALLAS_TILE_STRIDED", "DFFT_XLA_REAL",
    "DFFT_FORCE_REAL_LOWERING", "DFFT_OVERLAP",
    # Executor routing: the default-executor escape hatch and the
    # XLA:CPU fft-thunk guard both change which executor a default
    # planner call builds with.
    "DFFT_EXECUTOR", "DFFT_THUNK_GUARD",
    # Tuned planning: mode, wisdom store, budget, and survivor cap all
    # change what a tuned planner call would build/measure — as do the
    # calibrated-profile path and its correction opt-out (they move the
    # pruning model's ranking).
    "DFFT_TUNE", "DFFT_WISDOM", "DFFT_TUNE_ITERS", "DFFT_TUNE_MAX",
    "DFFT_HW_PROFILE", "DFFT_TUNE_CORRECTION",
    # On-wire exchange compression: the default of PlanOptions.wire_dtype
    # resolves from the env at plan time, so two calls under different
    # wire modes compile different collective programs.
    "DFFT_WIRE_DTYPE",
    # Pallas fusion tier: the default of PlanOptions.fuse resolves from
    # the env at plan time (fused chains compile a different program —
    # codec moved out of the transport into the stage kernels).
    "DFFT_FUSE",
)


def clear_plan_cache() -> None:
    """Drop every memoized plan (tuning sweeps that mutate env knobs
    outside ``_PLAN_ENV_KNOBS``, tests)."""
    _PLAN_CACHE.clear()


def _plan_cache_key(kind: str, shape, mesh, kw: dict):
    """Hashable cache key, or None when caching is off / impossible."""
    if os.environ.get("DFFT_PLAN_CACHE", "1") == "0":
        return None
    key = (
        kind, shape, mesh, tuple(sorted(kw.items())),
        bool(jax.config.jax_enable_x64),
        tuple(os.environ.get(v, "") for v in _PLAN_ENV_KNOBS),
    )
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _timed_build(kind: str, build: Callable, shape, mesh, kw: dict):
    # Fault-injection point "plan": a cache miss is about to construct a
    # plan (docs/ROBUSTNESS.md; cache hits replay an already-built plan
    # and are not a build). The label lets match= target one executor.
    _faults.check("plan", str(kw.get("executor") or ""))
    t0 = time.perf_counter()
    plan = build(shape, mesh, **kw)
    if _metrics._enabled:
        _metrics.observe(
            "plan_build_seconds", time.perf_counter() - t0, kind=kind)
        _metrics.inc(
            "plan_builds", kind=kind, decomposition=plan.decomposition,
            executor=getattr(plan, "executor", "dd"))
    return plan


def _plan_cached(kind: str, build: Callable) -> Callable:
    """Memoizing wrapper applied to each public planner below."""

    @functools.wraps(build)
    def wrapper(shape, mesh=None, **kw):
        shape = tuple(int(s) for s in shape)
        key = _plan_cache_key(kind, shape, mesh, kw)
        if key is None:
            return _timed_build(kind, build, shape, mesh, kw)
        plan = _PLAN_CACHE.get(key)
        if plan is not None:
            if _metrics._enabled:
                _metrics.inc("plan_cache_hits", kind=kind)
            return plan
        if _metrics._enabled:
            _metrics.inc("plan_cache_misses", kind=kind)
        plan = _PLAN_CACHE[key] = _timed_build(kind, build, shape, mesh, kw)
        while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
            _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
        return plan

    return wrapper


plan_dft_c2c_3d = _plan_cached("c2c", plan_dft_c2c_3d)
plan_dft_r2c_3d = _plan_cached("r2c", plan_dft_r2c_3d)
plan_dd_dft_c2c_3d = _plan_cached("dd_c2c", plan_dd_dft_c2c_3d)
plan_dd_dft_r2c_3d = _plan_cached("dd_r2c", plan_dd_dft_r2c_3d)


def _plan_exchange_bytes(plan: Plan3D) -> tuple[int, int]:
    """(true, wire) bytes one execution of ``plan`` moves between
    devices: chain exchanges per ``plan_logic.exchange_payloads`` under
    the plan's own algorithm, plus any brick-edge ring/a2av traffic.
    Computed once and cached on the plan object, so the per-execute
    metrics hook is a dict lookup."""
    cached = getattr(plan, "_exchange_bytes", None)
    if cached is not None:
        return cached
    import numpy as np

    true_b = wire_b = 0
    lp = plan.logic
    if lp is not None and lp.mesh is not None:
        from .parallel.exchange import WIRE_BYTE_KEYS
        from .plan_logic import exchange_payloads

        shape_eff = plan.out_shape if (plan.real and plan.forward) else (
            plan.in_shape if plan.real else plan.shape)
        if plan.batch is not None and len(shape_eff) == 4:
            # exchange_payloads takes the per-transform 3D shape; the
            # B-fold scaling rides on lp.batch inside it.
            shape_eff = shape_eff[1:]
        itemsize = np.dtype(plan.dtype).itemsize
        wire_key = WIRE_BYTE_KEYS[plan.options.algorithm]
        for e in exchange_payloads(lp, shape_eff, itemsize):
            true_b += e["true_bytes"]
            # wire_factor scales for on-wire compression (bf16 pairs
            # halve c64 wire bytes, int8 block-scaled pairs quarter
            # them, sidecar included); 1.0 on the exact wire.
            wire_b += int(e[wire_key] * e.get("wire_factor", 1.0))
    if plan.brick_edges is not None:
        itemsize = np.dtype(plan.dtype).itemsize
        for bs in plan.brick_edges:
            true_b += bs.payload_elems * itemsize
            wire_b += bs.wire_elems * itemsize
    plan._exchange_bytes = (true_b, wire_b)
    return true_b, wire_b


def execute(plan: Plan3D, x, *, scale: Scale = Scale.NONE):
    """Run a plan (``fft_mpi_execute_dft_3d_c2c``,
    ``fft_mpi_3d_api.cpp:181``). Accepts any array-like of the plan's global
    input shape; device placement follows the plan's input sharding.

    Telemetry: with tracing on, the whole call is the ``execute_*`` host
    span. The chain's t0..t3 stages are not host spans: they are
    ``stage_scope`` names in the compiled program, which the profiler's
    device trace reports on each op (its ``tf_op``; read with
    :mod:`.utils.xplane`). With metrics on, bumps the ``executes`` counter and the
    exchange true/wire byte counters. Both disabled (the default) cost
    one flag check each — no events, no allocations.
    """
    x = jnp.asarray(x, dtype=plan.in_dtype)
    if x.shape != plan.in_shape:
        raise ValueError(f"plan input shape is {plan.in_shape}, got {x.shape}")
    opname = getattr(plan, "op", "")
    if opname:
        kind = f"op_{opname}"  # fused spectral-operator execution
    elif plan.real:
        kind = "r2c" if plan.forward else "c2r"
    else:
        kind = "c2c"
    if _metrics._enabled:
        _metrics.inc("executes", kind=kind,
                     decomposition=plan.decomposition, executor=plan.executor)
        true_b, wire_b = _plan_exchange_bytes(plan)
        if true_b or wire_b:
            _metrics.inc("exchange_true_bytes", float(true_b))
            _metrics.inc("exchange_wire_bytes", float(wire_b))
    with add_trace(f"execute_{kind}_{plan.decomposition}"):
        # Fault-injection points (docs/ROBUSTNESS.md): "compile" fires
        # on a plan's FIRST execution (JAX compiles at first call),
        # "exchange" emulates a t2-exchange fault host-side for plans
        # that own one (a fault inside the compiled collective cannot
        # raise from XLA), "execute" on every dispatch. All three are
        # env-dict lookups when nothing is armed, and none touch the
        # traced program — the HLO is byte-identical either way.
        if not getattr(plan, "_warm", False):
            _faults.check("compile", plan.executor)
        if plan.mesh is not None:
            _faults.check("exchange", plan.options.algorithm)
        _faults.check("execute", plan.executor)
        y = plan.fn(x)
        plan._warm = True
        if scale != Scale.NONE:
            y = apply_scale(y, scale, plan.world_size)
    return y


def alloc_local(plan: Plan3D, fill=None):
    """Allocate a global array laid out per the plan's input sharding
    (``fft_mpi_alloc_local_memory``, ``fft_mpi_3d_api.h:73``).

    Uneven extents cannot be placed by ``device_put`` (equal-shard rule);
    there the array is returned unplaced and the plan's own pad/crop
    chain shards it on first execute — previously this raised, which
    silently failed every measured-tournament candidate (and
    ``executor="auto"``) on uneven shapes."""
    if fill is None:
        arr = jnp.zeros(plan.in_shape, plan.in_dtype)
    else:
        arr = jnp.asarray(fill, dtype=plan.in_dtype)
    if plan.in_sharding is not None and _spec_divides(
            plan.in_sharding.mesh, plan.in_sharding.spec, arr.shape):
        arr = jax.device_put(arr, plan.in_sharding)
    return arr


def explain(plan: Plan3D, **kw) -> dict:
    """Plan attribution record: the model/compiled/measured join per
    t0..t3 stage, with per-stage MFU, ICI utilization, whole-program
    cost/memory, and divergence flags (:mod:`.explain`). ``iters``
    controls the measured warm passes; ``measure=False`` skips every
    execution; ``device_timing=True`` attributes stages from the
    ``jax.profiler`` device timeline (host-bracket fallback);
    ``allgather=True`` merges per-host stage medians (collective).
    Render with :func:`.explain.format_explain`, or use the
    ``report explain`` subcommand / ``speed3d -explain`` drivers."""
    from .explain import explain as _explain_impl

    return _explain_impl(plan, **kw)


def destroy_plan(plan: Plan3D) -> None:
    """Parity shim for ``fft_mpi_destroy_plan`` — plans hold no manually
    managed device memory; XLA buffers are garbage collected."""
    del plan
