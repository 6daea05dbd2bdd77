"""Plan logic: option handling, decomposition selection, reshape minimization.

The heFFTe analog layer (``heffte/heffteBenchmark/include/heffte_plan_logic.h``,
``src/heffte_plan_logic.cpp``): ``plan_options`` {algorithm, use_reorder,
use_pencils, use_gpu_aware} (``heffte_plan_logic.h:69-89``) and
``plan_operations`` (``heffte_plan_logic.cpp:410-432``), which inspects the
in/out geometry and picks the cheapest reshape pipeline — the pencil planner
(``:162-245``) and slab planner (``:265-408``) both detect when the caller's
layouts already *are* pencils/slabs on useful axes and emit fewer reshapes.

On TPU the decision space is smaller and different: the transport is always
XLA collectives over the mesh (no gpu-aware/host-staged split — there is no
host staging to choose), layout reordering belongs to XLA's layout
assignment, and the real knobs are

- **decomposition**: slab (one exchange) vs pencil (two exchanges, but each
  on a smaller mesh axis and with more parallel lines per FFT stage);
- **axis assignment** (the reshape-minimization lever): which array axis the
  input/output sharding lives on. The slab chain works for ANY ordered axis
  pair (in_axis != out_axis) and the pencil chain for any axis permutation
  in either exchange order, so a plan can *start from the caller's layout*
  instead of resharding to a fixed canonical one — the TPU translation of
  heFFTe's "already pencils on the right axes -> skip the reshape";
- **exchange algorithm**: one fused ``all_to_all`` vs a pipelined
  ``ppermute`` ring (:mod:`.parallel.exchange`);
- **mesh geometry**: how to factor the device count into a 2D grid
  (min-surface search, :func:`distributedfft_tpu.native.pencil_grid`);
- **device count**: shrink to an evenly-dividing count when that removes
  padding at no per-device compute cost (``getProperDeviceNum``,
  ``fft_mpi_3d_api.cpp:232-272``).

:func:`logic_plan3d` resolves (shape, mesh/device-count, options, layouts)
to a concrete decomposition + mesh + stage chain, the role of
``plan_operations``.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field, replace
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import geometry as geo
from . import native
from .parallel.exchange import ALGORITHMS, WIRE_DTYPES, wire_itemsize
from .parallel.mesh import make_mesh
from .parallel.slab import check_batch


#: Valid ``PlanOptions.tune`` values (None defers to the DFFT_TUNE env var).
TUNE_MODES = (None, "off", "wisdom", "measure")


@dataclass(frozen=True)
class PlanOptions:
    """User-tunable plan knobs (``plan_options``,
    ``heffte_plan_logic.h:69-89``).

    ``decomposition``: "auto" | "single" | "slab" | "pencil".
    ``algorithm``: "alltoall" | "ppermute" (``reshape_algorithm``,
    ``heffte_plan_logic.h:47-56``).
    ``executor``: registered local-FFT backend name (``one_dim_backend``,
    ``heffte_common.h:275``).
    ``donate``: consume the input buffer (bufferDev ping-pong analog,
    ``fft_mpi_3d_api.cpp:66-81``).
    ``renegotiate``: device-count renegotiation when the mesh is built from
    an int device count (``getProperDeviceNum``, ``fft_mpi_3d_api.cpp:232-272``):
    "auto" shrinks only when the negotiated count removes padding at equal
    per-device compute (a strict win); "force" always shrinks to the largest
    evenly-dividing count (the reference's rule); "never" keeps the request.
    ``overlap_chunks``: pipelined t2/t3 exchange/compute overlap — the
    local block is split into K chunks along the bystander axis and each
    chunk's exchange issues before the previous chunk's downstream FFT
    (the ``MPI_Waitany`` overlap of the reference's pipelined p2p path,
    ``fft_mpi_3d_api.cpp:610-699``). ``None`` (the default) defers to the
    ``DFFT_OVERLAP`` env var at plan time (unset -> 1 = today's
    monolithic chain); an int >= 1 pins K; ``"auto"`` picks K from the
    per-device block bytes vs the VMEM/ICI crossover
    (:func:`auto_overlap_chunks`, model in ``docs/MFU_ANALYSIS.md``).
    ``tune``: measured plan selection (:mod:`.tuner`; the reference's
    plan-and-pick discipline generalized across decomposition,
    transport, executor, AND overlap K — heFFTe/AccFFT's finding that
    the best combination is configuration-dependent and must be
    searched). ``"off"`` keeps today's static heuristics byte-identical;
    ``"wisdom"`` consults the persistent wisdom store and falls back to
    the heuristics on a miss (never measures); ``"measure"`` runs the
    pruned tournament on a miss and records the winner. ``None`` (the
    default) defers to the ``DFFT_TUNE`` env var (unset -> ``"off"``).
    See ``docs/TUNING.md``.
    ``wire_dtype``: on-wire compression of the t2 exchange payload,
    one of the registered wire codecs
    (:data:`..parallel.exchange.WIRE_DTYPES`): ``"bf16"`` casts the
    complex payload to (real, imag) bfloat16 pairs immediately before
    each collective and back after (half the c64 wire bytes);
    ``"int8"`` quantizes the (real, imag) planes per exchange tile with
    power-of-two steps riding as a tiny f32 sidecar (~quarter the c64
    wire bytes). Both at a bounded, measured precision cost
    (:func:`..parallel.exchange.wire_roundtrip_error`). ``"none"`` pins
    the exact wire; ``None`` (the default) defers to the
    ``DFFT_WIRE_DTYPE`` env var at plan time (unset -> exact,
    byte-identical HLO to an uncompressed plan).
    ``max_roundtrip_err``: the plan's relative round-trip error budget.
    The tuner enumerates reduced-accuracy candidates — compressed wire
    (``wire_dtype``) and reduced matmul precision (``mm_precision``)
    tiers — only for plans that declare a budget, filters out candidates
    whose measured round-trip error (wire + precision errors compose;
    one budget governs the sum) exceeds it, and replays a stored
    reduced-accuracy winner only into plans whose budget admits its
    recorded error.
    ``mm_precision``: plan-scoped MXU contraction tier of the
    matmul-family executors — ``"bf16"`` (one bf16 pass), ``"f32"``
    (3-pass refinement), ``"highest"`` (f32-exact, the bare default).
    ``None`` (the default) leaves the trace on the ``DFFT_MM_PRECISION``
    env default — byte-identical HLO to today's plans. A non-None tier
    composes into the executor label (``matmul:bf16`` — a DISTINCT
    executor: plan-cache keyed, wisdom-recorded, two tiers coexisting in
    one process; :func:`..ops.executors.tiered_name`).
    ``mm_complex``: plan-scoped complex-product mode of the same family
    (``"gauss"`` = the 3-real-matmul split; ``None``/``"native"`` defers
    to ``DFFT_MM_COMPLEX``).
    ``fuse``: the Pallas stage-fusion tier — ``True`` composes the
    ``:fuse`` flag into the executor label (``pallas:fuse``, a DISTINCT
    plan-cache-keyed executor; :func:`..ops.executors.fused_name`),
    asking the stage-graph compiler's fusion pass to fold the wire
    codec's encode/decode into the adjacent stage computes (Pallas
    mega-kernels where eligible; see ``docs/TUNING.md`` "Pallas fusion
    tier"). ``False`` pins fusion off; ``None`` (the default) defers to
    the ``DFFT_FUSE`` env var at plan time (unset -> off,
    byte-identical HLO to today's plans). Only meaningful with a
    ``pallas``-family executor and a compressed ``wire_dtype``;
    ineligible graphs fall back to the unfused chain with a counted,
    explain-visible reason — never an error.
    """

    decomposition: str = "auto"
    algorithm: str = "alltoall"
    executor: str = "xla"
    donate: bool = False
    renegotiate: str = "auto"
    overlap_chunks: int | str | None = None
    tune: str | None = None
    wire_dtype: str | None = None
    max_roundtrip_err: float | None = None
    mm_precision: str | None = None
    mm_complex: str | None = None
    fuse: bool | None = None

    def __post_init__(self) -> None:
        if self.algorithm not in ALGORITHMS:
            raise ValueError(
                f"unknown algorithm {self.algorithm!r}; use one of {ALGORITHMS}"
            )
        wd = self.wire_dtype
        if isinstance(wd, str):
            wd = wd.strip().lower()
            object.__setattr__(self, "wire_dtype", wd or None)
            wd = self.wire_dtype
        if wd not in WIRE_DTYPES and wd != "none":
            raise ValueError(
                f"wire_dtype must be one of {WIRE_DTYPES} or 'none', "
                f"got {self.wire_dtype!r}")
        mre = self.max_roundtrip_err
        if mre is not None and (
                not isinstance(mre, (int, float)) or isinstance(mre, bool)
                or not mre > 0):
            raise ValueError(
                f"max_roundtrip_err must be a positive float or None, "
                f"got {mre!r}")
        if self.decomposition not in ("auto", "single", "slab", "pencil"):
            raise ValueError(f"unknown decomposition {self.decomposition!r}")
        if self.renegotiate not in ("auto", "force", "never"):
            raise ValueError(
                f"renegotiate must be auto|force|never, got {self.renegotiate!r}"
            )
        oc = self.overlap_chunks
        if isinstance(oc, str) and oc != "auto":
            # Numeric strings (the DFFT_OVERLAP env form) normalize to int.
            try:
                oc = int(oc)
            except ValueError:
                raise ValueError(
                    f"overlap_chunks must be an int >= 1, 'auto', or None, "
                    f"got {self.overlap_chunks!r}") from None
            object.__setattr__(self, "overlap_chunks", oc)
        if oc is not None and oc != "auto" and (
                not isinstance(oc, int) or isinstance(oc, bool) or oc < 1):
            raise ValueError(
                f"overlap_chunks must be an int >= 1, 'auto', or None, "
                f"got {self.overlap_chunks!r}")
        if self.tune not in TUNE_MODES:
            raise ValueError(
                f"tune must be one of {tuple(m for m in TUNE_MODES if m)} "
                f"or None, got {self.tune!r}")
        # Normalize + validate the plan-scoped matmul tiers (the executor
        # label is composed at plan time by executors.normalize_label; this
        # keeps an invalid tier from surviving into the plan cache key).
        from .ops.executors import (
            MM_COMPLEX_MODES, MM_TIERS, TIER_ALIASES,
        )

        mp = self.mm_precision
        if isinstance(mp, str):
            mp = mp.strip().lower() or None
            mp = TIER_ALIASES.get(mp, mp)  # lax-name spellings normalize
            object.__setattr__(self, "mm_precision", mp)
        if mp is not None and mp not in MM_TIERS:
            raise ValueError(
                f"mm_precision must be one of {MM_TIERS} or None, "
                f"got {self.mm_precision!r}")
        mc = self.mm_complex
        if isinstance(mc, str):
            mc = mc.strip().lower() or None
            object.__setattr__(self, "mm_complex", mc)
        if mc is not None and mc not in MM_COMPLEX_MODES:
            raise ValueError(
                f"mm_complex must be one of {MM_COMPLEX_MODES} or None, "
                f"got {self.mm_complex!r}")
        fu = self.fuse
        if isinstance(fu, str):
            # Env-style spellings normalize to the tri-state bool.
            fu = fu.strip().lower()
            if fu in ("", "none"):
                fu = None
            elif fu in ("1", "true", "on", "fuse"):
                fu = True
            elif fu in ("0", "false", "off"):
                fu = False
            else:
                raise ValueError(
                    f"fuse must be a bool or None, got {self.fuse!r}")
            object.__setattr__(self, "fuse", fu)
        elif fu is not None and not isinstance(fu, bool):
            raise ValueError(
                f"fuse must be a bool or None, got {self.fuse!r}")


DEFAULT_OPTIONS = PlanOptions()


def default_options(decomposition: str = "auto", **kw) -> PlanOptions:
    """cf. ``default_options<backend>()`` (``heffte_plan_logic.h:95``)."""
    return PlanOptions(decomposition=decomposition, **kw)


# Exchange/compute overlap auto-heuristic constants (crossover model in
# docs/MFU_ANALYSIS.md "Exchange/compute overlap"): a chunk's exchange
# payload must stay above the ICI packet-efficiency floor or the
# per-collective latency exceeds the transfer it hides, and chunk count is
# capped — each extra chunk adds one collective's fixed cost while the
# hideable transfer per chunk shrinks as 1/K.
OVERLAP_AUTO_MIN_CHUNK_BYTES = 4 << 20   # ~4 MiB/device per chunk floor
OVERLAP_AUTO_MAX_CHUNKS = 8


def auto_overlap_chunks(
    shape: Sequence[int], ndev: int, itemsize: int = 8,
) -> int:
    """Pick the overlap chunk count K from the per-device block bytes.

    K = clamp(block_bytes / OVERLAP_AUTO_MIN_CHUNK_BYTES, 1,
    OVERLAP_AUTO_MAX_CHUNKS): small blocks stay monolithic (nothing worth
    hiding; per-collective latency dominates), large blocks split until
    the per-chunk payload reaches the ICI efficiency floor or the chunk
    cap. ``itemsize`` defaults to complex64 (the on-chip tier — TPUs have
    no c128). The bystander-axis extent clamps K again inside
    :func:`..parallel.exchange.overlap_chunk_bounds`, so a coarse K here
    is safe for any chain geometry."""
    if ndev <= 1:
        return 1
    block = itemsize * math.prod(int(s) for s in shape) // ndev
    return max(1, min(OVERLAP_AUTO_MAX_CHUNKS,
                      block // OVERLAP_AUTO_MIN_CHUNK_BYTES))


def resolve_overlap_chunks(
    value: int | str | None,
    shape: Sequence[int] | None = None,
    ndev: int = 1,
    itemsize: int = 8,
) -> int:
    """Resolve a ``PlanOptions.overlap_chunks`` value to a concrete K.

    ``None`` reads the ``DFFT_OVERLAP`` env var at call time (unset ->
    1, today's monolithic chain); ``"auto"`` (from either source) runs
    :func:`auto_overlap_chunks`; ints pass through validated."""
    if value is None:
        raw = os.environ.get("DFFT_OVERLAP", "").strip()
        value = raw if raw else 1
    if isinstance(value, str):
        if value == "auto":
            return auto_overlap_chunks(shape, ndev, itemsize) if shape else 1
        try:
            value = int(value)
        except ValueError:
            raise ValueError(
                f"overlap_chunks must be an int >= 1 or 'auto', got "
                f"{value!r} (check DFFT_OVERLAP)") from None
    if value < 1:
        raise ValueError(f"overlap_chunks must be >= 1, got {value}")
    return int(value)


def resolve_wire_dtype(value: str | None) -> str | None:
    """Resolve a ``PlanOptions.wire_dtype`` value to a concrete wire
    mode: ``None`` (exact) or a registered codec name
    (:data:`..parallel.exchange.WIRE_DTYPES` — ``"bf16"``, ``"int8"``).

    ``None`` reads the ``DFFT_WIRE_DTYPE`` env var at plan time (unset
    -> exact); ``"none"`` pins the exact wire regardless of the env.
    One resolution point so the planners, the tuner's candidate space,
    and the benchmark drivers agree on what a given environment ships."""
    if value is None:
        value = os.environ.get("DFFT_WIRE_DTYPE", "").strip() or "none"
    v = value.strip().lower() if isinstance(value, str) else value
    if v in (None, "", "none", "0"):
        return None
    if v in WIRE_DTYPES:
        return v
    raise ValueError(
        f"wire_dtype must be one of {tuple(w for w in WIRE_DTYPES if w)} "
        f"or 'none', got {value!r} (check DFFT_WIRE_DTYPE)")


def resolve_fuse(value: bool | None) -> bool:
    """Resolve a ``PlanOptions.fuse`` value to a concrete bool.

    ``None`` reads the ``DFFT_FUSE`` env var at plan time (unset ->
    ``False``, today's unfused chains — byte-identical HLO); explicit
    bools pass through. One resolution point so the planners, the
    tuner's candidate space, and the benchmark drivers agree on whether
    a given environment fuses."""
    if value is None:
        raw = os.environ.get("DFFT_FUSE", "").strip().lower()
        if raw in ("", "0", "false", "off", "none"):
            return False
        if raw in ("1", "true", "on", "fuse"):
            return True
        raise ValueError(
            f"DFFT_FUSE must be 0/1/on/off, got {raw!r}")
    return bool(value)


def fused_model_stages(lp, shape=None, itemsize: int = 8) -> tuple:
    """Stage keys the Pallas fusion tier fuses with the wire codec for
    the chain ``lp`` describes — the ``fused=`` argument of
    :func:`model_stage_seconds` (the explain layer and the tuner's
    pruning model both derive it here, so they price fused plans
    identically).

    Empty when the plan does not activate fusion (the
    :func:`..stagegraph.plan_fusion` gate: executor carries the
    ``:fuse`` flag, a wire codec is set, overlap K == 1), and for
    chains with no kernel-fused stage: the single tier has no exchange,
    and the slab chains' multi-axis t0 sender and trailing op-chain
    inverse pass run the pure-JAX codec path, whose HBM streams match
    the unfused chain's — only their t3 receiver (c2c) fuses. Pencil
    chains fuse sender and every receiver (``t0``/``t1``/``t3``)."""
    from .ops.executors import split_fuse

    ex = lp.options.executor
    if not isinstance(ex, str):
        return ()
    try:
        if not split_fuse(ex)[1]:
            return ()
    except ValueError:
        return ()
    if resolve_wire_dtype(lp.options.wire_dtype) is None:
        return ()
    k = lp.options.overlap_chunks
    if not isinstance(k, int):
        ndev = 1 if lp.mesh is None else math.prod(lp.mesh.devices.shape)
        k = resolve_overlap_chunks(k, shape, ndev, itemsize)
    if k != 1:
        return ()
    if lp.mesh is None or lp.decomposition == "single":
        return ()
    if lp.decomposition == "pencil":
        return ("t0", "t1", "t3")
    if getattr(lp, "op", None):
        return ()
    return ("t3",)


def resolve_tune_mode(value: str | None) -> str:
    """Resolve a ``PlanOptions.tune`` value to a concrete mode.

    ``None`` reads the ``DFFT_TUNE`` env var at plan time (unset ->
    ``"off"``, today's static-heuristic planning); explicit strings pass
    through validated. One resolution point so the planners and the
    benchmark drivers agree on what a given environment plans."""
    if value is None:
        value = os.environ.get("DFFT_TUNE", "").strip() or "off"
    if value not in TUNE_MODES or value is None:
        raise ValueError(
            f"tune mode must be one of {tuple(m for m in TUNE_MODES if m)}, "
            f"got {value!r} (check DFFT_TUNE)")
    return value


def eligible_decompositions(shape: Sequence[int], ndev: int) -> tuple[str, ...]:
    """Decompositions worth *measuring* for ``ndev`` devices — the search
    axis the static :func:`choose_decomposition` collapses to one point.

    Slab is eligible while every device owns at least one plane on both
    exchange axes (past that the reference shrinks the device count,
    ``getProperDeviceNum``); pencil is eligible on any multi-device count
    (a prime count degrades to a 1xN grid, still a valid measurement).
    Single-device has nothing to search."""
    shape = tuple(int(s) for s in shape)
    if ndev <= 1:
        return ("single",)
    out = []
    if ndev <= min(shape[0], shape[1]):
        out.append("slab")
    out.append("pencil")
    return tuple(out)


@dataclass(frozen=True)
class LogicPlan:
    """Resolved plan skeleton (the ``logic_plan3d`` analog,
    ``heffte_plan_logic.h:152-164``): the decomposition, the mesh to run on,
    the axis assignment of the stage chain, and the intermediate layout
    chain as per-stage box lists. Orientation follows the plan's own
    direction: ``stages[0]`` is this plan's input side."""

    shape: tuple[int, int, int]
    decomposition: str            # "single" | "slab" | "pencil"
    mesh: Mesh | None
    options: PlanOptions
    forward: bool = True
    # Slab chain: input sharded on slab_axes[0], output on slab_axes[1].
    slab_axes: tuple[int, int] | None = None
    # Pencil chain: input layout (row->perm[0], col->perm[1], perm[2] local)
    # and exchange order "col_first" | "row_first".
    pencil_perm: tuple[int, int, int] | None = None
    pencil_order: str | None = None
    # Whether the caller's in/out layouts are realized by the chain itself
    # (True) or still need an edge reshard (False).
    in_absorbed: bool = True
    out_absorbed: bool = True
    # Device-count renegotiation record: (requested, used, reason).
    negotiated: tuple | None = None
    # Stage layouts: list of (fft_axes, boxes) pairs, input side first.
    stages: tuple = ()
    # Leading batch axis of a coalesced multi-request plan: B independent
    # transforms ride the chain with ONE shared exchange per stage (the
    # batch is a bystander dim of every collective). None = unbatched.
    # Geometry (stages, boxes) stays per-transform; the payload/model
    # accounting below scales with it.
    batch: int | None = None
    # Fused spectral-operator chain marker (:mod:`.operators`): the op
    # kind ("poisson", ...) of a FFT -> pointwise -> iFFT plan whose
    # forward half stops at the transposed midpoint and whose inverse
    # half retraces the chain. The payload/model accounting below
    # doubles per-exchange entries (out + back legs) and inserts the
    # ``t_mid`` stage when this is set. None = a plain transform.
    op: str | None = None

    @property
    def num_exchanges(self) -> int:
        n = {"single": 0, "slab": 1, "pencil": 2}[self.decomposition]
        # An operator chain retraces every exchange on the way back.
        return 2 * n if self.op else n


def spec_entries(mesh: Mesh, spec: P, ndim: int) -> tuple:
    """Validate a user PartitionSpec (rank, axis names) and return it padded
    to ``ndim`` entries."""
    entries = tuple(spec)
    if len(entries) > ndim:
        raise ValueError(
            f"PartitionSpec {spec} has more entries than the {ndim} array dims"
        )
    for entry in entries:
        if entry is None:
            continue
        for nm in entry if isinstance(entry, tuple) else (entry,):
            if nm not in mesh.shape:
                raise ValueError(
                    f"spec {spec} names unknown mesh axis {nm!r}; mesh axes: "
                    f"{tuple(mesh.shape)}"
                )
    return entries + (None,) * (ndim - len(entries))


def classify_layout(mesh: Mesh, spec: P) -> tuple[str, tuple]:
    """Classify a mesh-expressible 3D layout against the chain shapes.

    Returns ``("slab", (axis,))`` when a 1D mesh's axis shards exactly one
    array dim, ``("pencil", (row_dim, col_dim))`` when a 2D mesh's axes each
    shard exactly one distinct dim, and ``("other", ())`` for everything
    else (replicated dims, tupled axes, partial placements) — the layout
    detection step of heFFTe's planners (``heffte_plan_logic.cpp:162-245``
    checks ``is_pencils``; ``:265-408`` checks slabs).
    """
    entries = spec_entries(mesh, spec, 3)
    placement: dict = {}
    for d, e in enumerate(entries):
        if e is None:
            continue
        names = e if isinstance(e, tuple) else (e,)
        if len(names) != 1:
            return ("other", ())
        placement[names[0]] = d
    names = list(mesh.axis_names)
    if len(names) == 1 and set(placement) == set(names):
        return ("slab", (placement[names[0]],))
    if len(names) == 2 and set(placement) == set(names):
        return ("pencil", (placement[names[0]], placement[names[1]]))
    return ("other", ())


def choose_decomposition(shape: Sequence[int], ndev: int) -> str:
    """Pick slab vs pencil for ``ndev`` devices with no mesh constraint.

    A slab plan moves the whole world once; a pencil plan moves it twice but
    each FFT stage operates on full lines with ndev-way batching on both
    remaining axes. The slab plan stops scaling when devices outnumber the
    planes of the first axis (each device must own >= 1 X-plane and >= 1
    Y-plane) — the point where the reference would *shrink the device count*
    (``getProperDeviceNum``, ``fft_mpi_3d_api.cpp:232-272``) and heFFTe
    would switch to pencils (``use_pencils``, ``heffte_plan_logic.h:69-89``).
    """
    n0, n1, _ = shape
    if ndev <= 1:
        return "single"
    if ndev <= min(n0, n1):
        return "slab"
    return "pencil"


def negotiate_device_count(
    shape: Sequence[int], ndev: int, decomposition: str = "slab", *,
    slab_axes: tuple[int, int] | None = None,
    perm: tuple[int, int, int] | None = None,
    order: str | None = None,
) -> int:
    """Largest device count <= ``ndev`` whose slabs/pencils divide the split
    axes evenly — the reference's device-count renegotiation
    (``getProperDeviceNum``, ``fft_mpi_3d_api.cpp:232-272``: when N0 %
    devices != 0 it *shrinks* the device count until slabs divide).

    On TPU the padded-exchange path makes uneven shapes correct anyway, so
    this is an *optimization* choice, not a correctness one; see
    ``PlanOptions.renegotiate`` for how :func:`logic_plan3d` applies it.
    """
    shape = tuple(int(s) for s in shape)
    if decomposition == "slab":
        a0, a1 = slab_axes if slab_axes is not None else (0, 1)
        start = min(ndev, shape[a0], shape[a1])
    else:
        start = ndev
    for p in range(start, 0, -1):
        if all(shape[a] % parts == 0
               for a, parts in _chain_pad_axes(shape, decomposition, p,
                                               slab_axes=slab_axes,
                                               perm=perm, order=order)):
            return p
    return 1


def _chain_pad_axes(
    shape, decomposition: str, p: int, *,
    slab_axes: tuple[int, int] | None = None,
    perm: tuple[int, int, int] | None = None,
    order: str | None = None,
) -> list[tuple[int, int]]:
    """(array_axis, parts) pairs the chain ceil-pads at device count ``p`` —
    the padding surface the renegotiation decision must judge. Uses the
    ACTUAL chain axes (post layout absorption), not the canonical ones."""
    if decomposition == "slab":
        in_axis, out_axis = slab_axes if slab_axes is not None else (0, 1)
        return [(in_axis, p), (out_axis, p)]
    rows, cols = native.pencil_grid(shape, p)
    a, b, c = perm if perm is not None else (0, 1, 2)
    pairs = [(a, rows), (b, cols)]  # input-side shard pads
    if (order or "col_first") == "col_first":
        pairs += [(c, cols), (b, rows)]  # exchange split-axis pads
    else:
        pairs += [(c, rows), (a, cols)]
    return pairs


def _apply_renegotiation(
    shape: tuple[int, int, int], ndev: int, decomp: str, mode: str, *,
    slab_axes: tuple[int, int] | None = None,
    perm: tuple[int, int, int] | None = None,
    order: str | None = None,
) -> tuple[int, tuple | None]:
    """Resolve the device count to actually use, judged on the actual chain
    axes (after layout absorption). Returns (count, record) where record =
    (requested, used, reason) for ``plan_info``."""
    if mode == "never" or ndev <= 1 or decomp == "single":
        return ndev, None
    neg = negotiate_device_count(shape, ndev, decomp,
                                 slab_axes=slab_axes, perm=perm, order=order)
    if neg == ndev:
        return ndev, None
    if mode == "force":
        return neg, (ndev, neg, "forced: largest evenly-dividing count")
    # "auto": shrink only when per-device padded compute does not grow —
    # i.e. the ceil-shard extents stay the same on every chain axis, so
    # dropping devices only removes padding (a strict win: same compute per
    # device, less padded exchange payload, fewer participants).
    old = _chain_pad_axes(shape, decomp, ndev,
                          slab_axes=slab_axes, perm=perm, order=order)
    new = _chain_pad_axes(shape, decomp, neg,
                          slab_axes=slab_axes, perm=perm, order=order)
    free = all(
        geo.ceil_shards(shape[a0], p1) == geo.ceil_shards(shape[a0], p0)
        for (a0, p0), (_, p1) in zip(old, new)
    )
    if free:
        return neg, (ndev, neg, "auto: even shards at equal per-device compute")
    return ndev, (
        ndev, ndev,
        f"kept: shrinking to {neg} evenly-dividing devices would raise "
        "per-device compute more than the padding it removes",
    )


def logic_plan3d(
    shape: Sequence[int],
    mesh: Mesh | int | None,
    options: PlanOptions = DEFAULT_OPTIONS,
    *,
    forward: bool = True,
    in_spec: P | None = None,
    out_spec: P | None = None,
    batch: int | None = None,
) -> LogicPlan:
    """Resolve (shape, mesh-or-device-count, options, layouts) to a concrete
    plan skeleton. The role of ``plan_operations``
    (``heffte_plan_logic.cpp:410-432``): all geometry decisions happen here,
    and the builders in :mod:`.parallel` only execute them.

    ``batch=B`` records a leading batch axis of B coalesced transforms
    (:class:`LogicPlan.batch`); decomposition/mesh decisions are
    per-transform, but the overlap-K auto heuristic sees the B-fold
    per-device block.

    ``mesh`` may be ``None`` (single device), an int device count (the mesh
    is built here, shaped by the chosen decomposition — pencil grids come
    from the min-surface search, and the device count may be renegotiated
    per ``options.renegotiate``), or an existing :class:`Mesh` (1D -> slab,
    2D -> pencil; the mesh wins over ``options.decomposition == "auto"``).

    ``in_spec`` / ``out_spec`` are the caller's layouts (this plan's own
    orientation). When one classifies as a slab/pencil layout of the mesh,
    the stage chain is re-axed to *start (or end) right there*, eliminating
    the edge reshard — heFFTe's reshape minimization
    (``heffte_plan_logic.cpp:162-245,265-408``). Unabsorbable layouts are
    reported via ``in_absorbed``/``out_absorbed`` and handled by the caller
    with an edge reshard.
    """
    shape = tuple(int(s) for s in shape)
    batch = check_batch(batch)
    decomp = options.decomposition
    negotiated = None
    requested = None  # device count requested as an int (renegotiable)

    hier = options.algorithm == "hierarchical"
    if hier:
        # The two-leg ICI/DCN transport runs the slab chain (ONE logical
        # exchange, decomposed into two axis-local legs) over a hybrid
        # 2D mesh whose axes are the two fabrics — a pencil chain's
        # exchanges are each axis-local already, so there is nothing for
        # the hierarchical transport to split there.
        if not isinstance(mesh, Mesh) or len(mesh.axis_names) != 2:
            raise ValueError(
                "algorithm='hierarchical' requires an explicit 2D hybrid "
                "(dcn x ici) Mesh (e.g. multihost.make_hybrid_mesh()); "
                f"got {mesh!r}")
        if decomp not in ("auto", "slab"):
            raise ValueError(
                "hierarchical transport runs the slab chain over the "
                f"combined hybrid axis; decomposition={decomp!r} is not "
                "compatible")
        decomp = "slab"

    if isinstance(mesh, int):
        requested = ndev = mesh
        if decomp == "auto":
            decomp = choose_decomposition(shape, ndev)
        if decomp == "single" or ndev == 1:
            mesh = None
            decomp = "single"
        elif decomp == "slab":
            mesh = make_mesh(ndev)
        else:  # pencil: min-surface grid (rows over axis 0, cols over axis 1)
            r, c = native.pencil_grid(shape, ndev)
            mesh = make_mesh((r, c))

    if decomp == "single":  # explicit request wins over any provided mesh
        mesh = None
    if mesh is None or math.prod(mesh.devices.shape) == 1:
        decomp = "single"
        mesh = None
    elif decomp == "auto":
        decomp = "pencil" if len(mesh.axis_names) == 2 else "slab"

    if (decomp == "slab" and mesh is not None
            and len(mesh.axis_names) != 1 and not hier):
        raise ValueError("slab decomposition requires a 1D mesh")
    if decomp == "pencil" and mesh is not None and len(mesh.axis_names) != 2:
        raise ValueError("pencil decomposition requires a 2D mesh")

    # ---- axis assignment (reshape minimization) ----
    # The hierarchical slab chain runs over the COMBINED hybrid axis, so
    # 2D-mesh layout classification (which would read the mesh as a
    # pencil grid) does not apply — unabsorbable layouts get the edge
    # reshard exactly like any other non-chain layout.
    kin = classify_layout(mesh, in_spec) if (
        mesh is not None and in_spec is not None and not hier) else None
    kout = classify_layout(mesh, out_spec) if (
        mesh is not None and out_spec is not None and not hier) else None
    slab_axes = None
    perm = order = None
    in_absorbed = in_spec is None or mesh is None
    out_absorbed = out_spec is None or mesh is None

    if decomp == "slab" and mesh is not None:
        default_in, default_out = (0, 1) if forward else (1, 0)
        if kin is not None and kin[0] == "slab":
            in_axis = kin[1][0]
            in_absorbed = True
        else:
            in_axis = default_in
        if kout is not None and kout[0] == "slab" and kout[1][0] != in_axis:
            out_axis = kout[1][0]
            out_absorbed = True
        else:
            out_axis = default_out if default_out != in_axis else default_in
        slab_axes = (in_axis, out_axis)
    elif decomp == "pencil" and mesh is not None:
        default_perm = (0, 1, 2) if forward else (1, 2, 0)
        default_order = "col_first" if forward else "row_first"
        if kin is not None and kin[0] == "pencil":
            a, b = kin[1]
            perm = (a, b, 3 - a - b)
            in_absorbed = True
        else:
            perm = default_perm
        # The two exchange orders reach two different output layouts; pick
        # the one matching the caller's out_spec when possible.
        col_first_out = (perm[1], perm[2])  # (row_dim, col_dim)
        row_first_out = (perm[2], perm[0])
        if kout is not None and kout[0] == "pencil":
            if kout[1] == col_first_out:
                order, out_absorbed = "col_first", True
            elif kout[1] == row_first_out:
                order, out_absorbed = "row_first", True
            else:
                order = default_order
        else:
            order = default_order

    # ---- device-count renegotiation (int-mesh requests only), judged on
    # the ACTUAL chain axes chosen above ----
    if requested is not None and mesh is not None:
        used, negotiated = _apply_renegotiation(
            shape, requested, decomp, options.renegotiate,
            slab_axes=slab_axes, perm=perm, order=order,
        )
        if used != requested:
            if used == 1 and (in_spec is not None or out_spec is not None):
                # Layout-carrying plans need a mesh; keep the request.
                negotiated = (requested, requested,
                              "kept: in_spec/out_spec require a mesh")
            elif used == 1:
                mesh = None
                decomp = "single"
                slab_axes = perm = order = None
            elif decomp == "slab":
                mesh = make_mesh(used)
            else:
                r, c = native.pencil_grid(shape, used)
                mesh = make_mesh((r, c))

    stages = stage_layouts(
        decomp, mesh, geo.world_box(shape),
        slab_axes=slab_axes, pencil_perm=perm, pencil_order=order,
    )
    # Resolve the overlap knob (None -> DFFT_OVERLAP env, "auto" ->
    # block-bytes heuristic) to a concrete K on the FINAL mesh, so the
    # builders and plan_info see one int. Single-device chains have no
    # exchange to overlap.
    overlap = 1 if (decomp == "single" or mesh is None) else (
        resolve_overlap_chunks(
            options.overlap_chunks, shape=shape,
            ndev=math.prod(mesh.devices.shape),
            # A batched chain's per-device block is B-fold, which is what
            # the "auto" block-bytes crossover must judge.
            itemsize=8 * (batch or 1)))
    # Resolve the wire-compression knob (None -> DFFT_WIRE_DTYPE env) to
    # a concrete mode; single-device chains have no wire to compress.
    wire = None if (decomp == "single" or mesh is None) else (
        resolve_wire_dtype(options.wire_dtype))
    return LogicPlan(
        shape=shape, decomposition=decomp, mesh=mesh,
        options=replace(options, decomposition=decomp,
                        overlap_chunks=overlap, wire_dtype=wire),
        forward=forward,
        slab_axes=slab_axes, pencil_perm=perm, pencil_order=order,
        in_absorbed=in_absorbed, out_absorbed=out_absorbed,
        negotiated=negotiated, stages=stages, batch=batch,
    )


# ------------------------------------------------------------ executors

def resolve_options(
    executor: str | None,
    options: PlanOptions | None,
    *,
    decomposition: str | None = None,
    algorithm: str = "alltoall",
    donate: bool = False,
    overlap_chunks: int | str | None = None,
    tune: str | None = None,
    wire_dtype: str | None = None,
    max_roundtrip_err: float | None = None,
    mm_precision: str | None = None,
    mm_complex: str | None = None,
    fuse: bool | str | None = None,
) -> tuple[PlanOptions, bool]:
    """A planner's options, and whether they name its executor.

    ``options`` names the executor it holds. Otherwise ``executor`` does,
    and ``DFFT_EXECUTOR`` (unless unset, ``0`` or ``none``) stands in
    for an ``executor`` of ``None`` or ``"xla"``: the escape hatch for
    environments whose XLA FFT lowering is broken (``matmul`` routes
    every such plan through the thunk-free MXU engine). With nothing
    named the options hold ``"xla"`` until :func:`resolve_executor`
    settles the default on the logic plan. The label is normalized by
    :func:`..ops.executors.normalize_label`."""
    from .ops.executors import normalize_label

    if options is not None:
        if (decomposition is not None or executor not in (None, "xla")
                or donate or algorithm != "alltoall"
                or overlap_chunks is not None or tune is not None
                or wire_dtype is not None or max_roundtrip_err is not None
                or mm_precision is not None or mm_complex is not None
                or fuse is not None):
            raise ValueError(
                "pass either options= or individual plan keywords, not both"
            )
        return normalize_label(options), True
    if executor in (None, "xla"):
        env = os.environ.get("DFFT_EXECUTOR", "").strip()
        if env not in ("", "0", "none"):
            executor = env
    return normalize_label(PlanOptions(
        decomposition=decomposition or "auto",
        algorithm=algorithm,
        executor=executor or "xla",
        donate=donate,
        overlap_chunks=overlap_chunks,
        tune=tune,
        wire_dtype=wire_dtype,
        max_roundtrip_err=max_roundtrip_err,
        mm_precision=mm_precision,
        mm_complex=mm_complex,
        fuse=fuse,
    )), executor is not None


def _on_tpu() -> bool:
    """The backend predicate of the executor default."""
    return jax.default_backend() == "tpu"


def _thunk_guard(executor: str, lp: LogicPlan) -> str:
    """The XLA:CPU fft-thunk retirement path on a logic plan
    (:func:`..ops.executors.thunk_guard_substitute` is the shared
    predicate; the staged pipeline builders apply the same rule). A
    chain is uneven when some stage ceil-pads (shards of unequal
    shape): the even pencil chains run the thunk cleanly. The slab class
    is the minor-axis starved chain only: input slabs on axis 2 with
    zero-extent shards. Starved chains on the major axes run the thunk
    fine, and substituting there would break the executor-sensitive
    bitwise-parity contracts for no protection."""
    from .ops.executors import thunk_guard_substitute

    if lp.mesh is None:
        return executor
    uneven = any(len({b.shape for b in boxes}) > 1
                 for _axes, boxes in lp.stages)
    starved = bool(
        lp.decomposition == "slab" and lp.slab_axes
        and lp.slab_axes[0] == 2
        and any(0 in b.shape for b in lp.stages[0][1]))
    return thunk_guard_substitute(
        executor, decomposition=lp.decomposition, forward=lp.forward,
        uneven=uneven, starved=starved)


def _local_fft_blocks(lp: LogicPlan) -> list | None:
    """Every local FFT of a c2c chain as a ``(block shape, axis)``: the
    whole grid on each axis on one device. On a flat slab, t0's block
    (the in-axis shard, the other two axes whole) on both of its axes,
    and t3's block (the in-axis whole, the out-axis shard, each of the
    overlap chunks of the third axis) on the in-axis. ``None`` for
    chains never run on the plane passes: pencils and the hierarchical
    transport's (dcn, ici) slab."""
    from .parallel.exchange import overlap_chunk_bounds

    shape = lp.shape
    if lp.decomposition == "single":
        return [(shape, ax) for ax in range(3)]
    if lp.decomposition != "slab" or len(lp.mesh.axis_names) != 1:
        return None
    parts = math.prod(lp.mesh.devices.shape)
    i, o = lp.slab_axes
    c = 3 - i - o
    t0 = list(shape)
    t0[i] = -(-shape[i] // parts)
    blocks = [(tuple(t0), ax) for ax in range(3) if ax != i]
    for lo, hi in overlap_chunk_bounds(shape[c], lp.options.overlap_chunks):
        t3 = list(shape)
        t3[o] = -(-shape[o] // parts)
        t3[c] = hi - lo
        blocks.append((tuple(t3), i))
    return blocks


def resolve_executor(requested: str | None, lp: LogicPlan, *, dtype,
                     transform: str = "c2c") -> tuple[str, str]:
    """The executor a plan reports and the label its builders run.

    ``requested`` is the executor the caller named (:func:`resolve_options`),
    ``None`` if none. The fft-thunk guard comes first (:func:`_thunk_guard`:
    an XLA-family executor on a poisoned CPU chain runs its substitute).
    A named executor then runs as named. With nothing named, an unbatched
    complex64 c2c transform (not an operator) on the TPU whose every local
    FFT takes a plane pass (:func:`_local_fft_blocks`,
    ``pallas_fft.plane_pass_eligible``) reports ``pallas`` and runs
    ``pallas:highest``: exact, as XLA's FFT is, whatever
    ``DFFT_MM_PRECISION`` says; counted in ``c2c_default_pallas``.
    Everything else runs ``xla``. The Pallas import comes after the cheap
    gates, so plans that cannot take the kernels never pay it."""
    name = _thunk_guard(requested or "xla", lp)
    if requested is not None or name != "xla":
        return name, name
    if (transform != "c2c" or lp.op is not None or lp.batch is not None
            or jnp.dtype(dtype) != jnp.complex64 or not _on_tpu()):
        return name, name
    blocks = _local_fft_blocks(lp)
    if blocks is None:
        return name, name
    from .ops import pallas_fft

    if not all(pallas_fft.plane_pass_eligible(b, ax) for b, ax in blocks):
        return name, name
    from .utils import metrics as _metrics

    _metrics.inc("c2c_default_pallas", decomposition=lp.decomposition,
                 shape="x".join(map(str, lp.shape)))
    return "pallas", "pallas:highest"


def _grid_boxes(
    world: geo.Box3, placements: dict[int, int], *, rule=geo.ceil_splits,
    major_dim: int | None = None,
) -> tuple:
    """Boxes of a layout sharding ``placements`` = {array_dim: parts},
    ordered with ``major_dim``'s chunk index slowest (mesh row-major device
    order). With one entry this is a slab split; with two, a pencil grid."""
    dims = sorted(placements)
    if major_dim is not None and dims[0] != major_dim:
        dims = [major_dim] + [d for d in dims if d != major_dim]
    chunks = {
        d: [
            (world.low[d] + a, world.low[d] + b)
            for a, b in rule(world.shape[d], placements[d])
        ]
        for d in dims
    }
    import itertools

    boxes = []
    for combo in itertools.product(*(range(placements[d]) for d in dims)):
        low = list(world.low)
        high = list(world.high)
        for d, ci in zip(dims, combo):
            low[d], high[d] = chunks[d][ci]
        boxes.append(geo.Box3(tuple(low), tuple(high)))
    return tuple(boxes)


def stage_layouts(
    decomposition: str,
    mesh: Mesh | None,
    world: geo.Box3,
    *,
    slab_axes: tuple[int, int] | None = None,
    pencil_perm: tuple[int, int, int] | None = None,
    pencil_order: str | None = None,
) -> tuple:
    """The per-stage (fft_axes, boxes) layout chain of a decomposition over
    ``world`` — the single source of truth for box geometry (the 4-shape
    lists of ``logic_plan3d``, ``heffte_plan_logic.h:152-164``). Input side
    of the chain first, in the chain's own orientation."""
    if decomposition == "single" or mesh is None:
        return (((0, 1, 2), (world,)),)
    if decomposition == "slab":
        in_axis, out_axis = slab_axes if slab_axes is not None else (0, 1)
        # Product over every mesh axis: a 1D slab mesh has one, the
        # hierarchical slab chain's hybrid (dcn x ici) mesh has two
        # (their row-major linearization IS the combined slab axis).
        p = math.prod(mesh.shape[a] for a in mesh.axis_names)
        local_axes = tuple(a for a in range(3) if a != in_axis)
        return (
            (local_axes, _grid_boxes(world, {in_axis: p})),
            ((in_axis,), _grid_boxes(world, {out_axis: p})),
        )
    rows, cols = (mesh.shape[a] for a in mesh.axis_names[:2])
    a, b, c = pencil_perm if pencil_perm is not None else (0, 1, 2)
    order = pencil_order or "col_first"
    if order == "col_first":
        # fft c | exch col (c<->b) | fft b | exch row (b<->a) | fft a
        return (
            ((c,), _grid_boxes(world, {a: rows, b: cols}, major_dim=a)),
            ((b,), _grid_boxes(world, {a: rows, c: cols}, major_dim=a)),
            ((a,), _grid_boxes(world, {b: rows, c: cols}, major_dim=b)),
        )
    # row_first: fft c | exch row (c<->a) | fft a | exch col (a<->b) | fft b
    return (
        ((c,), _grid_boxes(world, {a: rows, b: cols}, major_dim=a)),
        ((a,), _grid_boxes(world, {c: rows, b: cols}, major_dim=c)),
        ((b,), _grid_boxes(world, {c: rows, a: cols}, major_dim=c)),
    )


def exchange_payloads(lp: LogicPlan, shape, itemsize: int) -> list[dict]:
    """Per-exchange payload accounting: the TRUE information moved versus
    the bytes each algorithm ships on the wire.

    A fused spectral-operator plan (``lp.op``) retraces every exchange on
    its inverse half, so its entry list is the forward chain's entries
    followed by their mirrors in reverse chain order (the return legs) —
    per-execute wire counters and the pruning model inherit the doubling
    from here. Mirror byte figures reuse the forward leg's (exact for the
    dense transports, whose padded volume is split/concat-symmetric; the
    ragged transport's uneven-world mirror differs only in which axis's
    ceil padding it strips).

    The reference sizes true payloads with exact per-peer count tables
    (``TransInfo``, ``fft_mpi_3d_api.cpp:84-133``; ``dfft_exchange_table``);
    on TPU the dense ``alltoall`` ships both split- and concat-axis ceil
    padding, ``alltoallv`` (ragged) strips the split-axis padding, and the
    concat-axis padding (the SPMD equal-shard layout itself) always
    travels. Entries: {stage, mesh_axis, parts, true_bytes,
    alltoall_bytes, alltoallv_bytes}.

    A batched plan (``lp.batch = B``) ships B transforms' payloads in ONE
    collective per stage — every byte entry scales by B (and the
    per-execute wire counters and the tuner's pruning model inherit that
    scaling from here), while ``parts``/launch counts do not.

    Every entry additionally carries ``link`` ("ici" | "dcn" — which
    fabric the entry's mesh axis rides, so the model prices each leg
    with the right bandwidth) and ``wire_factor`` (the on-wire byte
    scale of the plan's ``wire_dtype`` compression: 1.0 exact, 0.5 for
    c64 -> bf16 pairs — multiply any byte entry by it for the bytes
    actually on the wire). A hierarchical slab plan returns TWO entries
    (``t2a`` on the ICI axis, ``t2b`` on the DCN axis) — per-leg
    accounting of the one logical exchange.
    """
    if lp.mesh is None:
        return []

    def _done(entries: list[dict]) -> list[dict]:
        # Operator chains pay every exchange twice (out + back).
        if getattr(lp, "op", None):
            return entries + [dict(e) for e in reversed(entries)]
        return entries

    shape = tuple(int(s) for s in shape)
    bsz = getattr(lp, "batch", None) or 1
    pad = lambda n, k: k * (-(-n // k))
    wf = wire_itemsize(itemsize, lp.options.wire_dtype) / itemsize
    link = lambda ax: "dcn" if str(ax) == "dcn" else "ici"
    out = []
    if lp.decomposition == "slab":
        names = lp.mesh.axis_names
        p = math.prod(lp.mesh.shape[a] for a in names)
        a_in, a_out = lp.slab_axes if lp.slab_axes else (0, 1)
        oth = 3 - a_in - a_out
        n_in, n_out, n_oth = shape[a_in], shape[a_out], shape[oth]
        if lp.options.algorithm == "hierarchical" and len(names) == 2:
            # Two axis-local legs of the one logical exchange: each leg
            # is a dense tiled all-to-all over ITS axis of the padded
            # block, so each leg ships fraction (parts-1)/parts of the
            # padded world on its own fabric.
            dcn_name, ici_name = names
            padded = pad(n_in, p) * pad(n_out, p) * n_oth
            truev = n_in * n_out * n_oth
            for stage, ax_name, parts in (
                    ("t2a", ici_name, lp.mesh.shape[ici_name]),
                    ("t2b", dcn_name, lp.mesh.shape[dcn_name])):
                f = (parts - 1) / parts
                dense = int(padded * f * itemsize * bsz)
                out.append({
                    "stage": stage, "mesh_axis": ax_name, "parts": parts,
                    "link": link(ax_name), "wire_factor": wf,
                    "true_bytes": int(truev * f * itemsize * bsz),
                    "alltoall_bytes": dense,
                    "alltoallv_bytes": dense,  # each leg is dense
                })
            return _done(out)
        f = (p - 1) / p
        out.append({
            "stage": "t2", "mesh_axis": names[0], "parts": p,
            "link": link(names[0]), "wire_factor": wf,
            "true_bytes": int(n_in * n_out * n_oth * f * itemsize * bsz),
            "alltoall_bytes": int(pad(n_in, p) * pad(n_out, p) * n_oth * f
                                  * itemsize * bsz),
            "alltoallv_bytes": int(pad(n_in, p) * n_out * n_oth * f
                                   * itemsize * bsz),
        })
        return _done(out)
    rows, cols = (lp.mesh.shape[ax] for ax in lp.mesh.axis_names[:2])
    a, b, c = lp.pencil_perm if lp.pencil_perm else (0, 1, 2)
    order = lp.pencil_order or "col_first"
    # (stage, mesh_axis_idx, parts, split_axis, padded extents of the two
    # non-split axes at that stage)
    pa, pb = pad(shape[a], rows), pad(shape[b], cols)
    if order == "col_first":
        pc = pad(shape[c], cols)
        seq = [("t2a", 1, cols, c, pa * pb), ("t2b", 0, rows, b, pa * pc)]
    else:
        pc = pad(shape[c], rows)
        seq = [("t2a", 0, rows, c, pa * pb), ("t2b", 1, cols, a, pc * pb)]
    true_vol = shape[0] * shape[1] * shape[2]
    for stage, ax_i, parts, split, bystander_padded in seq:
        f = (parts - 1) / parts
        out.append({
            "stage": stage, "mesh_axis": lp.mesh.axis_names[ax_i],
            "parts": parts,
            "link": link(lp.mesh.axis_names[ax_i]), "wire_factor": wf,
            "true_bytes": int(true_vol * f * itemsize * bsz),
            "alltoall_bytes": int(bystander_padded * pad(shape[split], parts)
                                  * f * itemsize * bsz),
            "alltoallv_bytes": int(bystander_padded * shape[split] * f
                                   * itemsize * bsz),
        })
    return _done(out)


def mm_dft_flops(shape: Sequence[int], axes: Sequence[int] | None = None,
                 ) -> float:
    """Real flops of one dense-tier matmul-DFT transform over ``axes``
    (default: all three): each transformed axis is one complex
    contraction of the whole block against an n x n DFT matrix — N*n
    complex MACs = ``8*N*n`` real flops per axis. The four-step split
    spends fewer flops above the dense bound, so this is the
    conservative (dense) figure — a RANKING quantity for the
    precision-tier cost model (:func:`..tuner.mm_tier_tflops`), not a
    prediction."""
    shape = tuple(int(s) for s in shape)
    n_total = math.prod(shape)
    return sum(8.0 * n_total * shape[a] for a in (axes or range(3)))


def model_stage_seconds(
    lp: LogicPlan,
    shape: Sequence[int],
    itemsize: int,
    *,
    hbm_gbps: float,
    wire_gbps: float,
    launch_seconds: float,
    algorithm: str | None = None,
    overlap_chunks: int | None = None,
    exchange_correction: float = 1.0,
    dcn_gbps: float | None = None,
    mm_tflops: float | None = None,
    concurrent_hide_seconds: float = 0.0,
    hide_correction: float = 1.0,
    fused: Sequence[str] = (),
) -> dict:
    """Per-stage analytical prediction of one execution, keyed exactly
    ``t0..t3`` — the model side of the explain/attribution join. A fused
    spectral-operator plan (``lp.op``) additionally carries the
    ``t_mid`` midpoint stage (final forward FFT + pointwise multiply +
    first inverse FFT in the transposed layout) and prices BOTH legs of
    every exchange (``exchange_payloads`` doubles the entries).

    ``exchange_correction`` scales every exchange's modeled seconds (not
    its byte accounting): the persisted per-(device_kind, transport)
    measured/model ratio of the calibrated hardware profile
    (:func:`..calibrate.model_correction`), so a transport the ideal
    wire model consistently underprices on this fabric is predicted —
    and divergence-gated — at its observed cost.

    FFT stages are the HBM-stream roofline (each axis pass reads and
    writes the per-device block once — the 3-pass bound of
    ``docs/MFU_ANALYSIS.md``); exchanges are wire bytes under the plan's
    transport (:func:`exchange_payloads` +
    :func:`..parallel.exchange.exchange_model_seconds`) with the
    overlap-K exposure crossover, each exchange hiding under its own
    downstream FFT stage. Stage taxonomy: ``t0`` = input-side FFT pass
    (two local axes for slab, one for pencil), ``t1`` = the pencil
    chain's mid FFT (zero for slab/single — the pack is fused into the
    exchange by XLA), ``t2`` = every exchange's *exposed* time, ``t3`` =
    the output-side FFT pass. Every entry carries ``seconds`` plus the
    quantities it was derived from (``flops``, ``hbm_bytes``,
    ``wire_bytes``) so MFU/utilization ratios need no re-derivation.

    A batched plan (``lp.batch = B``) scales every per-stage quantity by
    B — B-fold FFT flops and HBM stream, B-fold exchange payload through
    :func:`exchange_payloads` — while collective launch counts stay at
    the unbatched plan's (the batched win the tuner's pruning and the
    explain attribution must both price honestly).

    ``mm_tflops`` prices the plan's FFT stages as matmul-DFT
    contractions at that MXU rate (the executor's precision tier —
    :func:`..tuner.mm_tier_tflops`): each stage's seconds become
    ``max(HBM stream, mm_flops / rate)`` and the entry carries
    ``mm_flops``, so the explain join and the pruning model both rank
    bf16 vs f32 vs exact tiers before any compile. ``None`` (the
    default, and every non-matmul executor) keeps the pure HBM
    roofline — byte-identical model output.

    ``concurrent_hide_seconds`` adds OTHER transforms' compute to every
    exchange's hide budget — the cross-transform hide of a
    :func:`..stagegraph.schedule_concurrent` program, priced exactly
    the way the leg pipeline prices the DCN leg under the ICI leg's
    hide: extra downstream work the wire transfer can overlap with.
    :func:`model_concurrent_seconds` derives it per transform from its
    co-scheduled peers; 0.0 (the default) is the single-transform
    model, numerically unchanged.

    ``hide_correction`` scales every exchange's hide budget — the
    measured/model *realized-overlap* ratio the monitor's dispatch
    attribution persists (:func:`..calibrate.model_correction` keys
    ``"leg_hide"``/``"concurrent_hide"``), so a schedule whose measured
    interleave achieves less hide than the ideal model assumes is
    priced — and auto-width/auto-K ranked — at its observed overlap.
    1.0 (the default) is the uncorrected model, numerically
    unchanged.

    ``fused`` names stages the Pallas fusion tier fuses with the wire
    codec (:func:`fused_model_stages`): the codec pack/unpack happens
    in-register inside the stage kernel, so the intermediate c64 block
    the unfused chain streams between stage and codec is replaced by
    the WIRE form — each read+write pass pair (2·block) becomes
    (1 + wire_factor)·block. Flops are unchanged (fusion moves bytes,
    not math); the mm_tflops compute floor still applies. ``()`` (the
    default) is the unfused model, numerically unchanged."""
    shape = tuple(int(s) for s in shape)
    ndev = 1 if lp.mesh is None else math.prod(lp.mesh.devices.shape)
    bsz = getattr(lp, "batch", None) or 1
    n_total = math.prod(shape) * bsz
    block_bytes = itemsize * n_total / ndev
    alg = algorithm or lp.options.algorithm
    k = overlap_chunks
    if k is None:
        oc = lp.options.overlap_chunks
        k = oc if isinstance(oc, int) else 1

    def fft_stage(axes) -> dict:
        hbm = 2.0 * block_bytes * len(axes)  # read + write per axis pass
        flops = sum(5.0 * n_total * math.log2(max(2, shape[a]))
                    for a in axes) / ndev
        out = {"seconds": hbm / (hbm_gbps * 1e9), "flops": flops,
               "hbm_bytes": hbm, "wire_bytes": 0.0}
        if mm_tflops:
            # Matmul-DFT pricing at the tier's rate; the HBM stream
            # stays the floor (a memory-bound stage cannot be bought
            # faster by a cheaper tier).
            mm = mm_dft_flops(shape, axes) * bsz / ndev
            out["mm_flops"] = mm
            out["seconds"] = max(out["seconds"], mm / (mm_tflops * 1e12))
        return out

    zero = {"seconds": 0.0, "flops": 0.0, "hbm_bytes": 0.0,
            "wire_bytes": 0.0}
    op_chain = bool(getattr(lp, "op", None))
    if op_chain:
        # Fused spectral-operator taxonomy (canonical chains only): t0 =
        # forward input-side pass(es), t1 = the pencil chain's forward
        # mid FFT, t2 = every exchange's exposed time (out AND back legs
        # — exchange_payloads doubles the entries), t_mid = the
        # transposed-midpoint stage (final forward FFT + the pointwise
        # multiply + first inverse FFT), t3 = the inverse passes back to
        # the input layout.
        mid = fft_stage((0, 0))  # forward + inverse pass of the mid axis
        pw = 2.0 * block_bytes   # pointwise multiply: read + write once
        mid["hbm_bytes"] += pw
        mid["seconds"] += pw / (hbm_gbps * 1e9)
        mid["flops"] += 6.0 * n_total / ndev  # one complex multiply/elem
        if lp.decomposition == "pencil" and lp.mesh is not None:
            out = {"t0": fft_stage((2,)), "t1": fft_stage((1,)),
                   "t2": dict(zero), "t_mid": mid,
                   "t3": fft_stage((1, 2))}
        else:  # slab and single-device fused chains share the shape
            out = {"t0": fft_stage((1, 2)), "t1": dict(zero),
                   "t2": dict(zero), "t_mid": mid,
                   "t3": fft_stage((1, 2))}
    elif lp.decomposition == "single" or lp.mesh is None:
        # The staged single pipeline splits the whole-cube transform into
        # t0 (YZ planes) and t3 (X lines); no pack, no exchange.
        out = {"t0": fft_stage((1, 2)), "t1": dict(zero),
               "t2": dict(zero), "t3": fft_stage((0,))}
    elif lp.decomposition == "slab":
        fft_stages = [s[0] for s in lp.stages]
        out = {"t0": fft_stage(fft_stages[0]), "t1": dict(zero),
               "t2": dict(zero), "t3": fft_stage(fft_stages[1])}
    else:
        fft_stages = [s[0] for s in lp.stages]
        out = {"t0": fft_stage(fft_stages[0]),
               "t1": fft_stage(fft_stages[1]),
               "t2": dict(zero), "t3": fft_stage(fft_stages[2])}

    if fused:
        from .parallel.exchange import wire_itemsize

        wf = wire_itemsize(itemsize, lp.options.wire_dtype) / float(itemsize)
        for st in fused:
            # A fused stage's exchange-facing stream is the WIRE form:
            # each of the stage's read+write pass pairs (2·block) keeps
            # one c64 stream and trades the other — the intermediate
            # block the unfused chain hands the codec — for wire bytes,
            # so 2·block -> (1 + wire_factor)·block per pass.
            e = out.get(st)
            if not e or e["hbm_bytes"] <= 0.0 or wf >= 1.0:
                continue
            e["hbm_bytes"] *= (1.0 + wf) / 2.0
            e["seconds"] = e["hbm_bytes"] / (hbm_gbps * 1e9)
            if mm_tflops and e.get("mm_flops"):
                e["seconds"] = max(e["seconds"],
                                   e["mm_flops"] / (mm_tflops * 1e12))
            e["fused"] = True

    from .parallel.exchange import (
        WIRE_BYTE_KEYS, exchange_model_seconds,
    )

    # Each exchange hides under the FFT stage that consumes its output:
    # slab t2 -> t3; pencil t2a -> t1, t2b -> t3.
    payloads = exchange_payloads(lp, shape, itemsize)
    hide = {"t2": out["t3"]["seconds"], "t2a": out["t1"]["seconds"],
            "t2b": out["t3"]["seconds"]}
    if lp.decomposition == "slab":
        # A hierarchical slab plan's two legs both hide under t3 (the
        # pencil-style t2a/t2b taxonomy without a mid FFT stage).
        hide["t2a"] = hide["t2b"] = out["t3"]["seconds"]
    if op_chain:
        # Operator chains: the outbound exchange hides under t_mid, the
        # return one under t3 — per-entry attribution collapses to one
        # figure because mirrored entries share their stage names, so
        # each exchange hides under half the downstream compute.
        half = 0.5 * (out["t_mid"]["seconds"] + out["t3"]["seconds"])
        hide = {"t2": half, "t2a": half, "t2b": half}
    if concurrent_hide_seconds:
        # Cross-transform hide: a co-scheduled transform's FFT compute
        # is available to run under this transform's wire time — the
        # same shape as the leg pipeline's dcn_raw hide bonus below.
        hide = {k: v + float(concurrent_hide_seconds)
                for k, v in hide.items()}
    t2 = out["t2"]
    # Leg-level pipelining of the hierarchical transport at K > 1:
    # chunk i's ICI leg issues while chunk i-1's DCN leg and downstream
    # FFT run (exchange._hierarchical_pipelined), so the ICI leg's hide
    # budget additionally includes the DCN leg's raw transfer — the
    # per-leg overlap exposure the tuner's auto-K and pruning must
    # price. Computed from the t2b entry's raw (K-independent) time.
    leg_pipelined = alg == "hierarchical" and k > 1
    dcn_raw = 0.0
    if leg_pipelined:
        for e in payloads:
            if e["stage"] == "t2b":
                gb = (dcn_gbps if e.get("link") == "dcn" and dcn_gbps
                      else wire_gbps)
                wb = (e[WIRE_BYTE_KEYS[alg]] * e.get("wire_factor", 1.0)
                      / ndev)
                dcn_raw = exchange_model_seconds(
                    wb, e["parts"], alg, wire_gbps=gb,
                    launch_seconds=launch_seconds)["seconds"]
                break
    for e in payloads:
        # Per-leg link bandwidth: the DCN leg of a hierarchical (or
        # hybrid-mesh pencil) exchange is priced at the calibrated DCN
        # figure, not the ICI one. wire_factor scales for the plan's
        # on-wire compression (bf16 pairs halve c64 wire bytes; int8
        # block-scaled pairs quarter them).
        gbps = (dcn_gbps if e.get("link") == "dcn" and dcn_gbps
                else wire_gbps)
        wire = e[WIRE_BYTE_KEYS[alg]] * e.get("wire_factor", 1.0) / ndev
        hide_s = hide.get(e["stage"], 0.0)
        pipelined = leg_pipelined and e["stage"] == "t2a"
        if pipelined:
            hide_s += dcn_raw
        hide_s *= hide_correction
        m = exchange_model_seconds(
            wire, e["parts"], alg, wire_gbps=gbps,
            launch_seconds=launch_seconds, overlap_chunks=k,
            hide_seconds=hide_s)
        t2["seconds"] += m["exposed_seconds"] * exchange_correction
        t2["wire_bytes"] += wire
        t2.setdefault("raw_seconds", 0.0)
        t2["raw_seconds"] += m["seconds"] * exchange_correction
        t2.setdefault("steps", 0)
        t2["steps"] += m["steps"]
        # Per-leg attribution rows (the t2a/t2b join axis of explain):
        # one entry per exchange/leg with its own modeled time, hide
        # budget, and whether the leg pipeline hides it.
        t2.setdefault("legs", []).append({
            "stage": e["stage"], "mesh_axis": str(e["mesh_axis"]),
            "link": e.get("link", "ici"), "parts": e["parts"],
            "wire_bytes": wire, "wire_gbps": gbps,
            "seconds": m["exposed_seconds"] * exchange_correction,
            "raw_seconds": m["seconds"] * exchange_correction,
            "hide_seconds": hide_s, "leg_pipelined": pipelined,
        })
    return out


def model_concurrent_seconds(
    transforms: Sequence[tuple],
    *,
    hbm_gbps: float,
    wire_gbps: float,
    launch_seconds: float,
    dcn_gbps: float | None = None,
    **model_kw,
) -> dict:
    """Analytical price of a :func:`..stagegraph.schedule_concurrent`
    program over N independent transforms — the cross-transform-hide
    model of the DaggerFFT scheduling framing, built from
    :func:`model_stage_seconds` the way the leg pipeline prices the
    ICI leg under the DCN leg.

    ``transforms`` is a sequence of ``(lp, shape, itemsize)`` triples
    (one per co-scheduled transform). Each transform's exchanges are
    re-priced with ``concurrent_hide_seconds`` = the OTHER transforms'
    total FFT compute: the staggered schedule places peer compute
    between a transform's collective issue and its consumption, so the
    wire transfer overlaps it (there is no cross-transform data
    dependency). Returns::

        {"sequential_seconds": sum of solo models,
         "concurrent_seconds": compute sum + re-priced exposed wire,
         "hidden_seconds":     what the schedule removed,
         "speedup":            sequential / concurrent,
         "per_transform":      the N re-priced stage dicts}

    ``concurrent_seconds`` never exceeds ``sequential_seconds`` (a
    schedule can be priced as no worse than running serially), and with
    one transform the two are equal — the degenerate case IS the solo
    model."""
    transforms = list(transforms)
    kw = dict(hbm_gbps=hbm_gbps, wire_gbps=wire_gbps,
              launch_seconds=launch_seconds, dcn_gbps=dcn_gbps,
              **model_kw)

    def compute_s(m: dict) -> float:
        return sum(m[k]["seconds"] for k in m if k != "t2")

    def exposed_s(m: dict) -> float:
        return m["t2"]["seconds"]

    solo = [model_stage_seconds(
                lp, shape, itemsize,
                fused=fused_model_stages(lp, shape, itemsize), **kw)
            for lp, shape, itemsize in transforms]
    comp = [compute_s(m) for m in solo]
    total_comp = sum(comp)
    priced = [
        model_stage_seconds(
            lp, shape, itemsize,
            concurrent_hide_seconds=total_comp - comp[i],
            fused=fused_model_stages(lp, shape, itemsize), **kw)
        for i, (lp, shape, itemsize) in enumerate(transforms)
    ]
    sequential = sum(comp[i] + exposed_s(solo[i])
                     for i in range(len(solo)))
    concurrent = min(
        sequential,
        total_comp + sum(exposed_s(m) for m in priced))
    return {
        "sequential_seconds": sequential,
        "concurrent_seconds": concurrent,
        "hidden_seconds": sequential - concurrent,
        "speedup": (sequential / concurrent) if concurrent > 0 else 1.0,
        "per_transform": priced,
    }


def io_boxes(lp: LogicPlan, world_in: geo.Box3, world_out: geo.Box3) -> tuple:
    """Per-device input/output boxes of the plan's own orientation; r2c
    plans pass a shrunk complex-side world."""
    first = stage_layouts(
        lp.decomposition, lp.mesh, world_in,
        slab_axes=lp.slab_axes, pencil_perm=lp.pencil_perm,
        pencil_order=lp.pencil_order,
    )[0][1]
    last = stage_layouts(
        lp.decomposition, lp.mesh, world_out,
        slab_axes=lp.slab_axes, pencil_perm=lp.pencil_perm,
        pencil_order=lp.pencil_order,
    )[-1][1]
    return list(first), list(last)
