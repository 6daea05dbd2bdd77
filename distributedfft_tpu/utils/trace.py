"""Event tracing — the heFFTe tracing subsystem, TPU-native.

The reference gates RAII wall-clock events behind a compile flag and writes
one log file per MPI rank (``heffte/heffteBenchmark/include/heffte_trace.h:48-127``:
``add_trace name("...")`` objects record ``MPI_Wtime`` pairs;
``init_tracing``/``finalize_tracing`` manage a per-rank
``heffte_trace_<id>.log``). The first-party engine prints per-stage wall
deltas on every execute (``fft_mpi_3d_api.cpp:184-201``).

Here the same surface is a runtime-gated (env ``DFFT_TRACE=1`` or
:func:`init_tracing`) context manager that records host-side wall-clock
events per process, doubles as a ``jax.profiler.TraceAnnotation`` so events
land on the host lane of XLA profiler timelines too, and writes one log per
process (``jax.process_index`` plays the MPI-rank role on multi-host).

Two kinds of name, for two clocks:

- :func:`add_trace` spans are host spans around runtime work (``execute_*``,
  the serving queue, the staged pipelines' stage calls). Inside code that
  ``jax.jit`` traces they would time Python tracing, once per compile, and
  leave nothing in the compiled program.
- :func:`stage_scope` names the chain's ``t0..t3`` stages inside the traced
  program. It is a ``jax.named_scope``: every HLO op traced under it carries
  the name in its ``op_name`` metadata, which the profiler reports as each
  device op's ``tf_op`` (:mod:`.xplane` reads it back). It records no host
  event.
"""

from __future__ import annotations

import json
import os
import socket
import time
import math
from contextlib import contextmanager
from dataclasses import dataclass

import jax
import numpy as np

_MB = 1.0 / (1024 * 1024)

TRACE_FORMATS = ("log", "chrome")

_events: list[tuple[str, float, float]] | None = None
_trace_root: str | None = None
_native_rec = None  # native.NativeTrace when the C recorder is in use
_session = 0  # bumped by init/finalize: stale in-flight events are dropped
_format = "log"  # "log" (heFFTe per-rank text) | "chrome" (Perfetto JSON)
# Wall-clock anchor of the current session: events are perf_counter
# pairs; adding _epoch maps them onto the time.time() axis so Chrome
# traces from different processes of one job share a timeline.
_epoch = 0.0
# Tee buffer for capture_events(): spans are appended here IN ADDITION
# to (or instead of) the session recorder while a capture is active.
_capture: list[tuple[str, float, float]] | None = None

#: Default ring capacity of the in-memory Python recorder. Generous — a
#: bench campaign's worth of spans — but finite, so a long-lived serving
#: process with tracing armed reaches a steady footprint instead of
#: growing without bound. Override with ``DFFT_TRACE_MAX_EVENTS`` (0 =
#: unbounded, the pre-ring behavior).
DEFAULT_TRACE_MAX_EVENTS = 1 << 20

_max_events = DEFAULT_TRACE_MAX_EVENTS
_dropped = 0  # oldest-events evicted by the ring this session


def dropped_events() -> int:
    """Events evicted by the ring cap in the current trace session."""
    return _dropped


def _push(ev: list, name: str, start: float, stop: float) -> None:
    """Append one event, evicting the oldest past the ring cap. The ring
    keeps the NEWEST events (under a stall you want the spans nearest
    the incident, not the warm-up) and evicts a capacity/16 chunk at a
    time so the list-shift cost amortizes to O(1) per append."""
    global _dropped
    if _max_events and len(ev) >= _max_events:
        cut = max(1, len(ev) - _max_events + max(1, _max_events // 16))
        del ev[:cut]
        _dropped += cut
        from . import metrics as _metrics

        _metrics.inc("trace_dropped_events", cut)
    ev.append((name, start, stop))


def tracing_enabled() -> bool:
    return _events is not None or _native_rec is not None


def _try_native():
    """The C trace recorder (``dfft_trace_*``, ``native/dfft_native.cpp``)
    when the library is built — lower per-event overhead than the Python
    list (the compile-gated-to-zero-cost discipline of
    ``Heffte_ENABLE_TRACING``). ``DFFT_TRACE_NATIVE=0`` forces the Python
    recorder."""
    if os.environ.get("DFFT_TRACE_NATIVE", "1") == "0":
        return None
    try:
        from .. import native

        rec = native.NativeTrace()
        if not rec.available:
            return None
        rec.init()
        return rec
    except Exception:  # noqa: BLE001 — recorder is best-effort
        return None


def init_tracing(root: str = "", format: str | None = None) -> None:
    """Start collecting events (``init_tracing``, ``heffte_trace.h:90``).
    ``root`` prefixes the log filename written by :func:`finalize_tracing`.

    ``format`` (default: env ``DFFT_TRACE_FORMAT``, else ``"log"``) picks
    the output: ``"log"`` is the heFFTe per-rank text log, ``"chrome"`` a
    Chrome-trace/Perfetto JSON (load in ui.perfetto.dev, or merge across
    processes with ``python -m distributedfft_tpu.report``).

    Re-init while a session is open finalizes the open session first
    (writing its log) — its events are never silently discarded, and a
    native recorder is never dropped with events still buffered.
    """
    global _events, _trace_root, _native_rec, _session, _format, _epoch
    global _max_events, _dropped
    if tracing_enabled():
        finalize_tracing()
    fmt = format or os.environ.get("DFFT_TRACE_FORMAT", "") or "log"
    if fmt not in TRACE_FORMATS:
        raise ValueError(
            f"unknown trace format {fmt!r}; use one of {TRACE_FORMATS}")
    _session += 1
    _trace_root = root or "dfft_trace"
    _format = fmt
    _epoch = time.time() - time.perf_counter()
    try:
        _max_events = int(
            os.environ.get("DFFT_TRACE_MAX_EVENTS", "")
            or DEFAULT_TRACE_MAX_EVENTS)
    except ValueError:
        _max_events = DEFAULT_TRACE_MAX_EVENTS
    _dropped = 0
    # The C recorder dumps the text format only; chrome sessions use the
    # Python recorder (its event list is what the JSON writer serializes).
    _native_rec = _try_native() if fmt == "log" else None
    _events = None if _native_rec is not None else []


def _write_chrome(path: str, events, proc: int, nprocs: int) -> None:
    """Serialize one session's events as Chrome-trace JSON: a ``B``/``E``
    pair per event, ``pid`` = the process index (the MPI-rank role),
    ``ts`` in wall-clock microseconds so per-process files merge onto one
    timeline."""
    trace_events = []
    for name, start, stop in events:
        b = {"name": name, "cat": "dfft", "ph": "B", "pid": proc, "tid": 0,
             "ts": (start + _epoch) * 1e6}
        e = dict(b, ph="E", ts=(stop + _epoch) * 1e6)
        trace_events.extend((b, e))
    # Chrome requires in-order begin/end nesting per (pid, tid). Events
    # are appended at END time (inner before outer); a stable sort on ts
    # with B before E at ties restores begin order and keeps zero-length
    # inner pairs inside their enclosing span.
    trace_events.sort(key=lambda ev: (ev["ts"], ev["ph"] != "B"))
    with open(path, "w") as f:
        # Writer identity: the trace lane's pid is the jax process
        # index (the MPI-rank role), so the OS-level identity rides in
        # metadata — the fleet tooling (report merge --monitor-dir)
        # matches trace lanes to monitor streams through it.
        meta = {"process": proc, "process_count": nprocs,
                "host": socket.gethostname(), "os_pid": os.getpid()}
        if _dropped:
            meta["dropped_events"] = _dropped
        json.dump(
            {
                "displayTimeUnit": "ms",
                "metadata": meta,
                "traceEvents": trace_events,
            },
            f,
        )


def finalize_tracing() -> str | None:
    """Write ``<root>_<process>.log`` (or ``.json`` for the chrome
    format) and stop tracing (``finalize_tracing``,
    ``heffte_trace.h:98-118``). Returns the path."""
    global _events, _trace_root, _native_rec, _session, _dropped
    if not tracing_enabled():
        return None
    _session += 1
    proc, nprocs = jax.process_index(), jax.process_count()
    if _native_rec is not None:
        path = f"{_trace_root}_{proc}.log"
        ok = _native_rec.dump(path, proc, nprocs)
        if not ok:
            # Same contract as the Python recorder's open() raising: a
            # failed dump must not silently discard the events.
            raise OSError(f"native trace dump to {path!r} failed")
        _native_rec = None
    elif _format == "chrome":
        path = f"{_trace_root}_{proc}.json"
        _write_chrome(path, _events, proc, nprocs)
    else:
        path = f"{_trace_root}_{proc}.log"
        t0 = _events[0][1] if _events else 0.0
        with open(path, "w") as f:
            f.write(f"process {proc} of {nprocs}\n")
            if _dropped:
                # Ring-cap evictions, parsed back out by ``report merge``
                # so a truncated timeline is never mistaken for a full one.
                f.write(f"dropped_events {_dropped}\n")
            for name, start, stop in _events:
                f.write(f"{start - t0:14.6f}  {stop - start:12.6f}  {name}\n")
    _events, _trace_root = None, None
    _dropped = 0
    return path


if os.environ.get("DFFT_TRACE", "") not in ("", "0"):
    init_tracing(os.environ.get("DFFT_TRACE_ROOT", "dfft_trace"))


@contextmanager
def add_trace(name: str):
    """Record one named event (RAII ``add_trace``, ``heffte_trace.h:48-66``).

    Always annotates the host lane of the XLA profiler timeline; wall-clock
    capture only when tracing is initialized. It times host work: around a
    jitted call it brackets the *dispatch*, not device execution, and it
    must not be used inside code that ``jax.jit`` traces, where it would
    record one span of Python tracing per compile and nothing on the
    device. Name traced stages with :func:`stage_scope` instead.
    """
    with jax.profiler.TraceAnnotation(name):
        # The C recorder's event table is process-global, so binding the
        # Python handle alone cannot isolate an in-flight event from a
        # finalize/re-init happening inside the block: a stale event id
        # would land in the NEW session's table. The session generation
        # drops such events instead (for the Python recorder, binding the
        # list suffices — a stale append goes to the discarded list).
        sess = _session
        rec = _native_rec
        cap = _capture
        if rec is not None:
            eid = rec.begin(name)
            start = time.perf_counter() if cap is not None else 0.0
            try:
                yield
            finally:
                if cap is not None:
                    cap.append((name, start, time.perf_counter()))
                if _session == sess:
                    rec.end(eid)
            return
        ev = _events
        if ev is None and cap is None:
            yield
            return
        start = time.perf_counter()
        try:
            yield
        finally:
            stop = time.perf_counter()
            if ev is not None:
                _push(ev, name, start, stop)
            if cap is not None:
                cap.append((name, start, stop))


def record_span(name: str, start: float, stop: float) -> bool:
    """Record one ALREADY-COMPLETED event with explicit
    ``time.perf_counter()`` endpoints — the retroactive counterpart of
    :func:`add_trace` for spans whose start crossed a function boundary
    before anyone knew the span would exist (the serving tier's
    per-request queue-wait: the wait begins at ``submit`` but is only
    attributable when the flush fires). Returns True when the event was
    captured.

    Python recorder only: the C recorder's begin/end API cannot take
    explicit timestamps, and a retroactive span can by definition not
    annotate the XLA profiler timeline — callers that need the native
    path wrap live code in :func:`add_trace` instead."""
    ev = _events
    if ev is None:
        return False
    _push(ev, name, float(start), float(stop))
    return True


@contextmanager
def capture_events():
    """Tee: while the block is active, every Python-recorder span
    (:func:`add_trace`, :func:`stage_scope`) is ALSO appended to the yielded
    ``(name, start, stop)`` list — even when no trace session is open,
    and without consuming ring capacity from one that is. The overlap
    attribution path (:mod:`...monitor`) wraps one fresh program trace
    in this to read the dispatch interleave without arming or
    disturbing a global session. Captures nest (inner shadows outer);
    the buffer is process-global, so concurrent captures from other
    threads land in the innermost active one."""
    global _capture
    prev = _capture
    buf: list[tuple[str, float, float]] = []
    _capture = buf
    try:
        yield buf
    finally:
        _capture = prev


@contextmanager
def stage_scope(name: str):
    """Name one stage of a traced chain program (``t0_fft_yz``,
    ``t2_exchange_slab[1]``, ``cc0:t3_fft_x``): a ``jax.named_scope``,
    so every HLO op traced inside carries ``name`` in its ``op_name``
    and the device trace can attribute the op to its stage
    (:func:`stage_key` of the outermost stage segment).

    It writes no session event and opens no ``TraceAnnotation``: at
    trace time there is no device work to time. While a
    :func:`capture_events` buffer is active the span is appended there
    too, which is how :func:`..monitor.dispatch_spans` reads a program's
    stage order under ``jax.eval_shape``. Costs nothing at run time: the
    scope only changes op metadata."""
    cap = _capture
    with jax.named_scope(name):
        if cap is None:
            yield
            return
        start = time.perf_counter()
        try:
            yield
        finally:
            cap.append((name, start, time.perf_counter()))


@contextmanager
def timed_span(name: str):
    """:func:`add_trace` plus wall-clock capture: yields a dict whose
    ``"seconds"`` is filled on exit. For callers that feed the duration
    to the metrics registry as well as the trace timeline (the tuner's
    per-candidate compile/measure spans) — one clock read serves both,
    so the two surfaces can never disagree about a span's length."""
    out = {"seconds": 0.0}
    with add_trace(name):
        start = time.perf_counter()
        try:
            yield out
        finally:
            out["seconds"] = time.perf_counter() - start


#: Canonical stage keys of the reference's per-execute breakdown
#: (``fft_mpi_3d_api.cpp:184-201``) — the join axis of the explain layer.
STAGE_KEYS = ("t0", "t1", "t2", "t3")

#: Stage keys of a fused spectral-operator chain (:mod:`...operators`):
#: the transform taxonomy plus the ``t_mid`` pointwise stage between the
#: forward and inverse halves (final forward FFT, wavenumber-diagonal
#: multiply, first inverse FFT — all in the transposed midpoint layout).
OP_STAGE_KEYS = ("t0", "t1", "t2", "t_mid", "t3")


def stage_key(name: str) -> str | None:
    """Canonical ``t0..t3`` / ``t_mid`` key of a stage/span name, or None.

    Normalizes every variant the chain builders emit — ``t0_fft_yz``,
    ``t2_all_to_all``, ``t2a_exchange_x``/``t2b_exchange_y`` (both map
    to ``t2``), per-chunk overlap spans ``t3_fft_x[4]``, the operator
    chains' ``t_mid``/``t_mid[k]`` midpoint spans — so the
    explain/attribution layer and the regress localization agree on one
    stage taxonomy regardless of which builder produced the span.
    ``t_mid_pointwise`` (the multiply sub-span nested inside ``t_mid``)
    maps to None so device-trace attribution never double-counts it.
    Concurrent-schedule spans (``cc<j>:t2_exchange_...`` — transform j
    of a :func:`~..stagegraph.schedule_concurrent` program) drop the
    transform prefix first, so rollups attribute each interleaved span
    to its t0..t3 key like any other."""
    if name.startswith("cc"):
        head, sep, rest = name.partition(":")
        if sep and head[2:].isdigit():
            name = rest
    if name.startswith("t_mid"):
        rest = name[5:]
        return "t_mid" if (not rest or rest[0] == "[") else None
    if len(name) >= 2 and name[0] == "t" and name[1] in "0123":
        key = name[:2]
        rest = name[2:]
        if not rest or rest[0] in "_[" or rest[:1] in ("a", "b"):
            return key
    return None


def traced_stage(name: str, fn):
    """Wrap one staged-pipeline callable so every call records a named
    event (the per-stage breakdown of ``fft_mpi_3d_api.cpp:184-201`` as
    trace spans). Dispatch-side by the :func:`add_trace` contract — the
    timing harness's sync bracketing still owns true device timings.
    The wrapped callable (usually a jit) stays reachable via
    ``__wrapped__`` so the explain layer can lower/compile individual
    stages for cost analysis."""

    def run(x):
        with add_trace(name):
            return fn(x)

    run.__wrapped__ = fn
    return run


def trace_stages(stages):
    """Apply :func:`traced_stage` to a ``[(name, fn), ...]`` stage list."""
    return [(name, traced_stage(name, fn)) for name, fn in stages]


@dataclass
class CsvRecorder:
    """Benchmark CSV writer, the batchTest recording pattern
    (``templateFFT/batchTest/Test_1D.cpp:186-190`` appends
    size/batch/time/gflops/error rows; outputs mirror
    ``templateFFT/csv/*.csv``)."""

    path: str
    header: tuple[str, ...]

    def __post_init__(self) -> None:
        if not os.path.exists(self.path) or os.path.getsize(self.path) == 0:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            with open(self.path, "w") as f:
                f.write(",".join(self.header) + "\n")
            return
        # Appending to an existing file: its header must match, or every
        # appended row would be silently misaligned against the columns a
        # downstream reader infers from line 1.
        with open(self.path) as f:
            existing = f.readline().rstrip("\n")
        want = ",".join(self.header)
        if existing != want:
            raise ValueError(
                f"CSV {self.path!r} has header {existing!r}, recorder "
                f"expects {want!r}; refusing to append misaligned rows "
                f"(use a fresh path or matching header)")

    def record(self, *row) -> None:
        if len(row) != len(self.header):
            raise ValueError(f"expected {len(self.header)} fields, got {len(row)}")
        with open(self.path, "a") as f:
            f.write(",".join(str(v) for v in row) + "\n")


def plan_info(plan) -> str:
    """Human-readable plan dump — the ``outputPlanInfo`` analog
    (``fft_mpi_3d_api.cpp:433-464`` writes per-rank plan/exchange tables to
    ``rank_i_gpu_j.txt``); here one string covering every device."""
    if not hasattr(plan, "executor"):  # DDPlan3D: the emulated-f64 tier
        lines = [
            f"plan: {plan.shape} "
            f"({'forward' if plan.forward else 'backward'}, dd tier)",
            f"decomposition: {plan.decomposition}",
            "executor: dd (double-double over exact-sliced bf16 matmuls)",
        ]
        if plan.mesh is not None:
            lines.append(
                "mesh: "
                + " x ".join(f"{n}={s}" for n, s in plan.mesh.shape.items())
                + f" ({plan.mesh.devices.size} devices)"
            )
            lines.append(f"in sharding:  {plan.in_sharding.spec}")
            lines.append(f"out sharding: {plan.out_sharding.spec}")
        return "\n".join(lines)
    _op = getattr(plan, "op", "")
    _rc = plan.real and not _op  # a real operator plan is r2c AND c2r
    lines = [
        f"plan: {plan.in_shape} -> {plan.out_shape} "
        f"({'forward' if plan.forward else 'backward'}"
        f"{', r2c' if _rc and plan.forward else ''}"
        f"{', c2r' if _rc and not plan.forward else ''})",
        f"decomposition: {plan.decomposition}",
        f"executor: {plan.executor}",
        f"algorithm: {plan.options.algorithm}",
        f"dtype: {plan.in_dtype} -> {plan.out_dtype}",
    ]
    _oc = getattr(plan.options, "overlap_chunks", None)
    if _oc not in (None, 1):
        lines.append(
            f"overlap: {_oc} chunks (pipelined t2/t3 exchange-compute "
            f"interleave along the bystander axis)")
    _b = getattr(plan, "batch", None)
    if _b is not None:
        lines.append(
            f"batch: {_b} coalesced transforms (one shared exchange per "
            f"t2 stage; batch rides the collectives as a bystander dim)")
    if _op and plan.real:
        lines.append(
            f"operator: fused {_op} on a real field (r2c -> pointwise on "
            f"the half spectrum -> c2r in one program)")
    elif _op:
        lines.append(
            f"operator: fused {_op} (FFT -> pointwise -> iFFT in one "
            f"program; multiplier applied at the transposed t_mid "
            f"midpoint, skipping the cancelling transpose pair)")
    if plan.mesh is not None:
        lines.append(
            "mesh: "
            + " x ".join(f"{n}={s}" for n, s in plan.mesh.shape.items())
            + f" ({plan.mesh.devices.size} devices)"
        )
        lines.append(f"in sharding:  {plan.in_sharding.spec}")
        lines.append(f"out sharding: {plan.out_sharding.spec}")
    if plan.real:
        # The halved axis travels on the plan (Plan3D.r2c_axis) — shape
        # diffing is ambiguous for extents 1 and 2 where N//2+1 == N.
        ax = getattr(plan, "r2c_axis", 2)
        if ax != 2:
            lines.append(
                f"r2c axis: {ax} (canonical chain runs on the transposed "
                f"view; spec/logic rows below are in chain convention)")
    lp = getattr(plan, "logic", None)
    if lp is not None:
        if lp.slab_axes is not None:
            lines.append(f"slab chain: in axis {lp.slab_axes[0]} -> out axis "
                         f"{lp.slab_axes[1]}")
        if lp.pencil_perm is not None:
            lines.append(f"pencil chain: perm {lp.pencil_perm} "
                         f"({lp.pencil_order})")
        if not (lp.in_absorbed and lp.out_absorbed):
            edges = [s for s, ok in (("in", lp.in_absorbed),
                                     ("out", lp.out_absorbed)) if not ok]
            lines.append(f"edge reshards: {', '.join(edges)}")
        if lp.negotiated is not None:
            req, used, reason = lp.negotiated
            lines.append(
                f"device negotiation: requested {req} -> using {used} ({reason})"
            )
        # Exchange payload accounting: true information moved vs bytes on
        # the wire per algorithm (the count-table role of TransInfo /
        # outputPlanInfo, fft_mpi_3d_api.cpp:84-133,433-464).
        if lp.mesh is not None:
            from ..plan_logic import exchange_payloads

            shape_eff = plan.out_shape if (plan.real and plan.forward) else (
                plan.in_shape if plan.real else plan.shape
            )
            itemsize = np.dtype(plan.dtype).itemsize
            for e in exchange_payloads(lp, shape_eff, itemsize):
                t, d, v = e["true_bytes"], e["alltoall_bytes"], e["alltoallv_bytes"]
                ov = lambda x: f"+{(x / t - 1) * 100:.1f}%" if t else "n/a"
                lines.append(
                    f"exchange {e['stage']} ({e['mesh_axis']}, {e['parts']}-way): "
                    f"true {t * _MB:.2f} MB | alltoall {d * _MB:.2f} MB ({ov(d)}) | "
                    f"alltoallv {v * _MB:.2f} MB ({ov(v)})"
                )
        if (lp.decomposition == "slab" and lp.mesh is not None
                and not plan.real):
            # Rank-0 row of the exact per-peer count tables (TransInfo
            # semantics; full tables via native.exchange_table).
            from .. import native

            p = lp.mesh.devices.size
            a_in, a_out = lp.slab_axes or (0, 1)
            oth = 3 - a_in - a_out
            sc, _, rc, _ = native.exchange_table(
                plan.shape[a_in], plan.shape[a_out], plan.shape[oth], p, 0
            )
            lines.append(f"exchange counts[rank0]: send {sc} recv {rc}")
    if getattr(plan, "brick_edges", None) is not None:
        # Overlap-map ring accounting for the brick-I/O edges: true
        # intersection payload vs what the padded ring ships (the
        # send_size/recv_size table role of heffte_reshape3d's overlap
        # maps).
        itemsize = np.dtype(plan.dtype).itemsize
        for label, bs in zip(("in->chain", "chain->out"), plan.brick_edges):
            t = bs.payload_elems * itemsize
            w = bs.wire_elems * itemsize
            ov = f"ratio {bs.wire_ratio:.2f}x" if t else "ratio n/a"
            how = (f"{len(bs.steps)} ring steps" if bs.algorithm == "ring"
                   else "a2av exact counts")
            tbl = ("" if bs.a2av_table_bytes is None else
                   f" | index tables {bs.a2av_table_bytes / 1024:.1f} "
                   f"KB/device (RLE)")
            lines.append(
                f"brick edge {label}: {how}, "
                f"payload {t * _MB:.2f} MB | wire {w * _MB:.2f} MB ({ov})"
                + tbl
            )
    # Per-device memory footprint estimate — the heFFTe benchmark's
    # "MB/rank" report (benchmarks/speed3d.h:156-181) and the reference's
    # getMaxDataCount allocation sizing (fft_mpi_3d_api.cpp:289-316).
    # Intermediates are sized at the plan's PADDED extents (ceil-split
    # pad/crop discipline), which is what the chain actually allocates.
    ndev = plan.mesh.devices.size if plan.mesh is not None else 1
    in_b = math.prod(plan.in_shape) * np.dtype(plan.in_dtype).itemsize
    out_b = math.prod(plan.out_shape) * np.dtype(plan.out_dtype).itemsize
    work = max(in_b, out_b)  # one staged intermediate at a time under jit
    spec = plan.spec
    if spec is not None and hasattr(spec, "in_padded"):
        isz = np.dtype(plan.in_dtype).itemsize
        work = max(work, math.prod(spec.in_padded) * isz,
                   math.prod(spec.out_padded) * isz)
    total = (in_b + out_b + (0 if plan.options.donate else work)) / ndev
    lines.append(
        f"memory/device (est): in {in_b / ndev * _MB:.1f} MB + out "
        f"{out_b / ndev * _MB:.1f} MB"
        + ("" if plan.options.donate else
           f" + work {work / ndev * _MB:.1f} MB")
        + f" ~= {total * _MB:.1f} MB"
        + (" (donating)" if plan.options.donate else "")
    )
    if plan.spec is not None:
        lines.append(f"padded extents: {plan.spec}")
    for label, boxes in (("in", plan.in_boxes), ("out", plan.out_boxes)):
        for i, b in enumerate(boxes):
            lines.append(f"{label} box[{i}]: low={b.low} high={b.high} shape={b.shape}")
    return "\n".join(lines)
