"""Structured metrics registry — process-global counters/gauges/histograms.

The reference surfaces its runtime health as printf noise (per-execute
t0..t3 lines, ``fft_mpi_3d_api.cpp:184-201``) that callers string-grep;
this module is the structured replacement: named series with labels,
snapshot-able as one JSON document, so benchmark harnesses (``bench.py``,
``benchmarks/speed3d.py``) can attach a telemetry block to every result
line instead of ad-hoc string fields.

Registered series (wired in :mod:`..api`):

- ``plan_builds`` (counter; kind/decomposition/executor) — actual plan
  constructions, cache misses included.
- ``plan_cache_hits`` / ``plan_cache_misses`` (counter; kind) — the
  plan-cache outcome of every public planner call.
- ``plan_build_seconds`` / ``compile_seconds`` (histogram) — plan-time
  cost, the hipRTC-compile-cost analog.
- ``executes`` (counter; kind/decomposition/executor) — one per
  ``execute()``.
- ``exchange_true_bytes`` / ``exchange_wire_bytes`` (counter) — per
  execute, the true information moved vs the bytes the plan's exchange
  algorithm ships (``plan_logic.exchange_payloads`` accounting).

Tuner series (wired in :mod:`..tuner`):

- ``tune_tournaments`` (counter; kind) — measured-selection tournaments
  actually run (wisdom hits skip these entirely).
- ``tune_timing_executions`` (counter; candidate) — one per candidate
  timed in a tournament; zero across a planner call proves the wisdom
  path was taken.
- ``tune_wisdom_hits`` / ``tune_wisdom_misses`` (counter; kind) — the
  wisdom-store outcome of every tuned planner call.
- ``tune_build_seconds`` / ``tune_measure_seconds`` (histogram;
  candidate) — per-candidate plan-build/compile and timing cost, also
  emitted as ``tune_build_*``/``tune_measure_*`` trace spans.

Serving / flight-recorder series (wired in :mod:`..serving`; see
docs/OBSERVABILITY.md "Flight recorder"):

- ``serving_submits`` / ``serving_flushes`` / ``serving_transforms``
  (counter; kind) — request intake and group execution.
- ``serving_flush_reasons`` (counter; kind/reason) — what triggered
  each flush: ``full`` (a group reached max_batch), ``manual``
  (an explicit ``flush()``), ``result`` (a caller's await outran the
  coalescer — the batch-size-vs-latency tell).
- ``serving_queue_depth`` (gauge; kind) — pending requests after every
  submit/flush.
- ``serving_wait_seconds`` (histogram; kind) — per-request
  enqueue-to-flush latency, the queue-wait of the request spans.
- ``serving_batch_size`` (histogram; kind) — transforms per flush.

Fault-tolerance series (wired in :mod:`..serving` / :mod:`..faults`;
see docs/ROBUSTNESS.md):

- ``fault_injected`` (counter; point/kind) — injected faults fired.
- ``serving_retries`` / ``serving_isolated_failures`` /
  ``serving_degraded`` / ``serving_expired`` / ``serving_rejected``
  (counter; kind, +executor on degraded) — the recovery chain's
  accounting: transient retries, bisection-isolated failures,
  fallback-executor resolutions, deadline cancellations, admission
  rejections.
- ``serving_warm_pool_skipped`` (counter) — stale wisdom tuples
  skipped during pool warm-up.

Multi-tenant QoS series (wired in :mod:`..serving`; see
docs/SERVING_QOS.md):

- ``serving_tenant_submits`` / ``serving_tenant_transforms`` (counter;
  kind/tenant) — per-tenant intake and drained transforms.
- ``serving_tenant_quota_shed`` (counter; kind/tenant) — submits shed
  with ``QuotaExceeded`` (over-quota under ``admission="raise"``).
- ``serving_tenant_deadline_misses`` (counter; kind/tenant) — deadline
  cancellations charged to the owning tenant.
- ``serving_tenant_wait_seconds`` (histogram; kind/tenant) — the
  per-tenant queue-wait distribution (the SLO ledger's p50/p99 ride
  the policy's in-process reservoir; ``report qos``).

Live-monitor series (wired in :mod:`..monitor` / :mod:`.trace`; see
docs/OBSERVABILITY.md "Live monitoring & health"):

- ``serving_stalls`` (counter; kind) — queue-stall watchdog firings: a
  pending group aged past ``stall_factor × max_wait_s`` with no flush
  progress between monitor samples.
- ``trace_dropped_events`` (counter) — flight-recorder events evicted
  by the in-memory ring cap (``DFFT_TRACE_MAX_EVENTS``).

Compile series (JAX's own ``jax.monitoring`` events, listened to only
while the registry is enabled):

- ``compile_cache_hits`` / ``compile_cache_misses`` (counter) — the
  persistent compile cache's outcome for every compile that consults it
  (``/jax/compilation_cache/cache_hits`` / ``cache_misses``); no cache
  directory, no events.
- ``backend_compile_seconds`` (histogram) — one per XLA compile request
  (``/jax/core/compile/backend_compile_duration``): the compile, or on a
  cache hit the load from the cache, which this time includes.
- ``compile_cache_retrieval_seconds`` (histogram) — the cache read of
  each hit (``/jax/compilation_cache/cache_retrieval_time_sec``), a part
  of that hit's ``backend_compile_seconds``.

Wait histograms additionally keep a fixed-size sampling reservoir
(:data:`RESERVOIR_SERIES`) so snapshots carry p50/p99; the per-series
``exact`` flag says whether the quantiles were computed over every
observation or a uniform sample (Algorithm R) once the count outgrows
the reservoir.

Disabled-path discipline: everything is gated on one module-level flag
(the ``tracing_enabled()`` pattern of :mod:`.trace`) — with metrics off
(the default) every hook is a single attribute check and early return,
no allocation, no lock. Enable with :func:`enable_metrics` or
``DFFT_METRICS=1``.
"""

from __future__ import annotations

import os
import random
import threading
import time

__all__ = [
    "METRICS_SCHEMA",
    "enable_metrics",
    "metrics_enabled",
    "inc",
    "set_gauge",
    "observe",
    "counter_total",
    "metrics_snapshot",
    "metrics_reset",
]

#: Snapshot document format version — bumped whenever the snapshot's
#: shape changes, and stamped into every snapshot (and from there into
#: the regress run records that embed one) so schema drift across
#: releases is detectable offline instead of silently misparsed.
METRICS_SCHEMA = 1

_enabled = os.environ.get("DFFT_METRICS", "") not in ("", "0")
_lock = threading.Lock()
# Keyed (name, ((label, value), ...)) with label values stringified —
# one flat series table per instrument family.
_counters: dict[tuple, float] = {}
_gauges: dict[tuple, float] = {}
_histograms: dict[tuple, list] = {}  # [count, total, min, max]

#: Histogram series that keep a bounded sampling reservoir for snapshot
#: quantiles. The wait distributions are the SLO-facing ones; the other
#: histograms stay pure count/total/min/max aggregates.
RESERVOIR_SERIES = frozenset(
    {"serving_wait_seconds", "serving_tenant_wait_seconds"})
#: Reservoir capacity per labeled series — beyond this many
#: observations, Algorithm R keeps a uniform sample (exact=False).
RESERVOIR_SIZE = 2048
_reservoirs: dict[tuple, list] = {}
_res_rng = random.Random(0x0FF7)  # deterministic per process


_listening = False


def _on_event(event: str, **_) -> None:
    """``jax.monitoring`` event listener (registered while enabled)."""
    if event == "/jax/compilation_cache/cache_hits":
        inc("compile_cache_hits")
    elif event == "/jax/compilation_cache/cache_misses":
        inc("compile_cache_misses")


def _on_duration(event: str, seconds: float, **_) -> None:
    """``jax.monitoring`` duration listener (registered while enabled)."""
    if event == "/jax/core/compile/backend_compile_duration":
        observe("backend_compile_seconds", seconds)
    elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
        observe("compile_cache_retrieval_seconds", seconds)


def _listen(on: bool) -> None:
    """Register the compile listeners with ``jax.monitoring`` (or remove
    them): they exist only while the registry is enabled."""
    global _listening
    if on == _listening:
        return
    import jax.monitoring as mon

    if on:
        mon.register_event_listener(_on_event)
        mon.register_event_duration_secs_listener(_on_duration)
    else:
        mon.unregister_event_listener(_on_event)
        mon.unregister_event_duration_listener(_on_duration)
    _listening = on


def metrics_enabled() -> bool:
    return _enabled


def enable_metrics(on: bool = True) -> None:
    """Turn the registry on (or off with ``on=False``). Off is the
    default; the recording hooks are single-check no-ops while off, and
    no ``jax.monitoring`` listener is registered."""
    global _enabled
    _enabled = bool(on)
    _listen(_enabled)


def _key(name: str, labels: dict) -> tuple:
    return (name, tuple(sorted((k, str(v)) for k, v in labels.items())))


def inc(name: str, value: float = 1.0, **labels) -> None:
    """Add ``value`` to the counter series ``name`` at ``labels``."""
    if not _enabled:
        return
    k = _key(name, labels)
    with _lock:
        _counters[k] = _counters.get(k, 0.0) + value


def set_gauge(name: str, value: float, **labels) -> None:
    """Set the gauge series ``name`` at ``labels`` to ``value``."""
    if not _enabled:
        return
    k = _key(name, labels)
    with _lock:
        _gauges[k] = float(value)


def observe(name: str, value: float, **labels) -> None:
    """Record one observation into the histogram series ``name`` —
    aggregated as count/total/min/max (the heFFTe finalize-summary
    statistics, not bucketed)."""
    if not _enabled:
        return
    k = _key(name, labels)
    value = float(value)
    with _lock:
        h = _histograms.get(k)
        if h is None:
            h = _histograms[k] = [1, value, value, value]
        else:
            h[0] += 1
            h[1] += value
            h[2] = min(h[2], value)
            h[3] = max(h[3], value)
        if name in RESERVOIR_SERIES:
            r = _reservoirs.get(k)
            if r is None:
                r = _reservoirs[k] = []
            if len(r) < RESERVOIR_SIZE:
                r.append(value)
            else:
                # Algorithm R: each of the h[0] observations so far ends
                # up in the sample with probability RESERVOIR_SIZE/h[0].
                j = _res_rng.randrange(h[0])
                if j < RESERVOIR_SIZE:
                    r[j] = value


def counter_total(name: str, **labels) -> float:
    """Sum of the counter ``name`` across every label combination, or
    across those that carry ``labels``."""
    want = set(_key(name, labels)[1])
    with _lock:
        return sum(v for (n, ls), v in _counters.items()
                   if n == name and want <= set(ls))


def _label_str(labels: tuple) -> str:
    return ",".join(f"{k}={v}" for k, v in labels)


def _quantile(sorted_vals: list, q: float) -> float:
    """Linear-interpolated quantile of an already-sorted sample."""
    if not sorted_vals:
        return 0.0
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (pos - lo)


def metrics_snapshot() -> dict:
    """One JSON-serializable document of every recorded series.

    Shape: ``{"schema", "captured_at_monotonic", "enabled", "counters":
    {name: {"label=value,...": total}}, "gauges": {...}, "histograms":
    {name: {labels: {count,total,mean,min,max}}}}`` (the empty string
    keys a label-less series). ``schema`` is :data:`METRICS_SCHEMA`;
    ``captured_at_monotonic`` is ``time.monotonic()`` at capture — a
    per-process ordering stamp (two snapshots from one process order by
    it; it is NOT wall clock and never compares across processes).
    Reset with :func:`metrics_reset`.
    """
    with _lock:
        counters: dict = {}
        for (name, labels), v in sorted(_counters.items()):
            counters.setdefault(name, {})[_label_str(labels)] = v
        gauges: dict = {}
        for (name, labels), v in sorted(_gauges.items()):
            gauges.setdefault(name, {})[_label_str(labels)] = v
        hists: dict = {}
        for (name, labels), (cnt, total, lo, hi) in sorted(
                _histograms.items()):
            entry = {
                "count": cnt,
                "total": total,
                "mean": total / cnt,
                "min": lo,
                "max": hi,
            }
            r = _reservoirs.get((name, labels))
            if r is not None:
                s = sorted(r)
                entry["p50"] = _quantile(s, 0.50)
                entry["p99"] = _quantile(s, 0.99)
                # Exact while every observation is still in the sample;
                # a uniform Algorithm-R estimate once the count outgrew
                # the reservoir.
                entry["exact"] = cnt <= RESERVOIR_SIZE
            hists.setdefault(name, {})[_label_str(labels)] = entry
    return {
        "schema": METRICS_SCHEMA,
        "captured_at_monotonic": time.monotonic(),
        "enabled": _enabled,
        "counters": counters,
        "gauges": gauges,
        "histograms": hists,
    }


def metrics_reset() -> None:
    """Drop every recorded series (the enabled flag is left as is)."""
    with _lock:
        _counters.clear()
        _gauges.clear()
        _histograms.clear()
        _reservoirs.clear()


if _enabled:  # DFFT_METRICS at import
    _listen(True)
