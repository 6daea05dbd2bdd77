"""Reduce one JAX profiler trace to what the per-layer readers need: the
intervals in which each device ran an operation, the host spans the
harness itself annotated, and the traced window.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. On a TPU
each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds
one event per executed HLO instruction, named by its HLO text (kept
here as its instruction name and opcode); the harness's
``TraceAnnotation`` spans land on the host plane ``/host:CPU``. Both
are on one clock, in nanoseconds.

A trace is kept as plain data (:class:`Trace`), so that the readers can
be checked on a small recorded trace (``perf/tests/data``) without a
chip or the profiler.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path

#: Device planes: one per chip (``/device:TPU:0`` ...).
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: The line of a device plane that holds one event per HLO operation.
OPS_LINE = "XLA Ops"
#: Host spans the harness opens around its own work (``run.py``).
HOST_SPANS = ("window", "dispatch", "sync")
#: HLO collectives, by opcode (their instruction names vary: the t2
#: exchange runs as ``%all_to_all.24 = f32[...] all-to-all(...)``).
COLLECTIVE = re.compile(r"^(all-to-all|all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|ragged-all-to-all)")


@dataclass
class Trace:
    """``ops[device]``: ``[name, opcode, start_ns, end_ns]`` of every
    operation that device ran inside ``window`` (clipped to it); ``host``: the
    harness's spans ``[name, start_ns, end_ns]``; ``window``: the
    ``window`` span's ``[start_ns, end_ns]``."""

    window: tuple[float, float]
    ops: dict[str, list[list]]
    host: list[list]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self, device: str, pick=None) -> list[tuple[float, float]]:
        """Union of the intervals of ``device``'s operations (those whose
        opcode ``pick`` accepts, or all)."""
        return union((s, e) for _, k, s, e in self.ops[device]
                     if pick is None or pick(k))

    def busy_s(self, device: str, pick=None) -> float:
        return total(self.busy(device, pick)) / 1e9

    def busiest(self) -> str:
        return max(self.ops, key=self.busy_s)

    def collective_s(self, device: str) -> float:
        """Seconds in which ``device`` ran a collective."""
        return self.busy_s(device, COLLECTIVE.search)

    def exposed_s(self, device: str) -> float:
        """Seconds in which ``device`` ran a collective and nothing else."""
        coll = self.busy(device, COLLECTIVE.search)
        other = self.busy(device, lambda n: not COLLECTIVE.search(n))
        return (total(coll) - overlap(coll, other)) / 1e9

    def to_json(self) -> str:
        return json.dumps({"window": list(self.window), "ops": self.ops,
                           "host": self.host})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(tuple(d["window"]), d["ops"], d["host"])


def load_xplane(path: str | Path) -> Trace:
    """Read an ``.xplane.pb`` into a :class:`Trace` clipped to the
    harness's ``window`` span (the whole trace where that span is
    missing)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict[str, list[list]] = {}
    host: list[list] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend([*parse_hlo(e.name), e.start_ns,
                                e.start_ns + e.duration_ns]
                               for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.start_ns + e.duration_ns]
                            for e in line.events if e.name in HOST_SPANS)
    win = [s for s in host if s[0] == "window"]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        ends = [x for evs in ops.values() for *_, s, e in evs for x in (s, e)]
        lo, hi = (min(ends), max(ends)) if ends else (0.0, 0.0)
    clipped = {d: [[n, k, max(s, lo), min(e, hi)] for n, k, s, e in evs
                   if e > lo and s < hi]
               for d, evs in ops.items()}
    return Trace((lo, hi), clipped, sorted(host, key=lambda s: s[1]))


def parse_hlo(hlo: str) -> tuple[str, str]:
    """Instruction name and opcode of a device event, which carries the
    whole HLO instruction: ``("fusion.160", "fusion")`` of ``%fusion.160 =
    (f32[...], f32[...]) fusion(...)``. Its operand list names other
    instructions, so only these two parts are read."""
    name, _, rest = hlo.partition(" = ")
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return name.lstrip("%"), rest.strip().partition("(")[0]


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    got = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            got += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return got


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between merged busy ones."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_label(host: list[list], t: float) -> str:
    """The innermost harness span open at ``t`` (``idle`` for none)."""
    open_ = [s for s in host if s[1] <= t < s[2]]
    return min(open_, key=lambda s: s[2] - s[1])[0] if open_ else "idle"


def breakdown(trace: Trace, chips: int) -> dict:
    """The ten device operations that took most time (seconds per chip,
    summed by name over the window) and the ten longest idle gaps of the
    busiest device, each named by what the host was doing in it."""
    per_op: dict[str, float] = {}
    for evs in trace.ops.values():
        for n, _, s, e in evs:
            per_op[n] = per_op.get(n, 0.0) + (e - s) / 1e9 / chips
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle = gaps(trace.busy(trace.busiest()), *trace.window) if trace.ops else []
    idle.sort(key=lambda g: g[0] - g[1])
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[host_label(trace.host, (s + e) / 2), (e - s) / 1e9]
                          for s, e in idle[:10]]}
