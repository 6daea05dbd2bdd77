"""Device ops of a JAX profiler capture, with the stage each belongs to.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``,
a serialized ``XSpace``. On a TPU each chip is a plane ``/device:TPU:<n>``
whose line ``XLA Ops`` holds one event per executed HLO instruction. The
event's metadata carries a ``tf_op`` stat: the instruction's ``op_name``,
which holds the :func:`.trace.stage_scope` names the chain was traced
under (``jit(fn)/shard_map/t0_fft_yz/jit(fft)/fft``).
``jax.profiler.ProfileData`` does not expose that stat, so this module
reads the protobuf wire format itself (the few ``XSpace`` fields it
needs), with no dependency beyond the standard library.
"""

from __future__ import annotations

import re
from pathlib import Path

from .trace import stage_key

#: Device planes: one per chip (``/device:TPU:0`` ...).
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: The line of a device plane that holds one event per HLO operation.
OPS_LINE = "XLA Ops"


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for a varint, a memoryview for a length-delimited field, raw
    bytes for a fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat_names(plane_fields) -> dict[int, str]:
    """``XPlane.stat_metadata`` (field 5): stat id -> name."""
    out = {}
    for num, entry in plane_fields:
        if num == 5:
            for k, v in _fields(entry):
                if k == 2:
                    meta = dict(_fields(v))
                    out[meta.get(1, 0)] = _text(meta.get(2, b""))
    return out


def _op_metadata(plane_fields, stat_names) -> dict[int, tuple[str, str]]:
    """``XPlane.event_metadata`` (field 4): id -> (instruction name,
    ``tf_op``). A string stat comes as ``str_value`` (5) or as a
    ``ref_value`` (7) naming a stat metadata entry."""
    out = {}
    for num, entry in plane_fields:
        if num != 4:
            continue
        for k, v in _fields(entry):
            if k != 2:
                continue
            mid, name, display, tf_op = 0, "", "", ""
            for f, x in _fields(v):
                if f == 1:
                    mid = x
                elif f == 2:
                    name = _text(x)
                elif f == 4:
                    display = _text(x)
                elif f == 5:
                    stat = dict(_fields(x))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = (_text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            inst = display or name.partition(" = ")[0].lstrip("%")
            out[mid] = (inst, tf_op)
    return out


def device_ops(path_or_bytes) -> dict[str, list[tuple[str, str, int, int]]]:
    """``{plane name: [(instruction, tf_op, start_ps, end_ps), ...]}`` of
    the ``XLA Ops`` line of every device plane in one ``.xplane.pb``,
    in trace order; times in picoseconds on the profiler's clock."""
    data = (path_or_bytes if isinstance(path_or_bytes, (bytes, bytearray))
            else Path(path_or_bytes).read_bytes())
    out: dict[str, list] = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        pf = list(_fields(plane))
        name = next((_text(v) for k, v in pf if k == 2), "")
        if not DEVICE_PLANE.match(name):
            continue
        meta = _op_metadata(pf, _stat_names(pf))
        evs = out.setdefault(name, [])
        for k, line in pf:
            if k != 3:
                continue
            lf = list(_fields(line))
            if next((_text(v) for f, v in lf if f == 2), "") != OPS_LINE:
                continue
            base = next((v for f, v in lf if f == 3), 0) * 1000
            for f, ev in lf:
                if f != 4:
                    continue
                e = dict(_fields(ev))
                inst, tf_op = meta.get(e.get(1, 0), ("", ""))
                start = base + e.get(2, 0)
                evs.append((inst, tf_op, start, start + e.get(3, 0)))
    return out


def op_stage(tf_op: str) -> str | None:
    """The ``t0``..``t3`` or ``t_mid`` stage of a device op: that of the
    outermost segment of its ``op_name`` path that
    :func:`.trace.stage_key` maps to one (``cc<j>:`` prefixes, ``[k]``
    chunk suffixes and the ``t2a``/``t2b`` legs included; an operator
    chain's ``t_mid_pointwise`` counts for the ``t_mid`` around it);
    None for an op under no stage scope. The profiler appends
    ``:<op type>`` to the path, which is dropped."""
    head, sep, last = tf_op.rpartition("/")
    if ":" in last and not stage_key(last):
        last = last.rpartition(":")[0]
    for seg in (head.split("/") if sep else []) + [last]:
        key = stage_key(seg)
        if key is not None:
            return key
    return None
