"""A reader of the profiler's ``.xplane.pb`` that keeps what
``jax.profiler.ProfileData`` drops: the stats of an event's metadata.

On a TPU plane each ``XLA Ops`` event's metadata carries a ``tf_op`` stat,
the op's scope path (``jit(_chunk_step_impl)/level.select/...``), so that
device time can be put down to the program's ``jax.named_scope``s.  On a
host plane each event carries its span's arguments as stats
(``TraceAnnotation("udt.put", bytes=...)`` gives ``{"bytes": ...}``).

It decodes the protobuf wire format of ``XSpace``
(``tsl/profiler/protobuf/xplane.proto``) itself, with no protobuf module.
Times are computed as ``ProfileData`` computes them: the line's
``timestamp_ns`` plus ``offset_ps // 1000``, and ``duration_ps // 1000``.
"""
from __future__ import annotations

import dataclasses
import functools
import struct

from bench import trace


@dataclasses.dataclass
class Trace:
    # device plane name -> [(name, start_ns, end_ns, tf_op)], its XLA Ops
    ops: dict
    # [(name, start_ns, end_ns, {stat: value})] of every host plane
    host: list


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of each field of ``buf[lo:hi]``: an int for
    varint and fixed-width fields, a (lo, hi) slice for the others."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = int.from_bytes(buf[i:i + n], "little"), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _str(buf, sl):
    return bytes(buf[sl[0]:sl[1]]).decode("utf-8", "replace")


def _stats(buf, slices, stat_names):
    """XStat messages -> {name: value}; a ref value is the name of the
    stat metadata it points at."""
    out = {}
    for sl in slices:
        key = val = None
        for f, v in _fields(buf, *sl):
            if f == 1:
                key = stat_names.get(v, str(v))
            elif f == 2:
                val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
            elif f == 3:
                val = v
            elif f == 4:
                val = _signed(v)
            elif f == 5:
                val = _str(buf, v)
            elif f == 6:
                val = bytes(buf[v[0]:v[1]])
            elif f == 7:
                val = stat_names.get(v, v)
        out[key] = val
    return out


def _map_entry(buf, sl):
    key, val = 0, (sl[1], sl[1])
    for f, v in _fields(buf, *sl):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, sl):
    """(name, [line slices], {metadata id: (name, stat slices)},
    {stat id: name}) of one XPlane."""
    name, lines, events, stat_names = "", [], {}, {}
    for f, v in _fields(buf, *sl):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            key, msg = _map_entry(buf, v)
            ename, stats = "", []
            for g, w in _fields(buf, *msg):
                if g == 2:
                    ename = _str(buf, w)
                elif g == 5:
                    stats.append(w)
            events[key] = (ename, stats)
        elif f == 5:
            key, msg = _map_entry(buf, v)
            stat_names[key] = next((_str(buf, w) for g, w in
                                    _fields(buf, *msg) if g == 2), "")
    return name, lines, events, stat_names


def _line(buf, sl):
    """(name, [(metadata id, start_ns, end_ns, stat slices)]) of an XLine."""
    name, ts, raw = "", 0, []
    for f, v in _fields(buf, *sl):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            ts = _signed(v)
        elif f == 4:
            raw.append(v)
    out = []
    for ev in raw:
        mid = off = dur = 0
        stats = []
        for f, v in _fields(buf, *ev):
            if f == 1:
                mid = v
            elif f == 2:
                off = _signed(v)
            elif f == 3:
                dur = _signed(v)
            elif f == 4:
                stats.append(v)
        start = ts + off // 1000
        out.append((mid, start, start + dur // 1000, stats))
    return name, out


def _tf_op(stats):
    """The op's scope path: ``tf_op`` is ``<path>:<type>``, the type empty
    for XLA's ops."""
    path = stats.get("tf_op", "")
    return path[:path.rfind(":")] if ":" in path else path


@functools.lru_cache(maxsize=2)
def load(path: str) -> Trace:
    """The XLA Ops of each TPU plane and the events of each host plane
    in the ``.xplane.pb`` at ``path`` (parsed once per path)."""
    with open(path, "rb") as f:
        buf = f.read()
    ops, host = {}, []
    for field, sl in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, lines, events, stat_names = _plane(buf, sl)
        tf_ops = {}
        device = bool(trace.DEVICE_PLANE.match(name))
        if not (device or name.startswith("/host:")):
            continue
        for line in lines:
            lname, evs = _line(buf, line)
            if device and lname != trace.OPS_LINE:
                continue
            for mid, s, e, stats in evs:
                ename, mstats = events.get(mid, ("", []))
                if device:
                    if mid not in tf_ops:
                        tf_ops[mid] = _tf_op(_stats(buf, mstats, stat_names))
                    ops.setdefault(name, []).append((ename, s, e, tf_ops[mid]))
                else:
                    host.append((ename, s, e,
                                 _stats(buf, stats, stat_names)))
    return Trace(ops, host)


def for_run(run) -> Trace | None:
    """The parsed trace of a traced run, or None for an untraced one."""
    if run.trace is None or run.window.trace_dir is None:
        return None
    return load(trace.find_xplane(run.window.trace_dir))


def in_scope(tf_op: str, scope: str) -> bool:
    """An op is in ``scope`` when one ``/``-separated component of its
    ``tf_op`` path is the scope's name."""
    return scope in tf_op.split("/")


def window_spans(tr: Trace, summary, name: str) -> list:
    """(start_ns, end_ns, args) of the host spans ``name`` that start in
    the window of ``summary`` (``bench.trace.Summary``)."""
    return [(s, e, args) for n, s, e, args in tr.host
            if n == name and summary.w0 <= s <= summary.w1]


def busy(ops: list, w0: float, w1: float) -> list:
    """The merged [start, end] intervals of ``ops`` (one chip's) inside
    the window."""
    return trace._union([(max(s, w0), min(e, w1)) for _, s, e, _ in ops
                         if e > w0 and s < w1])


def scope_s(tr: Trace, summary, scope: str) -> float | None:
    """Device seconds of the ops in ``scope`` inside the window, averaged
    over chips; None where the trace has no op in it."""
    per = []
    for ops in tr.ops.values():
        per.append(sum(min(e, summary.w1) - max(s, summary.w0)
                       for _, s, e, tf_op in ops
                       if e > summary.w0 and s < summary.w1
                       and in_scope(tf_op, scope)))
    return sum(per) / len(per) * 1e-9 if per and any(per) else None
