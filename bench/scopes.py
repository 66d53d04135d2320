"""Device time by the program's named scopes, and the program's own host
spans, from the traced window.

The program names the layers of its compiled programs with
``jax.named_scope`` (``repro.core.telemetry.SCOPES``: ``feds/train/gather``,
``feds/comm/upload``, ``feds/eval/score`` ...), which JAX writes into each
HLO op's ``op_name``; and it marks its host work with profiler annotations
``telemetry/<name>`` whose attributes carry work counters.  ``read()``
loads the newest ``.xplane.pb`` under ``harness.TRACE_DIR`` once per
process, cuts it to the harness's ``window`` span and returns a
``Scoped``: each device op's own time (``tracereduce.self_ns``: less the
ops nested in it) charged to the innermost scope its ``op_name`` holds,
and the window's ``telemetry/*`` host events with their attributes.

A scope is matched anywhere in the path, since JAX wraps a backward op's
scope as ``transpose(jvp(<scope>))``; scope names are full paths, so the
innermost is the one that starts last.  Ops are keyed by
``(hlo_module, name)``: fusion numbers repeat across programs.  A program
that names no layers (no ``SCOPES``) or writes no spans gives empty
tables, and the readers then return None.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re

import harness
import tracereduce

try:
    from repro.core.telemetry import SCOPES
except ImportError:  # a program that names no layers
    SCOPES = ()

SPAN_PREFIX = "telemetry/"
LOOP_SUFFIX = "/while"
MODULES_LINE = "XLA Modules"


def _scope_pattern(names):
    if not names:
        return None
    alt = "|".join(re.escape(n) for n in sorted(names, key=len, reverse=True))
    return re.compile(rf"(?:^|(?<=[/(]))({alt})(?=$|[/)])")


def innermost(op_name: str, pattern) -> str | None:
    """The scope of ``op_name`` that starts last (the longest where two
    start together), or None."""
    if pattern is None or not op_name:
        return None
    found = None
    for m in pattern.finditer(op_name):
        found = m.group(1)
    return found


@dataclasses.dataclass
class Scoped:
    """Own device seconds by scope, summed over the chips, and the host
    spans ``(name, start_ns, end_ns, attrs)`` of the window."""

    window_s: float
    busy_s: float
    scope_s: dict  # scope -> own seconds
    unscoped_s: float
    ops: dict  # (hlo_module, name) -> [scope, own seconds, runs, op_name]
    spans: list

    def own_s(self, scope: str) -> float | None:
        """Own seconds of the ops whose innermost scope is ``scope``."""
        return self.scope_s.get(scope)

    def under_s(self, prefix: str) -> float | None:
        """Own seconds of the ops in ``prefix`` or in a scope inside it."""
        hit = [t for s, t in self.scope_s.items()
               if s == prefix or s.startswith(prefix + "/")]
        return sum(hit) if hit else None

    def loop_unnamed_s(self, scope: str) -> float:
        """Own seconds of the ops of ``scope``'s loops that carry no scope
        of their own: the compiler made them with no op_name, and the
        profiler names them by the loop they run in (``.../while``).  The
        loop op itself is left out."""
        return sum(t for (_, name), (sc, t, _, op) in self.ops.items()
                   if sc == scope and op.endswith(LOOP_SUFFIX)
                   and not name.startswith("while"))

    def counter(self, span: str, attr: str) -> int | None:
        """Sum of ``attr`` over the window's ``telemetry/<span>`` spans."""
        vals = [a[attr] for n, _, _, a in self.spans
                if n == SPAN_PREFIX + span and attr in a]
        return sum(vals) if vals else None


def reduce(trace: tracereduce.Trace, window: str = "window",
           names=SCOPES) -> Scoped:
    """``trace``'s device events are ``((hlo_module, name), start_ns,
    end_ns, op_name)``; its host events ``(name, start_ns, end_ns,
    attrs)``."""
    lo, hi = tracereduce.span_of(trace, window)
    pattern = _scope_pattern(names)
    scope_s: dict = {}
    ops: dict = {}
    unscoped = busy = 0.0
    for events in trace.devices.values():
        cut = tracereduce.clip(events, lo, hi)
        busy += tracereduce.busy_ns(cut, lo, hi)
        for key, op_name, own in tracereduce.self_ns(cut):
            scope = innermost(op_name, pattern)
            if scope is None:
                unscoped += own
            else:
                scope_s[scope] = scope_s.get(scope, 0.0) + own * 1e-9
            rec = ops.setdefault(key, [scope, 0.0, 0, op_name])
            rec[1] += own * 1e-9
            rec[2] += 1
    spans = [x for x in tracereduce.clip(trace.host, lo, hi)
             if x[0].startswith(SPAN_PREFIX)]
    n = max(1, len(trace.devices))
    return Scoped(window_s=(hi - lo) * 1e-9, busy_s=busy / n * 1e-9,
                  scope_s=scope_s, unscoped_s=unscoped * 1e-9, ops=ops,
                  spans=spans)


# --------------------------------------------------- the raw .xplane.pb
# ``jax.profiler.ProfileData`` gives each event its own stats only.  On a
# TPU an op's ``op_name`` (stat ``tf_op``) and its ``program_id`` sit on the
# event's metadata, which ProfileData does not expose, so the device
# planes' event metadata is read from the protobuf's wire format here.
# Field numbers of tsl/profiler/protobuf/xplane.proto:
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_MD, XPLANE_STAT_MD = 2, 4, 5
MD_NAME, MD_STATS = 2, 5  # XEventMetadata (and XStatMetadata's name is 2)
STAT_MD_ID, STAT_UINT, STAT_INT, STAT_STR, STAT_REF = 1, 3, 4, 5, 7


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of a message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        else:
            i += 8 if kind == 1 else 4
            continue
        yield key >> 3, v


def _map_value(entry):
    """``(key, value bytes)`` of a map entry."""
    key = val = None
    for f, v in _fields(entry):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def device_op_names(raw: bytes) -> dict:
    """``{(plane, program_id, event name): op_name}`` of every TPU plane's
    event metadata that carries a ``tf_op`` stat."""
    out = {}
    for f, plane in _fields(memoryview(raw)):
        if f != XSPACE_PLANES:
            continue
        name, event_md, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == XPLANE_NAME:
                name = bytes(v).decode()
            elif g == XPLANE_EVENT_MD:
                event_md.append(v)
            elif g == XPLANE_STAT_MD:
                k, md = _map_value(v)
                stat_names[k] = next(
                    (bytes(x).decode() for h, x in _fields(md) if h == 2), "")
        if not name.startswith(tracereduce.DEVICE_PREFIX):
            continue
        for entry in event_md:
            _, md = _map_value(entry)
            ev_name, stats = "", {}
            for g, v in _fields(md):
                if g == MD_NAME:
                    ev_name = bytes(v).decode()
                elif g == MD_STATS:
                    sid, val = None, None
                    for h, x in _fields(v):
                        if h == STAT_MD_ID:
                            sid = x
                        elif h in (STAT_UINT, STAT_INT):
                            val = x
                        elif h == STAT_STR:
                            val = bytes(x).decode()
                        elif h == STAT_REF:
                            val = stat_names.get(x, "")
                    stats[stat_names.get(sid)] = val
            tf_op = stats.get("tf_op")
            if tf_op:
                # TensorFlow's "<name>:<type>" form; the type is empty here
                op, colon, _ = tf_op.rpartition(":")
                out[(name, stats.get("program_id"), ev_name)] = (
                    op if colon else tf_op)
    return out


MODULE_ID = re.compile(r"\((\d+)\)$")


def _modules(plane) -> list:
    """``(start_ns, end_ns, module name, program id)`` of a device plane's
    ``XLA Modules`` line, by start."""
    mods = []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for e in line.events:
                m = MODULE_ID.search(e.name)
                mods.append((e.start_ns, e.end_ns, e.name,
                             int(m.group(1)) if m else None))
    return sorted(mods)


def load_trace(path: str) -> tracereduce.Trace:
    """Device ops of each TPU plane's ``XLA Ops`` line as
    ``((hlo_module, name), start_ns, end_ns, op_name)``, the module being
    the ``XLA Modules`` event the op runs in; and every host event as
    ``(name, start_ns, end_ns, attrs)``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    op_names = device_op_names(raw)
    devices, host = {}, []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith(tracereduce.DEVICE_PREFIX):
            mods = _modules(plane)
            starts = [m[0] for m in mods]
            ops = []
            for line in plane.lines:
                if line.name != tracereduce.OPS_LINE:
                    continue
                for e in line.events:
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    mod, pid = (mods[i][2], mods[i][3]) if i >= 0 else ("", None)
                    op_name = op_names.get((plane.name, pid, e.name), "")
                    name = e.name.partition(" = ")[0].lstrip("%")
                    ops.append(((mod, name), e.start_ns, e.end_ns, op_name))
            devices[plane.name] = ops
        elif plane.name.startswith(tracereduce.HOST_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return tracereduce.Trace(devices=devices, host=host)


@functools.lru_cache(maxsize=4)
def _read(path: str, mtime: float) -> Scoped | None:
    trace = load_trace(path)
    if not trace.devices:
        return None
    return reduce(trace)


def read() -> Scoped | None:
    """The newest trace under ``harness.TRACE_DIR``, reduced; None where
    there is none."""
    paths = glob.glob(os.path.join(harness.TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    return _read(path, os.path.getmtime(path))


def seconds_per(scope: str, n: int, under: bool = False) -> float | None:
    """Own device seconds under ``scope`` (with its inner scopes where
    ``under``) per unit of ``n``, in the newest trace; None where the
    scope ran no op."""
    s = read()
    if s is None:
        return None
    t = s.under_s(scope) if under else s.own_s(scope)
    return None if t is None else t / n


def share(span: str, part: str, whole: str) -> float | None:
    """``100 * sum(part) / sum(whole)`` over the newest trace's
    ``telemetry/<span>`` spans, in %; None where no span carries them."""
    s = read()
    if s is None:
        return None
    a, b = s.counter(span, part), s.counter(span, whole)
    return None if a is None or not b else 100.0 * a / b
