"""From a JAX profiler trace to device busy time, idle gaps and kernel time.

The reduction works on plain tuples, so a test can hand it a trace built
by hand: an event is ``(name, start_ns, end_ns, text)``.  On a TPU the
``XLA Ops`` line names each op by its HLO text, ``%<op> = <type> ...``;
``name`` is then ``<op>`` (say ``change_score_pallas.1``), what a kernel
is looked up by, and ``text`` keeps the op's result type for the report.
Ops nest on that line (a ``while`` holds its body's ops), so busy time is
the union of the intervals, and an op's own time leaves out its children.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> list of op events
    host: list  # host annotation events


def load(trace_dir: str, host_names=None) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``.  Device events are
    those of each TPU plane's ``XLA Ops`` line; host events are kept only
    where their name is in ``host_names`` (all when None)."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, _, rest = e.name.partition(" = ")
                    ops.append((name.lstrip("%"), e.start_ns, e.end_ns,
                                re.sub(r"\{[^}]*\}", "", rest.split(" ")[0])[:60]))
            devices[plane.name] = ops
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if host_names is None or e.name in host_names:
                        host.append((e.name, e.start_ns, e.end_ns, e.name))
    return Trace(devices=devices, host=host)


def span_of(trace: Trace, name: str) -> tuple[float, float]:
    """``(start_ns, end_ns)`` of the first host event called ``name``."""
    for n, s, e, _ in trace.host:
        if n == name:
            return s, e
    raise KeyError(f"no host span {name!r} in the trace")


def clip(events, lo: float, hi: float) -> list:
    """Events cut to ``[lo, hi]``; those outside are dropped."""
    out = []
    for n, s, e, lab in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((n, s, e, lab))
    return out


def union(events) -> list:
    """Merged ``(start, end)`` intervals covered by ``events``."""
    merged = []
    for _, s, e, _ in sorted(events, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(events, lo: float, hi: float) -> float:
    return float(sum(e - s for s, e in union(clip(events, lo, hi))))


def gaps(events, lo: float, hi: float) -> list:
    """The idle ``(start, end)`` intervals of ``[lo, hi]``."""
    out, at = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def label_gap(gap, host, skip=("window",)) -> str:
    """The innermost host span that covers the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for n, s, e, _ in host:
        if n in skip or not s <= mid <= e:
            continue
        if best is None or e - s < best[1]:
            best = (n, e - s)
    return best[0] if best else "host"


def op_ns(events, pattern: str) -> tuple[float, int]:
    """Summed device time and count of the ops whose name holds ``pattern``."""
    hit = [x for x in events if pattern in x[0]]
    return float(sum(e - s for _, s, e, _ in hit)), len(hit)


def self_ns(events) -> list:
    """``(name, text, own ns)`` of each event: its time less that of the
    events nested directly inside it."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [float(e[2] - e[1]) for e in events]
    stack: list = []
    for i in order:
        _, s, e, _ = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(ev[0], ev[3], t) for ev, t in zip(events, own)]


def top_ops(events, lo: float, hi: float, n: int = 10) -> list:
    """``[["<op> <type>", seconds], ...]``: the ``n`` ops whose own time,
    summed over their runs, was longest."""
    tot: dict = {}
    for name, text, t in self_ns(clip(events, lo, hi)):
        key = f"{name} {text}".strip()
        tot[key] = tot.get(key, 0.0) + t
    best = sorted(tot.items(), key=lambda x: -x[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def top_gaps(events, host, lo: float, hi: float, n: int = 10) -> list:
    """``[[host span, seconds], ...]``: the ``n`` longest idle gaps."""
    g = sorted(gaps(events, lo, hi), key=lambda x: x[0] - x[1])[:n]
    return [[label_gap(x, host), (x[1] - x[0]) * 1e-9] for x in g]


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers read: the traced window, the device busy
    time averaged over the chips, and each chip's device ops cut to the
    window, which a reader looks up by the text its ops' names hold."""

    window_s: float
    busy_s: float
    ops: list  # per chip: (name, start_ns, end_ns, text) cut to the window
    device_ops: list
    idle_gaps: list

    def op_s(self, pattern: str) -> float:
        """Device seconds of the ops whose name holds ``pattern``, summed
        over the chips."""
        return sum(op_ns(p, pattern)[0] for p in self.ops) * 1e-9

    def op_calls(self, pattern: str) -> int:
        """How many ops' names hold ``pattern``, over the chips."""
        return sum(op_ns(p, pattern)[1] for p in self.ops)


def reduce(trace: Trace, window: str) -> Reduced:
    """Reduce ``trace`` over the host span ``window``."""
    lo, hi = span_of(trace, window)
    if not trace.devices:
        raise ValueError("the trace holds no TPU device plane")
    planes = [clip(p, lo, hi) for p in trace.devices.values()]
    busy = sum(busy_ns(p, lo, hi) for p in planes) / len(planes)
    first = planes[0]
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9, ops=planes,
        device_ops=top_ops(first, lo, hi),
        idle_gaps=top_gaps(first, trace.host, lo, hi),
    )
