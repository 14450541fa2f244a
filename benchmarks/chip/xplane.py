"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per device, the busy time inside the measured window and
the device time of each XLA module and op; and the idle gaps between
device work, named by the benchmark's host span that covers each.

A TPU trace has one plane per chip (``/device:TPU:<n>``) with the lines
``XLA Modules`` (one event per executed program) and ``XLA Ops`` (one per
op inside it), and a ``/host:CPU`` plane whose lines hold the host's
``TraceAnnotation`` spans.  All events share one clock, in nanoseconds.
Busy time is the union of the module events, clipped to the window.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"      # the host span that marks the window
HOST_PREFIX = "bench."            # the benchmark's own host spans

_MODULE_RE = re.compile(r"^(.*?)(\(\d+\))?$")
_OP_RE = re.compile(r"^%?([\w\-.]+?)(\.\d+)?(\s*=.*)?$", re.S)


def module_name(event_name: str) -> str:
    """``jit_dft_stage1_batched(8390...)`` -> ``jit_dft_stage1_batched``."""
    return _MODULE_RE.match(event_name.strip()).group(1)


def op_name(event_name: str) -> str:
    """``%dft_stage1_batched.1 = (f32[...]) custom-call(...)`` ->
    ``dft_stage1_batched``."""
    m = _OP_RE.match(event_name.strip())
    return m.group(1) if m else event_name.split(" ", 1)[0]


@dataclasses.dataclass
class Device:
    name: str
    busy_s: float = 0.0
    modules: dict = dataclasses.field(default_factory=dict)   # name -> s
    ops: dict = dataclasses.field(default_factory=dict)       # name -> s
    busy: list = dataclasses.field(default_factory=list)      # [(t0, t1)] ns


@dataclasses.dataclass
class Reduction:
    window_s: float
    devices: list                  # [Device], one per chip, by name
    host_spans: list               # [(t0_ns, t1_ns, name)], bench.* spans
    gaps: dict                     # host span name -> idle seconds (mean)
    longest_gaps: list             # [(name, seconds)], longest first

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def module_s(self, names) -> float:
        """Device seconds of the named modules, summed over chips."""
        return sum(s for d in self.devices for n, s in d.modules.items()
                   if n in names)


def find_trace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _clip(t0: float, t1: float, lo: float, hi: float) -> float:
    return max(0.0, min(t1, hi) - max(t0, lo))


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce_trace(path: str, top: int = 10) -> Reduction:
    """Reduce the trace at ``path`` over the window its ``bench.window``
    host span marks."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host_spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host_spans.append((e.start_ns,
                                           e.start_ns + e.duration_ns,
                                           e.name))
    windows = [s for s in host_spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, "
                           f"found {len(windows)}")
    lo, hi, _ = windows[0]
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = Device(plane.name)
        for line in plane.lines:
            if line.name not in ("XLA Modules", "XLA Ops"):
                continue
            into = dev.modules if line.name == "XLA Modules" else dev.ops
            name_of = module_name if line.name == "XLA Modules" else op_name
            for e in line.events:
                t0, t1 = e.start_ns, e.start_ns + e.duration_ns
                inside = _clip(t0, t1, lo, hi)
                if inside <= 0.0:
                    continue
                n = name_of(e.name)
                into[n] = into.get(n, 0.0) + inside * 1e-9
                if line.name == "XLA Modules":
                    dev.busy.append((max(t0, lo), min(t1, hi)))
        dev.busy = _union(dev.busy)
        dev.busy_s = sum(b - a for a, b in dev.busy) * 1e-9
        devices.append(dev)
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    devices.sort(key=lambda d: d.name)
    host = sorted(s for s in host_spans if s[2] != WINDOW_SPAN)
    gaps, longest = _gaps(devices, host, lo, hi, top)
    return Reduction(window_s=(hi - lo) * 1e-9, devices=devices,
                     host_spans=host, gaps=gaps, longest_gaps=longest)


def _gaps(devices, host, lo, hi, top):
    """Idle time of each device between its busy intervals, split by the
    innermost benchmark host span covering it (``idle`` where none does),
    averaged over devices; and the longest single gaps."""
    per_name = collections.defaultdict(float)
    longest = []
    starts = [s[0] for s in host]
    reach = max((s[1] - s[0] for s in host), default=0)
    for dev in devices:
        edges = [lo] + [t for ab in dev.busy for t in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            near = host[bisect.bisect_left(starts, a - reach):
                        bisect.bisect_left(starts, b)]
            parts = _split_by_host(a, b, near)
            for name, ns in parts.items():
                per_name[name] += ns * 1e-9 / len(devices)
            name = max(parts.items(), key=lambda kv: kv[1])[0]
            longest.append((name, (b - a) * 1e-9))
    longest.sort(key=lambda kv: -kv[1])
    return dict(per_name), longest[:top]


def _split_by_host(a, b, host):
    """Nanoseconds of [a, b) under each host span name, innermost (the
    latest-starting covering span) winning; the rest is ``idle``."""
    covering = [s for s in host if s[0] < b and s[1] > a]
    if not covering:
        return {"idle": b - a}
    points = sorted({a, b} | {max(a, s[0]) for s in covering}
                    | {min(b, s[1]) for s in covering})
    out = collections.defaultdict(float)
    for p, q in zip(points, points[1:]):
        mid = (p + q) / 2
        inner = [s for s in covering if s[0] <= mid < s[1]]
        name = max(inner, key=lambda s: s[0])[2] if inner else "idle"
        out[name] += q - p
    return out


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most
    time (summed over chips) and the longest idle gaps by host span."""
    ops = collections.Counter()
    for d in red.devices:
        ops.update(d.ops)
    return {"device_ops": [[n, s] for n, s in ops.most_common(top)],
            "idle_gaps": [[n, s] for n, s in red.longest_gaps[:top]]}
