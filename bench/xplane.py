"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time per chip, device time per operation name, the
idle gaps between operations, and the host span that was open in each gap.

The trace is read with ``jax.profiler.ProfileData``. A device is a plane
whose name starts with ``/device:``; its operations are the events of its
``XLA Ops`` line and its programs those of its ``XLA Modules`` line. Host
spans are the events of the host plane's lines. The window is the host
span named ``bench.window``, which the harness opens around the measured
loop; all times are nanoseconds on the trace's own clock.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations that hold others: they count as busy, but their time is
# their children's, so they are left out of the per-operation breakdown
CONTAINERS = re.compile(r"^(while|conditional|call)\b")


def short(name: str) -> str:
    """``%fusion.5 = s32[...] fusion(...)`` → ``fusion.5``: a TPU trace
    names each operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    window: tuple                 # (start, end) of the measured window
    ops: dict                     # device → [(name, start, end)], sorted
    modules: dict                 # device → [(name, start, end)], sorted
    host: list                    # [(name, start, end)] host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> list:
        return sorted(self.ops)


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the newest one under a directory, or a
    gzipped one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                evs = sorted(((short(e.name), e.start_ns,
                               e.start_ns + e.duration_ns)
                              for e in line.events), key=lambda e: e[1])
                if line.name == OPS_LINE:
                    ops[plane.name] = evs
                elif line.name == MODULES_LINE:
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if spans:
        window = (spans[0][1], spans[0][2])
    else:
        ends = [e for evs in ops.values() for e in evs]
        window = (min(e[1] for e in ends), max(e[2] for e in ends))
    return Trace(window, ops, modules, sorted(host, key=lambda h: h[1]))


def complete(trace: Trace) -> bool:
    """Whether every chip's operations run to the window's end: the
    profiler stops recording a chip's operations once its buffer is full,
    which would read as idle time."""
    lo, hi = trace.window
    last = [max((e for _, _, e in trace.ops[d]), default=lo)
            for d in trace.devices]
    mods = [max((e for _, _, e in trace.modules.get(d, [])), default=lo)
            for d in trace.devices]
    return all(m <= o + 0.01 * (hi - lo) for o, m in zip(last, mods))


def clip(intervals, lo, hi):
    """Intervals cut to [lo, hi], empty ones dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in intervals
            if min(e, hi) > max(s, lo)]


def union(intervals):
    """Merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for _, s, e in sorted(intervals, key=lambda i: i[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(trace: Trace, device: str) -> int:
    lo, hi = trace.window
    return sum(e - s for s, e in union(clip(trace.ops[device], lo, hi)))


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.ops:
        return 0.0
    return sum(busy_ns(trace, d) for d in trace.devices) * 1e-9 / len(
        trace.ops)


def idle_share(trace: Trace) -> float:
    """1 − busy / window, in %, averaged over the chips."""
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


def gaps(trace: Trace, device: str):
    """Idle [(start, end)] of one device within the window."""
    lo, hi = trace.window
    out, t = [], lo
    for s, e in union(clip(trace.ops[device], lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_ns(trace: Trace, device: str, lo: int, hi: int) -> int:
    """Idle time of one device within [lo, hi]."""
    busy = sum(e - s for s, e in union(clip(trace.ops[device], lo, hi)))
    return (hi - lo) - busy


def op_ns(trace: Trace, pattern: str, device: str | None = None) -> int:
    """Device time of the operations whose name matches ``pattern``
    (summed over the chips, or on one)."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    devs = trace.devices if device is None else [device]
    return sum(e - s for d in devs for n, s, e in clip(trace.ops[d], lo, hi)
               if rx.search(n))


def module_spans(trace: Trace, pattern: str, device: str):
    """Device [(start, end)] of the programs whose name matches."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    return [(s, e) for n, s, e in clip(trace.modules.get(device, []), lo, hi)
            if rx.search(n)]


def host_span_at(trace: Trace, start: int, end: int, names) -> str:
    """The named host span that covers most of [start, end]."""
    best, cover = "other", 0
    for n, s, e in trace.host:
        if n not in names or s >= end:
            continue
        c = min(e, end) - max(s, start)
        if c > cover:
            best, cover = n, c
    return best


def breakdown(trace: Trace, span_names, top: int = 10) -> dict:
    """Top device operations by time, and the longest idle gaps named by
    the host span open in them, on the first chip."""
    if not trace.devices:
        return {"device_ops": [], "idle_gaps": []}
    dev = trace.devices[0]
    lo, hi = trace.window
    per_op = {}
    for n, s, e in clip(trace.ops[dev], lo, hi):
        if not CONTAINERS.match(n):
            per_op[n] = per_op.get(n, 0) + (e - s)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(trace, dev), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, t * 1e-9] for n, t in ops],
        "idle_gaps": [[host_span_at(trace, s, e, span_names), (e - s) * 1e-9]
                      for s, e in idle],
    }
