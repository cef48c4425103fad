"""Reduction of a profiler trace (`.xplane.pb`) to the device's numbers.

Busy time is the union of the intervals in which an operation runs on a
device; the idle share is 1 - busy / window, mean over the chips. The
`breakdown` names the operations with the most device time and the longest
idle gaps, each gap with the host span (`jax.profiler.TraceAnnotation`, which
the program's `utils/trace.py` spans mirror) that covered most of it.

Device planes are those named `/device:TPU:<n>`. Their operations are the
events of the line `XLA Ops`; the other lines (`XLA Modules`, `Steps`, ...)
span whole programs, idle time inside them included, and are not busy time.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str | None:
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def read(path: str, device_prefix: str = DEVICE_PREFIX,
         ops_line: str = OPS_LINE) -> dict:
    """{"devices": {plane: [(name, start_ns, end_ns)]}, "host": [...]}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            ops = []
            for line in plane.lines:
                if line.name == ops_line:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if not e.name.startswith("$"))  # python frames
    return {"devices": devices, "host": host}


def merge(intervals) -> list:
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def clip(ops, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops
            if e > lo and s < hi]


def device_window(trace: dict) -> tuple:
    """First start to last end of any device operation: the traced window."""
    starts = [s for ops in trace["devices"].values() for _, s, _ in ops]
    ends = [e for ops in trace["devices"].values() for _, _, e in ops]
    if not starts:
        raise ValueError("no operation ran on a device in this trace")
    return min(starts), max(ends)


def busy_and_window(trace: dict, window: tuple | None = None) -> tuple:
    """(busy seconds, mean over the device planes; window seconds)."""
    window = window or device_window(trace)
    busy = []
    for ops in trace["devices"].values():
        merged = merge((s, e) for _, s, e in clip(ops, window))
        busy.append(sum(e - s for s, e in merged))
    return (sum(busy) / len(busy)) * 1e-9, (window[1] - window[0]) * 1e-9


def idle_share_percent(trace: dict, window: tuple | None = None) -> float:
    busy_s, window_s = busy_and_window(trace, window)
    return 100.0 * (1.0 - busy_s / window_s)


def short_name(name: str) -> str:
    """The trace prints an operation as its whole HLO instruction
    (`%fusion.215 = (s32[], ...) fusion(...)`): keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def self_times(ops) -> list:
    """[(name, self_ns)]: an operation's time less the time of the operations
    nested inside it (a `while` and the operations of its body are all
    events of the one line)."""
    out, stack = [], []        # stack of [name, end, self_ns]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((name, self_ns) for name, _, self_ns in stack)
    return out


def top_ops(trace: dict, n: int = 10, window: tuple | None = None) -> list:
    """[[name, seconds]] of the operations with the most device time of
    their own (nested operations not counted twice), mean over the device
    planes."""
    window = window or device_window(trace)
    total: dict = {}
    for ops in trace["devices"].values():
        for name, self_ns in self_times(clip(ops, window)):
            key = short_name(name)
            total[key] = total.get(key, 0.0) + self_ns
    planes = max(len(trace["devices"]), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / planes] for name, ns in ranked]


def idle_gaps(trace: dict, n: int = 5, window: tuple | None = None) -> list:
    """[[host span, seconds]] for the n longest gaps in which no operation
    ran on the first device plane; the span named is the host event that
    overlaps the gap most (`(no host span)` where none does)."""
    window = window or device_window(trace)
    name0 = sorted(trace["devices"])[0]
    merged = merge((s, e) for _, s, e in clip(trace["devices"][name0], window))
    edges = [window[0]] + [x for iv in merged for x in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for lo, hi in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, cover, best_len = "(no host span)", 0.0, float("inf")
        for hname, hs, he in trace["host"]:
            c = min(he, hi) - max(hs, lo)
            # the tightest span that covers most: prefer larger overlap,
            # and on a tie the shorter (more specific) span
            if c > cover or (c == cover and c > 0 and he - hs < best_len):
                best, cover, best_len = hname, c, he - hs
        out.append([best, (hi - lo) * 1e-9])
    return out


def breakdown(trace: dict, window: tuple | None = None) -> dict:
    return {"device_ops": top_ops(trace, 10, window),
            "idle_gaps": idle_gaps(trace, 5, window)}
