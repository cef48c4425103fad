"""The device's idle time a serving tick, taken apart where it happens.

`partition` gives every instant of the traced window in which no operation
ran on the first device plane to the INNERMOST event of the engine's thread
over it (`utils/trace.py`'s `serve_tick_*`, `serve_admit`, `serve_prefill*`
annotations on the host plane; the events of one thread nest), so the parts
sum to the idle time exactly:

    emit, stage              the tick's two phases that call only Python
    grow, h2d, enqueue       the parts of `serve_tick_dispatch`: page growth,
                             the staged arrays' copies, the jitted call
    dispatch_other           the rest of it (adopting the outputs)
    launch, between_ops,     under `serve_tick_block`, read from the device's
    wake                     side: idle before the first instant the device is
                             busy inside the event, between its operations and
                             programs, and after the last busy instant (all of
                             it where the device is never busy inside it)
    fetch                    the conversions of token, keys and counters
    wait_other               the rest of `serve_tick_wait`: its clock reads; the
                             whole of it in a capture of a build that has no
                             `serve_tick_block`
    admit                    under `serve_admit`, outside `serve_prefill`
    prefill_host             under `serve_prefill`
    loop                     under no event of the engine

The profiler puts the device planes on the host plane's clock only roughly: on
the v5e the device's operations lie about 1.4 ms EARLY (PR 34: a tick's first
operation 0.7 ms before the call that enqueues its program begins), which is
the size of the parts. `device_clock_shift` finds the least shift of the
device's clock at which the trace is causal: no operation runs between the
return of a `serve_tick_block` (everything enqueued has finished) and the next
time the runtime hands the device a program. `partition` applies it. How much
more would still be causal is its `slack_ns`, which could move from `wake` to
`launch`: on the v5e as much as the two parts themselves, so one reader gives
their sum (`tick_gap_launch_wake_ms.serve`) and the partition prints them
apart for the eye.

`clock_offset` reads the wall-clock anchors (`wallclock_us=<microseconds>`
annotations, `utils/trace.wallclock_anchor`): the offset between the profiler's
clock and `time.time()`, with which `spans_of_trace` picks the
`serve_decode_step` spans of the ticks that were traced.
"""

from __future__ import annotations

import bisect
import statistics

from benchmark import xplane

WAIT = "serve_tick_wait"
BLOCK = "serve_tick_block"
ANCHOR_PREFIX = "wallclock_us="
# an offset whose anchors disagree by more is not used to select spans
MAX_SPREAD_US = 100.0
# the TPU runtime's own host event around the hand-over of a program to the
# device (libtpu 0.0.34; on another thread, and later than the call that
# asked for it): what `device_clock_shift` tests the device's clock by
DOORBELL = "DoEnqueueProgram"
MAX_SHIFT_NS = 5_000_000

# innermost engine event -> the part its idle time goes to (`BLOCK` apart)
PART_OF = {
    "serve_tick_emit": "emit", "serve_tick_stage": "stage",
    "serve_tick_grow": "grow", "serve_tick_h2d": "h2d",
    "serve_tick_enqueue": "enqueue", "serve_tick_dispatch": "dispatch_other",
    "serve_tick_fetch": "fetch", WAIT: "wait_other", "serve_admit": "admit",
    "serve_prefill": "prefill_host", "serve_prefill_enqueue": "prefill_host",
    "serve_prefill_first": "prefill_host",
}
PARTS = ("emit", "stage", "grow", "h2d", "enqueue", "dispatch_other",
         "launch", "between_ops", "wake", "fetch", "wait_other", "admit",
         "prefill_host", "loop")


def innermost(events) -> list:
    """[(start, end, name)], sorted and disjoint: every instant under at
    least one of `events` [(name, start, end)], named by the event over it
    that began last."""
    out, stack, cursor = [], [], 0          # stack of (name, end)

    def close(upto):
        nonlocal cursor
        if stack and upto > cursor:
            out.append((cursor, upto, stack[-1][0]))
        cursor = max(cursor, upto)

    for name, start, end in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= start:
            close(stack[-1][1])
            stack.pop()
        close(start)
        stack.append((name, end))
    while stack:
        close(stack[-1][1])
        stack.pop()
    return out


def _first_plane_busy(trace: dict, window: tuple | None = None) -> list:
    plane = trace["devices"][sorted(trace["devices"])[0]]
    ops = plane if window is None else xplane.clip(plane, window)
    return xplane.merge((s, e) for _, s, e in ops)


def device_clock_shift(trace: dict):
    """{"shift_ns", "slack_ns", "zones"}: the shift of the first device
    plane's clock, the one nearest to none, at which no operation runs inside
    a zone in which the device has nothing to run: from the end of a
    `serve_tick_block` to the runtime's next `DoEnqueueProgram`. `slack_ns`:
    the width of the range of causal shifts of which the chosen one is the
    end nearest to none. None where the trace holds no such zone (a runtime
    that does not name its hand-overs so: the engine's own events begin too
    early to bound the shift as closely) or no shift within `MAX_SHIFT_NS`
    clears them all."""
    host = trace.get("host") or ()
    blocks = sorted(e for n, _, e in host if n == BLOCK)
    handovers = sorted(s for n, s, _ in host if n == DOORBELL)
    zones = []
    for end in blocks:
        k = bisect.bisect_left(handovers, end)
        if k < len(handovers):
            zones.append((end, handovers[k]))
    if not zones or not any((trace.get("devices") or {}).values()):
        return None
    busy = _first_plane_busy(trace)
    starts, ends = [b[0] for b in busy], [b[1] for b in busy]
    # an operation [d0, d1) lies in a zone [z0, z1) for shifts in (z0 - d1,
    # z1 - d0): the shifts that are left are the causal ones
    forbidden = []
    for z0, z1 in zones:
        a = bisect.bisect_right(ends, z0 - MAX_SHIFT_NS)
        b = bisect.bisect_left(starts, z1 + MAX_SHIFT_NS)
        forbidden.extend((z0 - d1, z1 - d0) for d0, d1 in busy[a:b])
    allowed, cursor = [], -MAX_SHIFT_NS
    for f0, f1 in xplane.merge(forbidden):
        if f0 > cursor:
            allowed.append((cursor, min(f0, MAX_SHIFT_NS)))
        cursor = max(cursor, f1)
    if cursor < MAX_SHIFT_NS:
        allowed.append((cursor, MAX_SHIFT_NS))
    if not allowed:
        return None
    nearness = lambda ab: 0 if ab[0] <= 0 <= ab[1] else min(map(abs, ab))
    a, b = min(allowed, key=nearness)
    shift = 0 if a <= 0 <= b else a if a > 0 else b
    return {"shift_ns": shift, "slack_ns": b - a, "zones": len(zones)}


def partition(trace: dict, window: tuple | None = None, shift_ns: float = 0):
    """{"ticks", "window_ns", "idle_ns", "parts_ns": {part: ns}} of the first
    device plane inside `window` (the device window), its clock moved by
    `shift_ns` against the host plane's; None where the trace holds no device
    operation or no `serve_tick_wait` event (one a tick)."""
    if not any((trace.get("devices") or {}).values()):
        return None
    lo, hi = window or xplane.device_window(trace)
    # the host's events on the device's clock: the same as moving the plane
    events = [(n, max(s - shift_ns, lo), min(e - shift_ns, hi))
              for n, s, e in trace["host"]
              if (n in PART_OF or n == BLOCK)
              and e - shift_ns > lo and s - shift_ns < hi]
    ticks = sum(1 for n, _, _ in events if n == WAIT)
    if not ticks:
        return None
    busy = _first_plane_busy(trace, (lo, hi))
    starts, ends = [b[0] for b in busy], [b[1] for b in busy]
    edges = [lo] + [x for b in busy for x in b] + [hi]
    gaps = [g for g in zip(edges[0::2], edges[1::2]) if g[1] > g[0]]

    def block_bounds(s, e):
        """First and last busy instant inside [s, e), or None."""
        a = bisect.bisect_right(ends, s)
        if a == len(busy) or starts[a] >= e:
            return None
        b = bisect.bisect_left(starts, e) - 1
        return max(s, starts[a]), min(e, ends[b])

    parts = dict.fromkeys(PARTS, 0)
    segments = innermost(events)
    i, bounds_of = 0, (None, None)          # (segment index, its bounds)
    for g0, g1 in gaps:                     # both lists sorted and disjoint
        while i < len(segments) and segments[i][1] <= g0:
            i += 1
        j, covered = i, 0
        while j < len(segments) and segments[j][0] < g1:
            s, e, name = segments[j]
            p0, p1 = max(s, g0), min(e, g1)
            if name != BLOCK:
                part = PART_OF[name]
            else:
                if bounds_of[0] != j:
                    bounds_of = (j, block_bounds(s, e))
                bounds = bounds_of[1]
                # an idle piece cannot straddle a busy instant
                if bounds is None or p0 >= bounds[1]:
                    part = "wake"
                elif p1 <= bounds[0]:
                    part = "launch"
                else:
                    part = "between_ops"
            parts[part] += p1 - p0
            covered += p1 - p0
            j += 1
        parts["loop"] += (g1 - g0) - covered
    return {"ticks": ticks, "window_ns": hi - lo,
            "idle_ns": sum(g1 - g0 for g0, g1 in gaps), "parts_ns": parts}


def shifted_partition(trace: dict) -> tuple:
    """(the partition with the device's clock moved by `device_clock_shift`,
    or as it is where none is found; that shift or None)."""
    shift = device_clock_shift(trace)
    return partition(trace, shift_ns=shift["shift_ns"] if shift else 0), shift


def of_observation(obs: dict):
    """`shifted_partition` of a traced serving run whose program has the
    nested events (`serve_tick_block` tells launch from wake); None
    otherwise, and where the partition is."""
    trace = obs.get("xplane") or {}
    if obs.get("kind") != "serve" or not any(
            name == BLOCK for name, _, _ in trace.get("host") or ()):
        return None
    found = shifted_partition(trace)
    return None if found[0] is None else found


def describe_shift(shift) -> str:
    if shift is None:
        return (f"device clock as the profiler placed it (no zone from a "
                f"block's end to a `{DOORBELL}` to test it by, or no causal "
                f"shift): the parts follow the capture's skew")
    return (f"device clock moved by {1e-3 * shift['shift_ns']:+.1f} us, the "
            f"least at which no operation runs in {shift['zones']} zones "
            f"without work (block end to {DOORBELL}); "
            f"{1e-3 * shift['slack_ns']:.1f} us more would be causal too: as "
            f"much of `wake` may be `launch`")


def ms_a_tick(part: dict, *names) -> float:
    """The named parts together (all of them where none is named), in
    milliseconds a tick."""
    ns = sum(part["parts_ns"][n] for n in names) if names else part["idle_ns"]
    return 1e-6 * ns / part["ticks"]


def clock_offset(trace: dict):
    """{"anchors", "offset_us", "spread_us", "range_us"} from the trace's
    wall-clock anchors: the median over them of (the event's start on the
    profiler's clock - the stamp in its name), the distance between the
    quartiles of those differences (None with one anchor: not known) and
    between their extremes. None where the trace holds no anchor."""
    diffs = []
    for name, start, _ in trace.get("host") or ():
        if name.startswith(ANCHOR_PREFIX):
            stamp = name[len(ANCHOR_PREFIX):]
            if stamp.isdigit():
                diffs.append(start / 1e3 - int(stamp))
    if not diffs:
        return None
    spread = None
    if len(diffs) > 1:
        q = statistics.quantiles(diffs, n=4)
        spread = q[2] - q[0]
    return {"anchors": len(diffs), "offset_us": statistics.median(diffs),
            "spread_us": spread, "range_us": max(diffs) - min(diffs)}


def describe_clock(clock) -> str:
    if clock is None:
        return "no wall-clock anchor in the trace"
    spread = ("not known" if clock["spread_us"] is None
              else f"{clock['spread_us']:.1f} us")
    return (f"profiler clock - wall clock = {clock['offset_us']:.1f} us over "
            f"{clock['anchors']} anchors, spread {spread}, range "
            f"{clock['range_us']:.1f} us")


def spans_of_trace(obs: dict, attr: str) -> tuple:
    """(the `serve_decode_step` spans that hold `attr` and began inside the
    traced window, how they were chosen): the device window less the clock
    offset, on `ts`. The whole observation's spans where the trace holds no
    anchor, where its anchors disagree by `MAX_SPREAD_US` or more, and where
    no span began inside the traced window."""
    spans = [s for s in obs.get("spans") or ()
             if s["name"] == "serve_decode_step" and attr in s]
    trace = obs.get("xplane") or {}
    clock = clock_offset(trace)
    if (clock is None or clock["spread_us"] is None
            or clock["spread_us"] >= MAX_SPREAD_US):
        return spans, f"every span of the window ({describe_clock(clock)})"
    lo, hi = ((w / 1e3 - clock["offset_us"]) * 1e-6
              for w in xplane.device_window(trace))
    chosen = [s for s in spans if lo <= s["ts"] <= hi]
    if not chosen:
        return spans, "every span of the window (none began in the trace)"
    return chosen, (f"{len(chosen)} of {len(spans)} spans, those that began "
                    f"in the traced {hi - lo:.3f} s")


def traced_serving(obs: dict) -> bool:
    """A serving run whose capture holds a device plane."""
    return obs.get("kind") == "serve" and bool(
        (obs.get("xplane") or {}).get("devices"))


def traced_ms_a_tick(obs: dict, attr: str, reader: str):
    """Sum `attr` over sum `ticks` of `spans_of_trace`, in milliseconds, for
    the reader of that name, which prints how the spans were chosen; None for
    an untraced run and where no span holds `attr`."""
    if not traced_serving(obs):
        return None
    spans, how = spans_of_trace(obs, attr)
    ticks = sum(s["ticks"] for s in spans)
    if not ticks:
        return None
    print(f"{reader}: {how}", flush=True)
    return 1e3 * sum(s[attr] for s in spans) / ticks


def describe(part: dict) -> str:
    """`<ms a tick> ms a tick over <n> ticks: part=ms, ...`, parts that are
    zero left out."""
    shown = ", ".join(f"{name}={ms_a_tick(part, name):.3f}"
                      for name in PARTS if part["parts_ns"][name])
    return (f"{ms_a_tick(part):.3f} ms a tick over {part['ticks']} ticks "
            f"(idle {100.0 * part['idle_ns'] / part['window_ns']:.2f}% of "
            f"{1e-9 * part['window_ns']:.3f} s): {shown}")
