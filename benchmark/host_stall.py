"""The serving engine's thread, as it accounts for itself (PR 50).

Since PR 50 the pending `serve_decode_step` span carries, summed over its
STEPS and flushed with the tick's own sums (`serve/engine.py` `HOST_SUMS`,
`HOST_COUNTS`):

    admit_s, unit_wait_s,      seconds the tick's four phases lacked: over a
    block_s, loop_s, step_s    run `admit_s + stage_s + dispatch_s + wait_s +
                               unit_wait_s + emit_s + loop_s` is `step_s`;
                               `block_s` is the `serve_tick_block` part of
                               `wait_s`
    gc_s, gc_collections,      what held the thread: the collector OUTSIDE its
    gc_gen2, wait_gc_s,        two device waits (`serve_tick_block`,
    compile_s, compiles        `serve_prefill_first`) and inside them (other
                               threads' collections while it slept), and the
                               compiler over the steps
    ticks_found_ready          ticks whose block returned at once with no
                               prefill unit enqueued behind them: the device
                               had finished before the host came for it and
                               had only the next tick to go on with

and a list `stalls` of records, one a phase of host work of 20 ms or more (or
a device wait in which other threads' collections ran that long: `in_wait`):
`phase`, `ts` (wall clock), `dur`, what of THAT phase the collector and the
compiler held (`gc_s`, `compile_s`) and `other_s`, what neither did.

**What a record cannot see.** The thread makes a record of its own WORK that
took too long. While it sleeps in a device wait, the wait's length is the
device's work and proves nothing, so a process stopped from outside (the
machine, its sandbox) while the thread sits in `block_until_ready` makes no
record however long the device idles: on the benchmark's host a gap of
3.2 s under `serve_tick_wait` read a stall share of 0.4% (PERF.md section 6,
PR 50). Only a capture sees those: `join` lists every idle gap of `GAP_S` or
more of which the records cover under half, and `describe_joined` prints them.

Everything here reads those names and returns None where a program does not
write them (a build before PR 50). The three readers
(`benchmark/layer_metrics/{host_stall_share,gc_pause_share,
host_bound_tick_share}.serve.py`) and `tools/trace_summary.py` share this
arithmetic. In a traced run a record is placed on the capture by the
wall-clock anchors (`tick_gap.clock_offset`: 2 us) and set against the first
device plane's idle time.
"""

from __future__ import annotations

from benchmark import tick_gap, xplane

# over a run these sum to `step_s`; what does not is printed as unaccounted
PHASES = ("admit_s", "stage_s", "dispatch_s", "wait_s", "unit_wait_s",
          "emit_s", "loop_s")
# summed over the spans where present
SUMMED = PHASES + (
    "step_s", "block_s", "gc_s", "compile_s", "wait_gc_s", "steps", "ticks",
    "gc_collections", "gc_gen2", "compiles", "ticks_found_ready",
    "stalls_dropped")
# a record's seconds by cause: (name, the record's keys that hold them)
CAUSES = (("collector", ("gc_s", "wait_gc_s")),
          ("compiler", ("compile_s",)),
          ("other", ("other_s",)))
# an idle gap of the device this long of which the records cover under half
# is printed beside them: the engine's `STALL_S`
GAP_S = 0.020
# host events of this program's own, left out where the runtime's are asked for
OWN_PREFIXES = ("serve_", tick_gap.ANCHOR_PREFIX, "py_gc ")


def account_spans(obs: dict) -> list:
    """The observation's `serve_decode_step` spans that carry the thread's
    account."""
    if obs.get("kind") != "serve":
        return []
    return [s for s in obs.get("spans") or ()
            if s.get("name") == "serve_decode_step" and "step_s" in s]


def account(spans) -> dict | None:
    """{name: sum over `spans`} for every name of `SUMMED` that ANY span
    carries (a name none carries is left out: not known, never 0), plus
    `gc_longest_s` (the largest) and `spans`; None for no span."""
    spans = list(spans)
    if not spans:
        return None
    out = {k: sum(s.get(k, 0) for s in spans)
           for k in SUMMED if any(k in s for s in spans)}
    out["gc_longest_s"] = max(s.get("gc_longest_s", 0.0) for s in spans)
    out["spans"] = len(spans)
    return out


def stalls_of(spans) -> list:
    """Every stall record the spans carry, in the order they happened."""
    return sorted((r for s in spans for r in s.get("stalls") or ()),
                  key=lambda r: r["ts"])


def window_s(obs: dict) -> float:
    t0, t1 = obs["window"]
    return t1 - t0


def share_of_window(obs: dict, *names):
    """100 x the sum of `names` over the window's spans / the window's
    seconds; None where no span carries the account or ANY of the names."""
    acc = account(account_spans(obs))
    if acc is None or any(n not in acc for n in names):
        return None
    return 100.0 * sum(acc[n] for n in names) / window_s(obs)


def stall_share(obs: dict):
    """100 x the window's stall records' `dur` / the window's seconds (0
    where the spans carry the account and no record); None where none does."""
    spans = account_spans(obs)
    if not spans:
        return None
    return 100.0 * sum(r["dur"] for r in stalls_of(spans)) / window_s(obs)


def found_ready_share(obs: dict):
    """100 x `ticks_found_ready` / `ticks` over the window's spans; None
    where none carries the account or they hold no tick."""
    acc = account(account_spans(obs))
    if acc is None or not acc.get("ticks"):
        return None
    return 100.0 * acc["ticks_found_ready"] / acc["ticks"]


def unaccounted_s(acc: dict) -> float:
    return acc["step_s"] - sum(acc.get(k, 0.0) for k in PHASES)


def host_share(acc: dict):
    """The share of its own thread's seconds in which the host works: all of
    `step_s` but the two device waits."""
    if not acc["step_s"]:
        return None
    return 100.0 * (acc["step_s"] - acc["block_s"]
                    - acc["unit_wait_s"]) / acc["step_s"]


def describe_partition(acc: dict) -> str:
    """`step_s <s> over <n> steps: phase=share%, ..., unaccounted=share%;
    the host works <share>% of it`."""
    total = acc["step_s"] or float("nan")
    parts = ", ".join(f"{k[:-2]}={100.0 * acc.get(k, 0.0) / total:.2f}%"
                      for k in PHASES)
    return (f"step_s {acc['step_s']:.3f} over {acc.get('steps', 0)} steps: "
            f"{parts}, unaccounted={100.0 * unaccounted_s(acc) / total:.2f}%"
            f" (block {100.0 * acc['block_s'] / total:.2f}% of it inside "
            f"wait); the host works {host_share(acc):.2f}% of its thread")


def describe_causes(acc: dict) -> str:
    """What held the thread outside its waits and inside them."""
    return (f"outside the waits: gc_s {acc['gc_s']:.4f} in "
            f"{acc['gc_collections']} collections ({acc['gc_gen2']} full); "
            f"inside them: wait_gc_s {acc['wait_gc_s']:.4f}; compile_s "
            f"{acc['compile_s']:.4f} in {acc['compiles']} programs")


def cause_seconds(rec: dict) -> dict:
    """{cause: seconds} of one record, in `CAUSES`' order."""
    return {cause: sum(rec.get(k, 0.0) for k in keys)
            for cause, keys in CAUSES}


def split(records) -> tuple:
    """({cause: seconds}, {phase: seconds of `dur`}) over `records`."""
    by_cause = dict.fromkeys((c for c, _ in CAUSES), 0.0)
    by_phase: dict = {}
    for rec in records:
        for cause, seconds in cause_seconds(rec).items():
            by_cause[cause] += seconds
        by_phase[rec["phase"]] = by_phase.get(rec["phase"], 0.0) + rec["dur"]
    return by_cause, by_phase


def describe_record(rec: dict) -> str:
    causes = ", ".join(f"{c} {1e3 * s:.1f}"
                       for c, s in cause_seconds(rec).items() if s)
    return (f"{1e3 * rec['dur']:.1f} ms under {rec['phase']}"
            f"{' (in a device wait)' if rec.get('in_wait') else ''} at "
            f"{rec['ts']:.3f}, step {rec.get('step')}, {rec.get('active')} "
            f"rows, {rec.get('units')} unit(s) in flight: ms by cause: "
            f"{causes or 'none named'}")


# -- a record against a capture ------------------------------------------------

def on_trace(rec: dict, clock: dict) -> tuple:
    """(start_ns, end_ns) of a record on the profiler's clock."""
    start = (rec["ts"] * 1e6 + clock["offset_us"]) * 1e3
    return start, start + rec["dur"] * 1e9


def idle_intervals(trace: dict) -> list:
    """[(start_ns, end_ns)]: where no operation ran on the first device
    plane inside the device window."""
    lo, hi = xplane.device_window(trace)
    plane = trace["devices"][sorted(trace["devices"])[0]]
    busy = xplane.merge((s, e) for _, s, e in xplane.clip(plane, (lo, hi)))
    edges = [lo] + [x for b in busy for x in b] + [hi]
    return [g for g in zip(edges[0::2], edges[1::2]) if g[1] > g[0]]


def _overlap(intervals, lo, hi) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def runtime_events_over(trace: dict, lo: float, hi: float, n: int = 3) -> list:
    """[(name, ns)]: the n host events NOT of this program's own (any
    thread: the runtime's `ReadSyncFlag`, `DeferredTpuAllocator::Allocate`,
    `ExecuteHelperOnSingleDevice`, ...) with the most time inside
    [lo, hi), summed by name."""
    by_name: dict = {}
    for name, s, e in trace.get("host") or ():
        if e > lo and s < hi and not name.startswith(OWN_PREFIXES):
            by_name[name] = by_name.get(name, 0.0) + min(e, hi) - max(s, lo)
    return sorted(by_name.items(), key=lambda kv: -kv[1])[:n]


def events_begun(trace: dict, lo: float, hi: float) -> tuple:
    """(host events of any thread, this program's own among them, that BEGAN
    inside [lo, hi); the number the capture's mean rate would put there).
    Next to none where many are due says that no thread of the process ran:
    the machine or its sandbox held all of it, not a lock one thread."""
    starts = [s for _, s, _ in trace.get("host") or ()]
    if not starts:
        return 0, 0.0
    span = (max(starts) - min(starts)) or 1
    return (sum(1 for s in starts if lo <= s < hi),
            len(starts) * (hi - lo) / span)


def engine_event_over(trace: dict, lo: float, hi: float) -> str:
    """The engine's own host event (`serve_...`) with the most time inside
    [lo, hi), of two that have as much the shorter; `(none)` where none
    touches it."""
    best, cover, best_len = "(none)", 0.0, float("inf")
    for name, s, e in trace.get("host") or ():
        if not name.startswith("serve_"):
            continue
        c = min(e, hi) - max(s, lo)
        if c > cover or (c == cover and c > 0 and e - s < best_len):
            best, cover, best_len = name, c, e - s
    return best


def join(obs: dict, records) -> dict | None:
    """The records that lie inside the capture set against the device's
    idle time: {"clock", "idle_ns", "idle_in_stalls_ns", "uncovered" (the
    idle gaps of `GAP_S` or more of which records cover under half, longest
    first:
    [{"start_ns" (from the device window's start), "gap_ns", "under"
    (`engine_event_over` it), "begun" (`events_begun` in it)}]: what the
    thread's own account cannot see, a device wait that is long because
    the process stood still), "records":
    [{"record", "start_ns", "end_ns", "idle_ns" (idle time inside it),
    "gap_ns" (the longest idle gap that touches it), "gap_starts_ns" and
    "gap_ends_ns" (that gap's start less the record's start, its end less
    the record's end: a tick in flight keeps the device busy for up to a
    tick after a host stall begins, and the gap ends a dispatch after it
    ends; None where no gap touches it), "runtime" (`runtime_events_over`
    it), "begun" (`events_begun` in it)}]}. None for an untraced run and
    where the capture holds no anchor."""
    trace = obs.get("xplane") or {}
    if not any((trace.get("devices") or {}).values()):
        return None
    clock = tick_gap.clock_offset(trace)
    if clock is None:
        return None
    lo, hi = xplane.device_window(trace)
    idle = idle_intervals(trace)
    out, covered = [], []
    for rec in records:
        s, e = on_trace(rec, clock)
        if e <= lo or s >= hi:
            continue
        covered.append((max(s, lo), min(e, hi)))
        touching = [g for g in idle if g[1] > s and g[0] < e]
        gap = max(touching, key=lambda g: g[1] - g[0], default=None)
        out.append({
            "record": rec, "start_ns": s, "end_ns": e,
            "idle_ns": _overlap(idle, s, e),
            "gap_ns": gap[1] - gap[0] if gap else 0.0,
            "gap_starts_ns": gap[0] - s if gap else None,
            "gap_ends_ns": gap[1] - e if gap else None,
            "runtime": runtime_events_over(trace, s, e),
            "begun": events_begun(trace, s, e)})
    merged = xplane.merge(covered)
    inside = sum(_overlap(idle, s, e) for s, e in merged)
    uncovered = [
        {"start_ns": g[0] - lo, "gap_ns": g[1] - g[0],
         "under": engine_event_over(trace, *g),
         "begun": events_begun(trace, *g)}
        for g in sorted(idle, key=lambda g: g[0] - g[1])
        if g[1] - g[0] >= 1e9 * GAP_S
        and 2 * _overlap(merged, *g) < g[1] - g[0]]
    return {"clock": clock, "idle_ns": sum(e - s for s, e in idle),
            "idle_in_stalls_ns": inside, "uncovered": uncovered,
            "records": out}


def describe_joined(joined: dict) -> list:
    """Lines: the share of the capture's idle time inside a stall record,
    then each record beside the idle time inside it."""
    idle = joined["idle_ns"]
    lines = [
        f"{len(joined['records'])} record(s) inside the capture; "
        f"{1e-6 * joined['idle_in_stalls_ns']:.3f} of the first device "
        f"plane's {1e-6 * idle:.3f} idle ms lie inside one "
        f"({100.0 * joined['idle_in_stalls_ns'] / idle if idle else 0.0:.1f}%)"
        f"; {tick_gap.describe_clock(joined['clock'])}"]
    for gap in joined["uncovered"]:
        lines.append(
            f"NO RECORD covers an idle gap of {1e-6 * gap['gap_ns']:.3f} ms "
            f"at {1e-9 * gap['start_ns']:.3f} s of the capture, under "
            f"{gap['under']} (under a device wait: the thread slept, "
            f"and its own account cannot tell the device busy from the "
            f"process stopped); host events of any thread that began "
            f"inside it: {gap['begun'][0]} (the capture's mean rate would "
            f"give {gap['begun'][1]:.0f})")
    for j in joined["records"]:
        offset = ("no idle gap touches it" if j["gap_starts_ns"] is None else
                  f"longest gap touching it {1e-6 * j['gap_ns']:.3f} ms, "
                  f"its start {1e-6 * j['gap_starts_ns']:+.3f} and its end "
                  f"{1e-6 * j['gap_ends_ns']:+.3f} ms from the record's")
        runtime = ", ".join(f"{n} {1e-6 * ns:.3f}" for n, ns in j["runtime"])
        lines.append(
            f"{describe_record(j['record'])}; device idle inside it "
            f"{1e-6 * j['idle_ns']:.3f} ms, {offset}; the runtime's host "
            f"events over it, ms: {runtime or 'none'}; host events of any "
            f"thread that began inside it: {j['begun'][0]} (the capture's "
            f"mean rate would give {j['begun'][1]:.0f})")
    return lines
