"""Device time by the program's own names: event -> `op_name` path -> class
and leaf scope.

The program names its device work with `jax.named_scope` and `name=` on its
kernels (`llama_pipeline_parallel_tpu/utils/trace.py`, the `SCOPE_*`
vocabulary). The name survives compilation as the operation's `op_name` path,

    jit(train_step)/shard_map/while/body/closed_call/pp_bwd/transpose(jvp())/
        while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general

and the profiler keeps that path per device event: not in the event's name
(the whole HLO instruction) and not among the stats `jax.profiler.ProfileData`
shows, but as the `tf_op` stat of the event's metadata (seen on a v5e, PR 24).
The metadata's name is the whole instruction, so the type of its result
(`bf16[641,64,32,128]`) is there too, for a reader that has to tell one
operand from another. So this module reads the `.xplane.pb` wire format itself, the few fields it
needs: XSpace.planes=1; XPlane.name=2, lines=3, event_metadata=4 and
stat_metadata=5 (maps: key=1, value=2); XEventMetadata.id=1, name=2, stats=5;
XStatMetadata.id=1, name=2; XStat.metadata_id=1, str_value=5, ref_value=7;
XLine.name=2, timestamp_ns=3, events=4; XEvent.metadata_id=1, offset_ps=2,
duration_ps=3.

A program that names nothing (the parent of PR 24) gives paths with no word of
the vocabulary in them; every reader built on this module then returns None.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from typing import NamedTuple

from benchmark import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNS_DIR = os.path.join(ROOT, "benchmark", ".runs")

# The program's vocabulary, as this reduction reads it (a test holds it equal
# to utils/trace.SCOPES; the benchmark also runs against programs without it).
VOCABULARY = (
    "embed", "attn_qkv", "attn_core", "attn_out", "mlp", "final_norm",
    "lm_head_loss", "cast_weights", "tp_collective", "sp_collective",
    "optimizer", "grad_clip", "grad_reduce", "numerics",
    "pp_fwd", "pp_recompute", "pp_bwd", "pp_w", "pp_handoff",
    "kv_gather", "kv_write", "decode_attn", "decode_mlp", "lm_head", "sample")
_VOCABULARY = frozenset(VOCABULARY)

CLASSES = ("forward", "recompute", "backward", "weight-gradient", "optimizer",
           "hand-off", "other")
REMAT_MARKER = "rematted_computation"    # jax.checkpoint's recomputed half
# scopes that name model work whatever pass it runs in: all but the step's
# bookkeeping and the schedule's slots
_MODEL_WORK = _VOCABULARY - {
    "optimizer", "grad_clip", "grad_reduce", "numerics",
    "pp_fwd", "pp_recompute", "pp_bwd", "pp_w", "pp_handoff"}


class Op(NamedTuple):
    """One event of a device plane's operations line."""
    instruction: str     # `fusion.12`: the name as `xplane.short_name` cuts it
    path: str            # the `op_name` path, "" where the compiler gave none
    start_ns: float
    end_ns: float
    result: str          # `bf16[8,128]`: the type of the instruction's result


_RESULT = re.compile(r"= \(?(\w+\[[\d,]*\])")


# -- the path ----------------------------------------------------------------

def components(path: str) -> list:
    return path.split("/") if path else []


def leaf_scope(path: str):
    """The innermost word of the vocabulary in the path, or None."""
    for part in reversed(components(path)):
        if part in _VOCABULARY:
            return part
    return None


def under(path: str, names) -> bool:
    """Whether the operation lies under any of the named scopes."""
    return any(part in names for part in components(path))


def classify(path: str) -> str:
    """forward / recompute / backward / weight-gradient / optimizer / hand-off
    / other, from the path alone. Recompute is anything under `pp_recompute`
    (a schedule unit running its stage forward again) or under JAX's remat
    marker, wherever it nests: it wins over the pass it is recomputed for."""
    parts = components(path)
    has = set(parts).__contains__
    if has("pp_handoff"):
        return "hand-off"
    if has("optimizer") or has("grad_clip"):
        return "optimizer"
    if has("pp_recompute") or has(REMAT_MARKER):
        return "recompute"
    if has("pp_w"):
        return "weight-gradient"
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    if has("pp_fwd"):       # also the one-stage B unit's own forward,
        return "forward"    # which nests in `pp_bwd`
    if has("pp_bwd"):
        return "backward"
    if any(p in _MODEL_WORK for p in parts):
        return "forward"
    return "other"


# -- the file ----------------------------------------------------------------

def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane message")


def _map_entry(buf):
    key = value = None
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _event_paths(plane_fields) -> dict:
    """{event metadata id: (instruction name, op_name path, result type)} of
    one plane."""
    stat_names, metas = {}, []
    for num, v in plane_fields:
        if num == 5:
            key, msg = _map_entry(v)
            stat_names[key] = next(
                (bytes(x).decode() for n, x in _fields(msg) if n == 2), "")
        elif num == 4:
            metas.append(_map_entry(v))
    out = {}
    for key, msg in metas:
        name, path = "", ""
        for num, v in _fields(msg):
            if num == 2:
                name = bytes(v).decode(errors="replace")
            elif num == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                if 5 in stat:
                    path = bytes(stat[5]).decode(errors="replace")
                elif 7 in stat:
                    path = stat_names.get(stat[7], "")
        result = _RESULT.search(name)
        # `tf_op` is "<op_name>:<op type>", the type empty for XLA programs
        out[key] = (xplane.short_name(name), path.rsplit(":", 1)[0],
                    result.group(1) if result else "")
    return out


def read(path: str) -> dict:
    """{"devices": {plane: [Op]}, "named": whether any path holds a word of
    the vocabulary} for the operations line of every device plane, on the
    clock `xplane.read` uses."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for n, v in fields if n == 2), "")
        if not name.startswith(xplane.DEVICE_PREFIX):
            continue
        paths = _event_paths(fields)
        events = []
        for n, line in fields:
            if n != 3:
                continue
            line_fields = list(_fields(line))
            if next((bytes(v).decode() for k, v in line_fields if k == 2),
                    "") != xplane.OPS_LINE:
                continue
            t_line = next((v for k, v in line_fields if k == 3), 0)
            for k, event in line_fields:
                if k != 4:
                    continue
                meta = offset_ps = duration_ps = 0
                for e_num, e_v in _fields(event):
                    if e_num == 1:
                        meta = e_v
                    elif e_num == 2:
                        offset_ps = e_v
                    elif e_num == 3:
                        duration_ps = e_v
                start = t_line + offset_ps / 1000.0
                instruction, op_path, result = paths.get(meta, ("", "", ""))
                events.append(Op(instruction, op_path, start,
                                 start + duration_ps / 1000.0, result))
        devices[name] = events
    named = any(leaf_scope(path) for path in {
        op.path for events in devices.values() for op in events})
    return {"devices": devices, "named": named}


def find_run_trace(cell_name: str):
    """The newest `.xplane.pb` of a traced run of the cell: the run's own
    directory exists while its metrics are read (harness.run_cell)."""
    found = glob.glob(os.path.join(glob.escape(RUNS_DIR),
                                   glob.escape(cell_name) + ".*.1", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def _read_cached(path: str, mtime: float) -> dict:
    return read(path)


def for_observation(obs: dict, kind: str):
    """The scoped device trace of the run whose observations these are, or
    None where the cell is of another kind, the run was not traced, or
    nothing in its trace carries a scope."""
    if obs.get("kind") != kind or not (obs.get("xplane") or {}).get("devices"):
        return None
    path = find_run_trace(obs["cell"].name)
    if path is None:
        return None
    trace = _read_cached(path, os.path.getmtime(path))
    return trace if trace["named"] else None


# -- the reduction -----------------------------------------------------------

def window_of(trace: dict) -> tuple:
    """First start to last end of any device operation (`xplane.device_window`
    on these events)."""
    spans = [(op.start_ns, op.end_ns)
             for events in trace["devices"].values() for op in events]
    if not spans:
        raise ValueError("no operation ran on a device in this trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def self_time_by(events, key, window: tuple) -> dict:
    """{key(op): self ns} of one plane's events inside the window
    (`xplane.self_times`: nested operations are not counted twice)."""
    kept = [op for op in events
            if op.end_ns > window[0] and op.start_ns < window[1]]
    ops = [(i, max(op.start_ns, window[0]), min(op.end_ns, window[1]))
           for i, op in enumerate(kept)]
    out: dict = {}
    for i, self_ns in xplane.self_times(ops):
        k = key(kept[i])
        out[k] = out.get(k, 0.0) + self_ns
    return out


def busy_ns(events, window: tuple) -> float:
    merged = xplane.merge(
        (max(op.start_ns, window[0]), min(op.end_ns, window[1]))
        for op in events if op.end_ns > window[0] and op.start_ns < window[1])
    return float(sum(e - s for s, e in merged))


def time_under(events, names, window: tuple, but_not=()) -> float:
    """Self ns of one plane's operations under any scope of `names` and
    under none of `but_not`."""
    names, but_not = frozenset(names), frozenset(but_not)
    hit = self_time_by(
        events,
        lambda op: under(op.path, names) and not under(op.path, but_not),
        window)
    return hit.get(True, 0.0)


def share_under(trace: dict, names, of: str = "busy") -> float:
    """Percent of busy time (or of the window) under the named scopes, mean
    over the device planes."""
    window = window_of(trace)
    shares = []
    for events in trace["devices"].values():
        base = (busy_ns(events, window) if of == "busy"
                else float(window[1] - window[0]))
        shares.append(100.0 * time_under(events, names, window) / base)
    return sum(shares) / len(shares)


def shares_by(trace: dict, key) -> dict:
    """{key: percent of busy time}, mean over the device planes."""
    window = window_of(trace)
    planes = list(trace["devices"].values())
    total: dict = {}
    for events in planes:
        busy = busy_ns(events, window)
        for k, ns in self_time_by(events, key, window).items():
            total[k] = total.get(k, 0.0) + 100.0 * ns / busy / len(planes)
    return total


def class_shares(trace: dict) -> dict:
    return shares_by(trace, lambda op: classify(op.path))


def leaf_shares(trace: dict) -> dict:
    """{leaf scope or "(no scope)": percent of busy time}."""
    return shares_by(trace, lambda op: leaf_scope(op.path) or "(no scope)")


def kernel_durations(trace: dict, kernel: str) -> list:
    """Durations in ns of the events of one named kernel (`<kernel>` or
    `<kernel>.<n>`, as the compiler numbers a name used twice)."""
    return [op.end_ns - op.start_ns
            for events in trace["devices"].values() for op in events
            if op.instruction == kernel
            or op.instruction.startswith(kernel + ".")]


def bubble_by_stage(trace: dict, schedule: list):
    """Per stage, the percent of its busy time spent in masked slots: masked
    F slots at the mean `pp_fwd` slot time, masked B at the mean
    (`pp_recompute` + `pp_bwd`), masked W at the mean `pp_w`. A masked slot
    runs the operations of a live one (so a slot's mean time is the scope's
    time over the slots executed, and the steps traced cancel), all but the
    head of a B slot: `pipeline.chunk_fwd` gates it by the slot's validity
    under `lax.cond`, so the time under `lm_head_loss` belongs to live B
    slots alone and is left out of the masked ones' price. (A W slot's head
    is gated by the stage only, and an F slot has none.) None where a
    stage's plane is not in the trace."""
    window = window_of(trace)
    out = []
    for stage in schedule:
        plane = f"{xplane.DEVICE_PREFIX}{stage['devices'][0]}"
        events = trace["devices"].get(plane)
        if events is None:
            return None
        slot_time = {
            "f": time_under(events, ("pp_fwd",), window),
            "b": time_under(events, ("pp_recompute", "pp_bwd"), window,
                            but_not=("pp_w", "lm_head_loss")),
            "w": time_under(events, ("pp_w",), window)}
        masked = sum(slot_time[k] * stage[k + "_masked"] / stage[k]
                     for k in ("f", "b", "w") if stage[k])
        out.append(100.0 * masked / busy_ns(events, window))
    return out
