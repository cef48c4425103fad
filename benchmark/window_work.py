"""What the window / full softmax block's attention needs, from the model's
sizes and the program's own counters alone (beside `kernel_work.py`, whose
`roofline_percent` turns these into a share), and what this family's
per-layer readers share: the names its programs give their device work
(`utils/trace.py` WINDOW_SCOPES), the counters' sums over the spans that
began inside the traced window (a kernel's time in the trace is
`mla_work.kernel_calls`).

Needed work, not executed work. `window_entries_read` and
`full_entries_read` count, summed over rows (or a unit's queries) and
layers, the ring entries and the page entries a query reads (pads and rows
that do not decode count for nothing). The tick is charged one read of each
such entry as published (`kv_h x (head_dim + v_head_dim)` numbers: a full
layer's 2,560 B, a window layer's 5,120 B, not the wider rows they are
stored in) and the products over it; a prefill unit the products of each
visible (query, key) pair alone (the band's own pairs in a window layer,
whatever tiles the kernel visits), its queries and outputs once and its keys
and values once a layer, however often the kernel's blocks re-read them. So
no share can pass 100%.
"""

from __future__ import annotations

from benchmark import hybrid_scopes, scopes, tick_gap, xplane
from benchmark.mla_work import kernel_calls  # (seconds, calls) of a kernel's events

WINDOW_ATTN = ("window_decode_attn", "window_prefill_attn")
FULL_ATTN = ("full_decode_attn", "full_prefill_attn")
TICK_KERNEL = "paged_decode_attn"
WINDOW_KERNEL, FULL_KERNEL = "window_prefill_attn", "full_chunk_attn"
WINDOW_COUNTER, FULL_COUNTER = "window_entries_read", "full_entries_read"
WINDOW, FULL = 1, 0                 # `hybrid_layer_pattern`'s two kinds


# -- the counts -----------------------------------------------------------------

def sizes(model: dict) -> dict:
    """The numbers of the configuration the counts need."""
    pattern = model["hybrid_layer_pattern"]
    return {"window_layers": pattern.count(WINDOW),
            "full_layers": pattern.count(FULL),
            "heads": model["num_attention_heads"], "dk": model["head_dim"],
            "dv": model["v_head_dim"],
            "kv_full": model["num_key_value_heads"],
            "kv_window": model["swa_num_key_value_heads"],
            "window": model["sliding_window"]}


def entry_bytes(sz: dict, kind: int, dtype_bytes: int = 2) -> int:
    """Bytes a layer of `kind` keeps of one position, as published."""
    kv = sz["kv_window"] if kind == WINDOW else sz["kv_full"]
    return kv * (sz["dk"] + sz["dv"]) * dtype_bytes


def tick_read_work(window_entries: float, full_entries: float,
                   sz: dict) -> tuple:
    """(FLOPs, HBM bytes) of one tick's attention of both kinds: each entry
    read (the counters, summed over rows and layers) once at its published
    width; a score product over `dk` and a weighted sum over `dv` numbers
    for each of the `heads` query heads."""
    entries = window_entries + full_entries
    flops = entries * sz["heads"] * (sz["dk"] + sz["dv"]) * 2
    hbm = (window_entries * entry_bytes(sz, WINDOW)
           + full_entries * entry_bytes(sz, FULL))
    return flops, hbm


def prefill_unit_work(pairs: float, queries: float, keys: float, layers: int,
                      kind: int, sz: dict, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one prefill unit's attention in the `layers`
    layers of `kind`: `pairs` visible (query, key) pairs summed over the
    unit's queries and those layers, each a score product over `dk` and a
    weighted sum over `dv` numbers a head; `queries` tokens' queries and
    outputs and `keys` positions' keys and values, once a layer."""
    H, dk, dv = sz["heads"], sz["dk"], sz["dv"]
    flops = pairs * H * (dk + dv) * 2
    hbm = layers * (queries * H * (dk + dv) * dtype_bytes
                    + keys * entry_bytes(sz, kind, dtype_bytes))
    return flops, hbm


def host_entries(records: list, warm_buckets, sz: dict) -> tuple:
    """(window entries, full entries) the ticks of a run must have read, from
    the lengths alone: a request of n prompt tokens whose client received m
    tokens went through m - 1 ticks, the j-th with n + j positions to see,
    of which a window layer reads the last `window`; each warm-up request (a
    prompt the bucket long, two tokens) through one."""
    w = sz["window"]
    in_window = sum(min(b + 1, w) for b in warm_buckets)
    in_full = sum(b + 1 for b in warm_buckets)
    for r in records:
        n, ticks = len(r["request"]["prompt"]), len(r["tokens"]) - 1
        if ticks < 1:
            continue
        short = max(0, min(ticks, w - n - 1))   # ticks that see under w
        in_window += short * n + short * (short + 1) // 2 + (ticks - short) * w
        in_full += ticks * n + ticks * (ticks + 1) // 2
    return in_window * sz["window_layers"], in_full * sz["full_layers"]


# -- what the readers share -----------------------------------------------------

def window_trace(obs: dict):
    """The scoped trace of a traced serving run whose programs carry this
    family's names, else None (another kind of cell, an untraced run, a
    program without the names: the parent of the PR that added them)."""
    trace = scopes.for_observation(obs, "serve")
    if trace is None:
        return None
    named = any(hybrid_scopes.scope_of(op, WINDOW_ATTN + FULL_ATTN)
                for events in trace["devices"].values() for op in events)
    return trace if named else None


def counter_sums(obs: dict):
    """Sums over the observation's `serve_decode_step` spans of `ticks`,
    `tokens` and the two counters; None where the spans carry none."""
    spans = [s for s in obs.get("spans", ())
             if s["name"] == "serve_decode_step" and FULL_COUNTER in s]
    if not spans or not sum(s["tokens"] for s in spans):
        return None
    return {key: sum(s[key] for s in spans)
            for key in ("ticks", "tokens", WINDOW_COUNTER, FULL_COUNTER)}


def spans_of_trace(obs: dict, name: str) -> tuple:
    """(the spans of `name` that hold the counters and began inside the
    traced window, how they were chosen), as `tick_gap.spans_of_trace`
    chooses a tick's: the device window less the clock offset, on `ts`; every
    span of the observation where the trace holds no usable anchor or none
    began inside it."""
    spans = [s for s in obs.get("spans") or ()
             if s["name"] == name and FULL_COUNTER in s]
    trace = obs.get("xplane") or {}
    clock = tick_gap.clock_offset(trace)
    if (clock is None or clock["spread_us"] is None
            or clock["spread_us"] >= tick_gap.MAX_SPREAD_US):
        return spans, "every span of the window"
    lo, hi = ((w / 1e3 - clock["offset_us"]) * 1e-6
              for w in xplane.device_window(trace))
    chosen = [s for s in spans if lo <= s["ts"] <= hi]
    if not chosen:
        return spans, "every span of the window (none began in the trace)"
    return chosen, (f"{len(chosen)} of {len(spans)} spans, those that began "
                    f"in the traced {hi - lo:.3f} s")


def prefill_roofline(obs: dict, kind: int, reader: str):
    """Percent of the roofline a prefill unit's attention reaches in the
    layers of `kind`, for the reader of that name: the mean unit of the
    `serve_prefill` spans that began in the traced window (its pairs from the
    kind's counter, its `chunk` queries, the positions it is given: the
    window's other entries before a window layer's span, the row so far in a
    full layer), over the published peaks, over the time a traced unit
    spends in the kind's kernel (one call a layer). None where the spans
    carry no counter or the trace holds no such kernel."""
    from benchmark import kernel_work, peaks

    trace = window_trace(obs)
    if trace is None:
        return None
    window = kind == WINDOW
    kernel, counter = ((WINDOW_KERNEL, WINDOW_COUNTER) if window
                       else (FULL_KERNEL, FULL_COUNTER))
    sz = sizes(obs["cell"].model)
    layers = sz["window_layers" if window else "full_layers"]
    spans, how = spans_of_trace(obs, "serve_prefill")
    seconds, calls = kernel_calls(trace, kernel)
    traced = calls / layers
    if not spans or not seconds or not traced:
        return None
    mean = lambda values: sum(values) / len(spans)
    pairs = mean(s[counter] for s in spans)
    queries = mean(s["chunk"] for s in spans)
    keys = (queries + sz["window"] - 1 if window
            else mean(s["offset"] + s["chunk"] for s in spans))
    flops, hbm = prefill_unit_work(pairs, queries, keys, layers, kind, sz)
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds / traced,
        peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"{reader}: {how}; {traced:.0f} units traced, "
          f"{1e3 * seconds / traced:.3f} ms a unit in {calls} calls of "
          f"{kernel}; a unit of {queries:.0f} queries given {keys:.0f} "
          f"positions sees {pairs:.0f} pairs: {flops / 1e9:.1f} GFLOP, "
          f"{hbm / 1e6:.1f} MB, bound by {bound}", flush=True)
    return share
