"""What the compressed-window attention (models/eva/: an exact window beside
pooled chunk summaries, one softmax) needs, from the model's sizes and the
program's own counters alone (beside `kernel_work.py`, whose
`roofline_percent` turns these into a share), and what the per-layer readers
of such a cell share: the family's scopes, the counters' sums over the
window's spans, the time and the executions of a scope in the trace.

Needed work, not executed work. `eva_window_visible` and
`eva_summary_visible` count, summed over rows and layers, the exact entries
and the pooled entries a query reads (pads and rows that do not decode count
for nothing). An entry of either kind is one key and one value a head: `2 x
kv_heads x head_dim` numbers, 16,384 B at the published sizes. The tick is
charged one read of each visible entry and a score product and a weighted sum
over it a head; a prefill unit the same products of each visible (query,
entry) pair, its queries and outputs once and the keys and values it is
given once, however often the kernel's blocks re-read them, and nothing for
the tiles it visits and masks. So neither share can pass 100%.

Read by SCOPE, not by kernel: the time is the self time of whatever runs
under `eva_attn` (the tick) or `eva_attn_prefill` (a unit), so the share
reads the same work whatever kernel implements it.
"""

from __future__ import annotations

from benchmark import hybrid_scopes, scopes

POOL, SUMMARY_WRITE = "eva_pool", "eva_summary_write"
TICK_SCOPE, PREFILL_SCOPE = "eva_attn", "eva_attn_prefill"
SCOPES = (POOL, SUMMARY_WRITE, TICK_SCOPE, PREFILL_SCOPE)
WINDOW, SUMMARY, WRITTEN = ("eva_window_visible", "eva_summary_visible",
                            "eva_summaries_written")


def entry_bytes(model: dict, dtype_bytes: int = 2) -> int:
    """One entry of either kind: a key and a value a head."""
    head_dim = model["hidden_size"] // model["num_attention_heads"]
    return 2 * model["num_key_value_heads"] * head_dim * dtype_bytes


def tick_read_work(visible: float, model: dict, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one tick's read, `visible` = exact + pooled
    entries summed over the tick's rows and layers: each entry read once, a
    score product and a weighted sum over `head_dim` numbers a head."""
    head_dim = model["hidden_size"] // model["num_attention_heads"]
    flops = visible * 2 * 2 * head_dim * model["num_attention_heads"]
    return flops, visible * entry_bytes(model, dtype_bytes)


def prefill_unit_work(visible: float, queries: float, keys: float,
                      model: dict, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one prefill unit's attention, all layers:
    `visible` (query, entry) pairs of both kinds summed over the unit's
    queries and layers; `queries` tokens' queries and outputs and `keys`
    entries' keys and values (the slot's summaries, the ring as it stood,
    the unit's own), once a layer."""
    heads = model["num_attention_heads"]
    head_dim = model["hidden_size"] // heads
    flops = visible * 2 * 2 * head_dim * heads
    hbm = model["num_hidden_layers"] * (
        queries * 2 * heads * head_dim * dtype_bytes
        + keys * entry_bytes(model, dtype_bytes))
    return flops, hbm


def host_visible(records: list, warm_buckets, model: dict) -> tuple:
    """What the ticks' `eva_window_visible` and `eva_summary_visible` must
    sum to over a run, from the lengths alone: a request of n prompt tokens
    whose client received m tokens went through m - 1 ticks, the j-th taking
    in the token at position p = n + j - 1, which reads `p mod W + 1` exact
    entries and `(p // W) x (W / C)` pooled ones a layer; each warm-up
    request (a prompt the bucket long, two tokens) through one."""
    W = model["window_size"]
    per_window = W // model["chunk_size"]
    positions = [b for b in warm_buckets]
    for r in records:
        n = len(r["request"]["prompt"])
        positions.extend(range(n, n + len(r["tokens"]) - 1))
    layers = model["num_hidden_layers"]
    return (sum(p % W + 1 for p in positions) * layers,
            sum(p // W * per_window for p in positions) * layers)


# -- what the readers share -----------------------------------------------------

def eva_trace(obs: dict):
    """The scoped trace of a traced serving run whose programs carry this
    family's names, else None (another kind of cell, an untraced run, a
    program without the names: the parent of the PR that added them)."""
    trace = scopes.for_observation(obs, "serve")
    if trace is None:
        return None
    named = any(hybrid_scopes.scope_of(op, SCOPES)
                for events in trace["devices"].values() for op in events)
    return trace if named else None


def counted_spans(obs: dict, name: str) -> list:
    """The observation's spans of `name` that carry the counters."""
    return [s for s in obs.get("spans", ())
            if s["name"] == name and WINDOW in s]


def scope_runs(trace: dict, scope: str, in_tick: bool) -> tuple:
    """(self seconds, executions) of the work under `scope`, innermost name
    winning, inside the decode-tick program or outside it, on the first
    device plane. An execution is one pass of a layer: every instruction of
    the layer loop's body runs once a layer, so the events of the scope's
    costliest instruction count them."""
    events = trace["devices"][sorted(trace["devices"])[0]]
    tick = hybrid_scopes.tick_ops(events)
    mine = [op for i, op in enumerate(events)
            if (i in tick) == in_tick
            and hybrid_scopes.scope_of(op, SCOPES) == scope]
    if not mine:
        return 0.0, 0
    keep = {id(op) for op in mine}
    window = scopes.window_of(trace)
    by = scopes.self_time_by(
        events, lambda op: op.instruction if id(op) in keep else None, window)
    by.pop(None, None)
    if not by:
        return 0.0, 0
    costliest = max(by, key=by.get)
    return (1e-9 * sum(by.values()),
            sum(1 for op in mine if op.instruction == costliest))
