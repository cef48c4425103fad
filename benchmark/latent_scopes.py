"""What the latent family's per-layer readers share: the names its programs
give their device work (`utils/trace.py` LATENT_SCOPES; a third vocabulary
beside `benchmark/scopes.py`'s and `hybrid_scopes.py`'s until a benchmark PR
joins them), shares of busy time told apart by program (the decode tick
against the prefills), and the sums of the indexer's counters over the
window's spans. The trace itself, the tick's program and the grouped
product's kernels are read as `hybrid_scopes.py` reads them.
"""

from __future__ import annotations

from benchmark import hybrid_scopes, scopes

ATTENTION = ("mla_proj", "sparse_attn", "window_attn", "attn_gate", "attn_out")
INDEXER = ("index_proj", "index_score", "index_topk")
CACHE = ("latent_write", "latent_gather", "ring_gather", "ring_write")
SPARSE_READ = ("index_score", "index_topk", "latent_gather", "sparse_attn")
COUNTERS = ("index_visible", "index_selected")


def latent_trace(obs: dict):
    """The scoped trace of a traced serving run whose programs carry this
    family's names, else None."""
    trace = scopes.for_observation(obs, "serve")
    if trace is None:
        return None
    everything = ATTENTION + INDEXER + CACHE
    named = any(hybrid_scopes.scope_of(op, everything)
                for events in trace["devices"].values() for op in events)
    return trace if named else None


def split_shares(trace: dict, names) -> dict:
    """{name: (percent of busy time inside the decode-tick program, outside
    it)}, innermost name wins, mean over the device planes."""
    window = scopes.window_of(trace)
    planes = list(trace["devices"].values())
    out = {name: [0.0, 0.0] for name in names}
    for events in planes:
        busy = scopes.busy_ns(events, window)
        in_tick = {id(events[i]) for i in hybrid_scopes.tick_ops(events)}
        by = scopes.self_time_by(
            events, lambda op: (hybrid_scopes.scope_of(op, names),
                                id(op) in in_tick), window)
        for (name, tick), ns in by.items():
            if name is not None:
                out[name][0 if tick else 1] += 100.0 * ns / busy / len(planes)
    return {name: tuple(v) for name, v in out.items()}


def print_and_sum(metric: str, parts: dict) -> float:
    print(f"{metric} parts, % of busy time (tick + prefill): " + ", ".join(
        f"{name} {tick:.2f} + {fill:.2f}"
        for name, (tick, fill) in parts.items()), flush=True)
    return sum(tick + fill for tick, fill in parts.values())


def index_sums(obs: dict, names=("serve_decode_step", "serve_prefill")):
    """Sums of the indexer's counters over the observation's spans of the
    given names; None where none carries them."""
    spans = [s for s in obs.get("spans", ())
             if s["name"] in names and "index_visible" in s]
    if not spans:
        return None
    return {key: sum(s[key] for s in spans) for key in COUNTERS}
