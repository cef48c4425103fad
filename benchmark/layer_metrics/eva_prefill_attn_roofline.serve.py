"""Share of the chip's roofline a prefill unit's attention over both kinds
of key reaches (`ops/eva_prefill_attention.py` under the scope
`eva_attn_prefill`): a unit's visible (query, entry) pairs
(`eva_window_visible` + `eva_summary_visible`, the program's own counters),
its `chunk` queries and the entries it is given (the slot's summary pages,
the ring as it stood, its own keys: benchmark/eva_work.py), a unit's mean
over the window's `serve_prefill` spans, over the published peaks, over the
self time a traced unit spends under the scope (one pass a layer);
FLOP-bound. None where the spans carry no counter or the trace holds nothing
under the scope."""

from benchmark import eva_work, kernel_work, peaks

LAYER = "compressed-window attention layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = eva_work.eva_trace(obs)
    spans = eva_work.counted_spans(obs, "serve_prefill") if trace else []
    if not spans:
        return None
    seconds, runs = eva_work.scope_runs(trace, eva_work.PREFILL_SCOPE, False)
    model = obs["cell"].model
    traced = runs / model["num_hidden_layers"]      # one pass a layer a unit
    if not seconds or not traced:
        return None
    mean = lambda values: sum(values) / len(spans)
    seen = mean(s[eva_work.WINDOW] + s[eva_work.SUMMARY] for s in spans)
    queries = mean(s["chunk"] for s in spans)
    engine = obs["cell"].params["engine"]
    summaries = -(-engine["max_len"] // model["chunk_size"])
    keys = summaries + model["window_size"] + queries
    flops, hbm = eva_work.prefill_unit_work(seen, queries, keys, model)
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds / traced,
        peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"eva_prefill_attn_roofline.serve: {traced:.0f} units traced, "
          f"{1e3 * seconds / traced:.3f} ms a unit under "
          f"{eva_work.PREFILL_SCOPE}; a unit of {queries:.0f} queries given "
          f"{keys:.0f} entries sees {seen:.0f} pairs: {flops / 1e9:.1f} "
          f"GFLOP, {hbm / 1e6:.1f} MB, bound by {bound}", flush=True)
    return share
