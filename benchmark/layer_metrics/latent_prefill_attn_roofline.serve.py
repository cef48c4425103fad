"""Share of the chip's roofline the prefill's dense attention over a latent
cache reaches (`ops/latent_prefill_attention.py`, instruction
`latent_prefill_attn.<n>`, the projected form): a prefill unit's visible
(query, position) pairs (`latent_visible`, the program's own counter), its
`chunk` queries and the `offset + chunk` positions whose keys and values it
expands (benchmark/mla_work.py), a unit's mean over the window's
`serve_prefill` spans, over the published peaks, over the time a traced unit
spends in the kernel (one call a layer); FLOP-bound. None where the spans
carry no counter or the trace holds no such kernel."""

from benchmark import kernel_work, mla_work, peaks

LAYER = "latent attention layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = mla_work.dense_trace(obs)
    spans = mla_work.counted_spans(obs, "serve_prefill") if trace else []
    if not spans:
        return None
    seconds, calls = mla_work.kernel_calls(trace, mla_work.PREFILL_KERNEL)
    model = obs["cell"].model
    traced = calls / model["num_hidden_layers"]     # one call a layer a unit
    if not seconds or not traced:
        return None
    mean = lambda values: sum(values) / len(spans)
    seen = mean(s[mla_work.COUNTER] for s in spans)
    queries = mean(s["chunk"] for s in spans)
    keys = mean(s["offset"] + s["chunk"] for s in spans)
    flops, hbm = mla_work.prefill_unit_work(seen, queries, keys, model)
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds / traced,
        peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"latent_prefill_attn_roofline.serve: {traced:.0f} units traced, "
          f"{1e3 * seconds / traced:.3f} ms a unit in {calls} calls of "
          f"{mla_work.PREFILL_KERNEL}; a unit of {queries:.0f} queries "
          f"against {keys:.0f} positions sees {seen:.0f} pairs: "
          f"{flops / 1e9:.1f} GFLOP, {hbm / 1e6:.1f} MB, bound by {bound}",
          flush=True)
    return share
