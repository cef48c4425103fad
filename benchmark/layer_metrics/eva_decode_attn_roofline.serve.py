"""Share of the chip's roofline the decode tick's read of its two kinds of
page reaches: the exact entries of the rows' windows and the pooled entries
of their earlier windows (`eva_window_visible` + `eva_summary_visible`, the
program's own counters, a tick's mean over the `serve_decode_step` spans
that began in the traced seconds: `tick_gap.spans_of_trace`), each read once at 16,384 B with a score product and a weighted sum
over it a head (benchmark/eva_work.py), over the published peaks, over the
self time a traced tick spends under the SCOPE `eva_attn` in
`jit(paged_decode_step)`: by scope and counters, so it reads the same work
whatever kernel implements it; bytes-bound. None where the spans carry no
counter or the trace holds nothing under the scope."""

from benchmark import eva_work, kernel_work, peaks, tick_gap

LAYER = "compressed-window attention layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = eva_work.eva_trace(obs)
    spans, how = (tick_gap.spans_of_trace(obs, eva_work.WINDOW) if trace
                  else ([], ""))
    ticks = sum(s["ticks"] for s in spans)
    if not ticks:
        return None
    seconds, runs = eva_work.scope_runs(trace, eva_work.TICK_SCOPE, True)
    model = obs["cell"].model
    traced = runs / model["num_hidden_layers"]      # one pass a layer a tick
    if not seconds or not traced:
        return None
    seen = sum(s[eva_work.WINDOW] + s[eva_work.SUMMARY] for s in spans) / ticks
    flops, hbm = eva_work.tick_read_work(seen, model)
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds / traced,
        peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"eva_decode_attn_roofline.serve: {how}; {traced:.0f} ticks traced, "
          f"{1e3 * seconds / traced:.3f} ms a tick under {eva_work.TICK_SCOPE}"
          f"; a tick reads {seen:.0f} entries: {hbm / 1e6:.1f} MB, "
          f"{flops / 1e9:.2f} GFLOP, bound by {bound}", flush=True)
    return share
