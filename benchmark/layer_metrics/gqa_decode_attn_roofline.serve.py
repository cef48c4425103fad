"""Share of the chip's roofline the decode tick's attention of both kinds
reaches (`ops/paged_attention.py`, instruction `paged_decode_attn.<n>`, one
call a layer: over the live pages in a full layer, over the slot's ring in
a window layer): the entries the decoding rows read (`full_entries_read`,
`window_entries_read`, the program's own counters, a tick's mean over the
`serve_decode_step` spans that began in the traced window), each read once
at its published width with the products over it (benchmark/window_work.py),
over the published peaks, over the time a traced tick spends in the kernel;
bytes-bound. None where the spans carry no counter or the trace holds no
such kernel."""

from benchmark import kernel_work, peaks, window_work

LAYER = "window and full attention layer"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = window_work.window_trace(obs)
    if trace is None:
        return None
    spans, how = window_work.spans_of_trace(obs, "serve_decode_step")
    ticks = sum(s["ticks"] for s in spans)
    seconds, calls = window_work.kernel_calls(trace, window_work.TICK_KERNEL)
    sz = window_work.sizes(obs["cell"].model)
    traced = calls / (sz["window_layers"] + sz["full_layers"])
    if not ticks or not seconds or not traced:
        return None
    in_window, in_full = (sum(s[c] for s in spans) / ticks for c in (
        window_work.WINDOW_COUNTER, window_work.FULL_COUNTER))
    flops, hbm = window_work.tick_read_work(in_window, in_full, sz)
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds / traced,
        peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"gqa_decode_attn_roofline.serve: {how}; {traced:.0f} ticks traced, "
          f"{1e3 * seconds / traced:.3f} ms a tick in {calls} calls of "
          f"{window_work.TICK_KERNEL}; a tick reads {in_full:.0f} page and "
          f"{in_window:.0f} ring entries: {hbm / 1e6:.1f} MB, "
          f"{flops / 1e9:.2f} GFLOP, bound by {bound}", flush=True)
    return share
