"""Share of the chip's roofline the decode tick's dense read of the latent
cache reaches: the entries of the positions the decoding rows could see
(`latent_visible`, the program's own counter, a tick's mean over the
window's `serve_decode_step` spans), each read once at its published 1152 B
with the absorbed products over it (benchmark/mla_work.py), over the
published peaks, over the time a traced tick spends in
`paged_latent_decode_attn.<n>`; bytes-bound at 64 heads. None where the spans carry no counter or the trace
holds no such kernel."""

from benchmark import kernel_work, mla_work, peaks

LAYER = "latent attention layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = mla_work.dense_trace(obs)
    spans = mla_work.counted_spans(obs, "serve_decode_step") if trace else []
    ticks = sum(s["ticks"] for s in spans)
    if not ticks:
        return None
    seconds, calls = mla_work.kernel_calls(trace, mla_work.TICK_KERNEL)
    model = obs["cell"].model
    traced = calls / model["num_hidden_layers"]     # one call a layer a tick
    if not seconds or not traced:
        return None
    seen = sum(s[mla_work.COUNTER] for s in spans) / ticks
    flops, hbm = mla_work.dense_tick_work(seen, model)
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds / traced,
        peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"latent_decode_attn_roofline.serve: {traced:.0f} ticks traced, "
          f"{1e3 * seconds / traced:.3f} ms a tick in {calls} calls of "
          f"{mla_work.TICK_KERNEL}; a tick sees {seen:.0f} positions: "
          f"{hbm / 1e6:.1f} MB, {flops / 1e9:.2f} GFLOP, bound by {bound}",
          flush=True)
    return share
