"""Share of the chip's roofline the flash-attention forward kernel reaches:
the FLOPs and bytes one call needs (benchmark/kernel_work.py: causal half,
FLOP-bound at these shapes) over the published peaks, over the median
duration of one `flash_fwd` event. None where the cell ran no such kernel."""

from benchmark import kernel_work, peaks, scopes
from benchmark.stats import percentile

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = scopes.for_observation(obs, "train")
    durations = scopes.kernel_durations(trace, "flash_fwd") if trace else []
    if not durations:
        return None
    model, mix = obs["cell"].model, obs["cell"].mix
    heads = model["num_attention_heads"]
    flops, hbm = kernel_work.flash_fwd_work(
        obs["seq_length"], model["hidden_size"] // heads, heads,
        model["num_key_value_heads"], mix["rows_per_microbatch"])
    share, bound = kernel_work.roofline_percent(
        flops, hbm, 1e-9 * percentile(durations, 50),
        peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"flash_fwd_roofline.train: {len(durations)} events, median "
          f"{1e-3 * percentile(durations, 50):.1f} us, {flops / 1e9:.2f} GFLOP "
          f"and {hbm / 1e6:.1f} MB a call, bound by {bound}", flush=True)
    return share
