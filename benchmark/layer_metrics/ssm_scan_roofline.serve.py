"""Share of the chip's roofline a prefill unit's chunked scans reach: the
products under the causal mask inside a chunk, the chunk states in and out,
and the operands at the activations' width (benchmark/ssm_work.py
`scan_work`) of a unit of the window's mean bucket, over the published
peaks, over the self time a traced unit spends under the scope `ssm_scan`;
prints which bound. None where the window saw no prefill or the trace holds
nothing under the scope."""

from benchmark import kernel_work, peaks, ssm_work

LAYER = "state-space layer"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = ssm_work.ssm_trace(obs)
    tokens = ssm_work.prefill_tokens(obs) if trace else None
    timed = (ssm_work.unit_seconds_under(obs, trace, ("ssm_scan",))
             if tokens else None)
    if not timed or not timed[0]:
        return None
    seconds, units = timed
    flops, hbm = ssm_work.scan_work(tokens, ssm_work.sizes(obs["cell"].model))
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds, peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"ssm_scan_roofline.serve: {units} units traced, "
          f"{1e3 * seconds:.3f} ms a unit under ssm_scan; a unit of "
          f"{tokens:.0f} positions (the window's mean): {flops / 1e9:.1f} "
          f"GFLOP, {hbm / 1e6:.1f} MB, bound by {bound}", flush=True)
    return share
