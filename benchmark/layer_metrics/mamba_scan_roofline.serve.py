"""Share of the chip's roofline a prefill unit's chunked scans reach in the
dense state-space block: the products under the causal mask inside a chunk
of 256, one group of B and C, the chunk states in and out, and the operands
at the activations' width (benchmark/ssm_work.py `scan_work`, at this
configuration's sizes) of a unit of the window's mean places, over the
published peaks, over the self time a traced unit spends under the scope
`ssm_scan`; prints which bound. None where the window saw no unit of the
family or the trace holds nothing under the scope."""

from benchmark import granite_work, kernel_work, peaks, ssm_work

LAYER = "state-space layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = granite_work.mamba_trace(obs)
    places = granite_work.unit_positions(obs) if trace else None
    timed = (ssm_work.unit_seconds_under(obs, trace, ("ssm_scan",))
             if places else None)
    if not timed or not timed[0]:
        return None
    seconds, units = timed
    flops, hbm = ssm_work.scan_work(places,
                                    granite_work.sizes(obs["cell"].model))
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds, peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"mamba_scan_roofline.serve: {units} units traced, "
          f"{1e3 * seconds:.3f} ms a unit under ssm_scan; a unit of "
          f"{places:.0f} places (the window's mean): {flops / 1e9:.1f} "
          f"GFLOP, {hbm / 1e6:.1f} MB, bound by {bound}", flush=True)
    return share
