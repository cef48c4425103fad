"""Share of the chip's roofline the dense state-space block's tick reaches
in its recurrence: the float32 state and the convolution's inputs of the rows
that decoded (the host's `tokens` over `ticks`), read once and written once
in each of the Mamba-2 layers (benchmark/ssm_work.py `step_work`, at this
configuration's sizes: `granite_work.sizes`), over the published peaks, over
the time a traced tick spends under the SCOPES `ssm_step`, `state_gather` and
`state_write` in the decode-tick program, whatever implements them;
bytes-bound. None where the spans carry none of the family's counters or no
tick was traced."""

from benchmark import granite_work, hybrid_scopes, kernel_work, peaks, ssm_work

LAYER = "state-space layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = granite_work.mamba_trace(obs)
    spans = granite_work.family_spans(obs, "serve_decode_step")
    ticks = sum(s["ticks"] for s in spans)
    timed = (hybrid_scopes.tick_seconds_under(obs, trace, ssm_work.STEP)
             if trace and ticks else None)
    if not timed or not timed[0]:
        return None
    seconds, traced = timed
    sz = granite_work.sizes(obs["cell"].model)
    rows = sum(s["tokens"] for s in spans) / ticks
    flops, hbm = ssm_work.step_work(rows, sz)
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds, peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"mamba_step_roofline.serve: {traced} ticks traced, "
          f"{1e3 * seconds:.3f} ms a tick under ssm_step + state_*; "
          f"{rows:.1f} rows x {sz['ssm_layers']} layers: {hbm / 1e6:.1f} MB, "
          f"{flops / 1e9:.2f} GFLOP, bound by {bound}", flush=True)
    return share
