"""Share of the chip's roofline the decode tick's recurrence reaches: the
float32 state and the convolution's inputs of the rows that decoded (the
host's `tokens` over `ticks`), read once and written once in each Mamba-2
layer (benchmark/ssm_work.py `step_work`), over the published peaks, over
the time a traced tick spends under the SCOPES `ssm_step`, `state_gather`
and `state_write` in the decode-tick program, whatever implements them;
bytes-bound. None where the spans carry no counters or no tick was
traced."""

from benchmark import hybrid_scopes, kernel_work, peaks, ssm_work

LAYER = "state-space layer"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = ssm_work.ssm_trace(obs)
    sums = hybrid_scopes.counter_sums(obs) if trace else None
    timed = (hybrid_scopes.tick_seconds_under(obs, trace, ssm_work.STEP)
             if sums else None)
    if not timed or not timed[0]:
        return None
    seconds, ticks = timed
    sz = ssm_work.sizes(obs["cell"].model)
    rows = sums["tokens"] / sums["ticks"]
    flops, hbm = ssm_work.step_work(rows, sz)
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds, peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"ssm_step_roofline.serve: {ticks} ticks traced, "
          f"{1e3 * seconds:.3f} ms a tick under ssm_step + state_*; "
          f"{rows:.1f} rows x {sz['ssm_layers']} layers: {hbm / 1e6:.1f} MB, "
          f"{flops / 1e9:.2f} GFLOP, bound by {bound}", flush=True)
    return share
