"""Device idle time a decode tick: the time inside the traced window in which
no operation ran on the first device plane, over the number of
`serve_tick_wait` events (one a tick). A run prints the partition
(benchmark/tick_gap.py), whose parts sum to this value: every idle instant
goes to the innermost event of the engine's thread over it, and under
`serve_tick_block` to `launch`, `between_ops` or `wake` by where the device's
own busy instants lie inside the event. Value x ticks / window is the same
trace's idle share, printed beside `device_idle_share.serve`'s own reading.
The device plane's clock is first moved onto the host plane's by the least
shift at which the trace is causal (`tick_gap.device_clock_shift`: 1.4 ms on
the v5e), which the run prints with how far it is known. Also prints the offset
between the profiler's clock and the wall clock from the trace's anchors.
None where the program has no `serve_tick_block`."""

from benchmark import tick_gap, xplane

LAYER = "serving engine decode tick"
UNIT = "ms"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    found = tick_gap.of_observation(obs)
    if found is None:
        return None
    part, shift = found
    print(f"tick_gap_ms.serve: {tick_gap.describe(part)}; the whole trace's "
          f"idle share {xplane.idle_share_percent(obs['xplane']):.2f}%",
          flush=True)
    print(f"tick_gap_ms.serve: {tick_gap.describe_shift(shift)}", flush=True)
    print("tick_gap_ms.serve clock: "
          + tick_gap.describe_clock(tick_gap.clock_offset(obs["xplane"])),
          flush=True)
    return tick_gap.ms_a_tick(part)
