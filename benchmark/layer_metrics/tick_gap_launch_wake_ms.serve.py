"""The `launch` and `wake` parts of `tick_gap_ms.serve` together, a tick:
device idle time under `serve_tick_block` before the first instant the device
is busy inside the event (the enqueue has returned and the program has not
started) and after the last (the device is done and the host not yet woken;
all of the event where the device is never busy inside it). One number and
not two: the device's clock is placed on the host's only to a slack of 0.4 to
0.6 ms on the v5e, as large as either part, and while a tick's program lies
inside the event their sum does not move with the placement (the partition
prints both). None where the program has no
`serve_tick_block`, and where the trace has no `DoEnqueueProgram` event of the
runtime to place the device's clock by."""

from benchmark import tick_gap

LAYER = "serving engine decode tick"
UNIT = "ms"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    found = tick_gap.of_observation(obs)
    if found is None or found[1] is None:
        return None
    return tick_gap.ms_a_tick(found[0], "launch", "wake")
