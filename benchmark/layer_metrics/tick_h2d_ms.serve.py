"""Host-clock time a decode tick in the nine `jnp.asarray` copies of the
staged arrays and the page table (`serve_tick_h2d`): sum `h2d_s` over sum
`ticks` of the `serve_decode_step` spans of a traced run. Where the trace holds
wall-clock anchors, the spans that began inside the traced window, so that it
describes the ticks `tick_gap_ms.serve` partitions, under the same tracing
cost; else every span of the window. A run prints which. None for an untraced
run and where the spans have no `h2d_s`."""

from benchmark import tick_gap

LAYER = "serving engine decode tick"
UNIT = "ms"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_span"


def read(obs: dict):
    return tick_gap.traced_ms_a_tick(obs, "h2d_s", "tick_h2d_ms.serve")
