"""Host-clock time a decode tick in the jitted `paged_decode_step` call, up to
its return (`serve_tick_enqueue`: flattening the weights and the store, the
runtime's enqueue): sum `enqueue_s` over sum `ticks` of the
`serve_decode_step` spans of a traced run, chosen as `tick_h2d_ms.serve`
chooses them; a run prints which. None for an untraced run and where the
spans have no `enqueue_s`."""

from benchmark import tick_gap

LAYER = "serving engine decode tick"
UNIT = "ms"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_span"


def read(obs: dict):
    return tick_gap.traced_ms_a_tick(obs, "enqueue_s", "tick_enqueue_ms.serve")
