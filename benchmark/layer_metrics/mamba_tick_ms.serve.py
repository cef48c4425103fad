"""Mean seconds of the engine's thread a decode tick of the dense state-space
block, in ms: the sum of `dur` over the sum of `ticks` of the window's
`serve_decode_step` spans that carry the family's counters
(`ssm_positions`). With a tick and a prefill unit in flight a span's `dur`
is what the host's thread spent on the step, NOT the device's time of the
tick and not a decoding row's gap between tokens (the cell read 20.0 ms
where the device spends about 23 a tick and a row waits 43 to 48: PERF.md §6
PR 53). The same reading as `decode_tick_ms.serve`, whose list of cells is
held to other cells. None where the spans carry no such counter."""

from benchmark import granite_work

LAYER = "serving engine decode tick"
UNIT = "ms"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_span"


def read(obs: dict):
    spans = granite_work.family_spans(obs, "serve_decode_step")
    ticks = sum(s["ticks"] for s in spans)
    return 1e3 * sum(s["dur"] for s in spans) / ticks if ticks else None
