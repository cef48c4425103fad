"""Mean host-clock time of one decode tick of the window / full softmax
family: the sum of `dur` over the sum of `ticks` of the window's
`serve_decode_step` spans that carry the family's counters
(`full_entries_read`). A tick is enqueued behind the step's prefill unit, so
this holds the unit's device time too: tokens a second are the rows that
decode over this. None where the spans carry no such counter."""

from benchmark import window_work

LAYER = "serving engine decode tick"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    spans = [s for s in obs["spans"] if s["name"] == "serve_decode_step"
             and window_work.FULL_COUNTER in s]
    ticks = sum(s["ticks"] for s in spans)
    return 1e3 * sum(s["dur"] for s in spans) / ticks if ticks else None
