"""Share of the chip's roofline a prefill unit's banded attention reaches
(`ops/gqa_prefill_attention.py` `window_prefill_attention`, instruction
`window_prefill_attn.<n>`, one call a window layer): the band's OWN (query,
key) pairs (`window_entries_read`, the program's own counter: at most 128 a
query, whatever tiles the kernel visits), the unit's `chunk` queries and the
positions whose keys and values it is given (benchmark/window_work.py
`prefill_roofline`), a unit's mean over the `serve_prefill` spans that began
in the traced window, over the published peaks, over the time a traced unit
spends in the kernel; FLOP-bound. None where the spans carry no counter or
the trace holds no such kernel."""

from benchmark import window_work

LAYER = "window and full attention layer"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    return window_work.prefill_roofline(
        obs, window_work.WINDOW, "window_prefill_attn_roofline.serve")
