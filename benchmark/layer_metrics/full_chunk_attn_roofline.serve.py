"""Share of the chip's roofline a prefill unit's causal attention over the
slot's row so far reaches (`ops/gqa_prefill_attention.py`
`full_prefill_attention`, instruction `full_chunk_attn.<n>`, one call a full
layer): the visible (query, key) pairs (`full_entries_read`, the program's
own counter), the unit's `chunk` queries and the `offset + chunk` positions
whose keys and values it gathers (benchmark/window_work.py
`prefill_roofline`), a unit's mean over the `serve_prefill` spans that began
in the traced window, over the published peaks, over the time a traced unit
spends in the kernel; FLOP-bound at long rows. None where the spans carry no
counter or the trace holds no such kernel."""

from benchmark import window_work

LAYER = "window and full attention layer"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    return window_work.prefill_roofline(
        obs, window_work.FULL, "full_chunk_attn_roofline.serve")
