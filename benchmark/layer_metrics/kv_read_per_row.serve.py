"""Bytes of pages and rings one tick reads for one decoding row, as
published: `full_entries_read` x a full layer's 2,560 B an entry plus
`window_entries_read` x a window layer's 5,120 B, over the rows that decoded
(`tokens`), from the program's own counters summed over the window's
`serve_decode_step` spans. What the two kinds of layer make of the mix: a
row at 6k positions reads 31 MB of pages and 3.3 MB of rings a tick where
seven full layers of 8 KV heads would read 215 MB. None where the spans
carry no such counter."""

from benchmark import window_work

LAYER = "window and full attention layer"
UNIT = "MB"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    sums = window_work.counter_sums(obs)
    if not sums:
        return None
    sz = window_work.sizes(obs["cell"].model)
    _, hbm = window_work.tick_read_work(sums[window_work.WINDOW_COUNTER],
                                        sums[window_work.FULL_COUNTER], sz)
    return hbm / sums["tokens"] / 1e6
