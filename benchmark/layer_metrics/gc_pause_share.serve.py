"""Share of the window any thread of the process spent COLLECTING:
(`gc_s` + `wait_gc_s`) summed over the window's `serve_decode_step` spans,
over the window's seconds. The thread that collects holds the interpreter
lock, so every other Python thread stands still for those seconds; `gc_s` is
the part outside the engine thread's two device waits, which held the
engine's thread for certain, `wait_gc_s` the part while it slept in one,
which held it only if the device finished meanwhile (`gc.callbacks`,
`utils/trace.HostWatch`). Prints both parts, the collections, the full
(generation 2) ones among them and the process's longest pause. None for a
program before PR 50."""

from benchmark import host_stall

LAYER = "serving engine host thread"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"

NAME = "gc_pause_share.serve"


def read(obs: dict):
    value = host_stall.share_of_window(obs, "gc_s", "wait_gc_s")
    if value is None:
        return None
    acc = host_stall.account(host_stall.account_spans(obs))
    print(f"{NAME}: outside the device waits {acc['gc_s']:.4f} s (held the "
          f"engine's thread), inside them {acc['wait_gc_s']:.4f} s, of "
          f"{host_stall.window_s(obs):.3f}; {acc['gc_collections']} "
          f"collections, {acc['gc_gen2']} of generation 2; the process's "
          f"longest pause so far {1e3 * acc['gc_longest_s']:.2f} ms",
          flush=True)
    return value
