"""Share of the window's decode ticks at which the HOST SET THE PACE:
`ticks_found_ready` over `ticks`, summed over the window's
`serve_decode_step` spans. A tick counts when its `block_until_ready`
returned at once (under `serve/engine.py` `READY_S`, what a block on a ready
array costs with a margin) AND no prefill unit was enqueued behind it. With
one tick in flight the first says that the device finished tick k before
the host came for it; the second that it then had only tick k+1 to go on
with, which the host had enqueued a moment before: the device waited for
the host, or came within that moment of it. (Behind a unit a tick is found
ready because the hand-over kept the host 3 ms while the device ran the
unit: the host was late and the device busy, and such ticks are not
counted.) It is a share of TICKS, not of time: the capture's idle share
(`device_idle_share.serve`) is the seconds, and the two are set side by side
in PERF.md section 6, PR 50. None for a program before PR 50."""

from benchmark import host_stall

LAYER = "serving engine host thread"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"

NAME = "host_bound_tick_share.serve"


def read(obs: dict):
    value = host_stall.found_ready_share(obs)
    if value is not None:
        acc = host_stall.account(host_stall.account_spans(obs))
        print(f"{NAME}: {acc['ticks_found_ready']} of {acc['ticks']} ticks "
              f"found ready with no unit enqueued behind them", flush=True)
    return value
