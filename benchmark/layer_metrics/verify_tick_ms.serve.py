"""Mean host-clock time of one VERIFY tick (two trunk queries and up to two
module positions a row): the sum of `dur` over the sum of `ticks` of the
window's `serve_decode_step` spans that carry the drafting family's
counters. None where none does."""

from benchmark import spec_work

LAYER = "serving engine decode tick"
UNIT = "ms"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_span"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    spans = spec_work.spec_spans(obs)
    ticks = sum(s["ticks"] for s in spans)
    if not ticks:
        return None
    ahead = sum(s["ticks_ahead"] for s in spans)
    print(f"verify_tick_ms.serve: {ticks} ticks, {ahead} of them enqueued "
          f"behind a tick in flight ({ahead / ticks:.4f}), "
          f"{sum(s['row_ticks'] for s in spans) / ticks:.2f} rows a tick",
          flush=True)
    return 1e3 * sum(s["dur"] for s in spans) / ticks
