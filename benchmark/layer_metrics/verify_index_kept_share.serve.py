"""Share of the positions a verify tick's queries could see that the indexer
let them read: `index_selected` over `index_visible`, the program's own
counters summed over the window's `serve_decode_step` spans that carry the
drafting family's counters (both trunk queries x trunk layers, the module's
positions x its layer). 100% would mean the traffic never works the selection
(no row longer than `index_topk`). None where no span carries them."""

from benchmark import spec_work

LAYER = "sparse-attention indexer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_counter"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    spans = spec_work.spec_spans(obs)
    seen = sum(s["index_visible"] for s in spans)
    if not seen:
        return None
    kept = sum(s["index_selected"] for s in spans)
    print(f"verify_index_kept_share.serve: the ticks' queries saw {seen} "
          f"positions and selected {kept}", flush=True)
    return 100.0 * kept / seen
