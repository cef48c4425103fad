"""Share of the drafts a verify tick was offered that it accepted:
`spec_accepted` over `spec_offered`, the program's own counters summed over
the window's `serve_decode_step` spans. Prints the tokens a row-tick
(`tokens / row_ticks`: 1 + this share where every row-tick has a draft).
Under seeded weights a draft is right about once in a vocabulary. None
where no span carries the counters or no draft was offered."""

from benchmark import spec_work

LAYER = "serving engine decode tick"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_counter"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    spans = spec_work.spec_spans(obs)
    offered = sum(s["spec_offered"] for s in spans)
    if not offered:
        return None
    accepted = sum(s["spec_accepted"] for s in spans)
    row_ticks = sum(s["row_ticks"] for s in spans)
    print(f"spec_accept_rate.serve: {offered} drafts offered over {row_ticks} "
          f"row-ticks, {accepted} accepted; "
          f"{sum(s['tokens'] for s in spans) / max(row_ticks, 1):.5f} tokens "
          f"a row-tick, {sum(s['tokens_discarded'] for s in spans)} tokens "
          f"discarded, {sum(s['spec_dead_entries'] for s in spans)} cache "
          f"places written and not kept", flush=True)
    return 100.0 * accepted / offered
