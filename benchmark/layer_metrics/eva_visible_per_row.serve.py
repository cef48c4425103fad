"""Entries a decoding row reads in one layer of a model of the
compressed-window family: (`eva_window_visible` + `eva_summary_visible`)
over `tokens` x layers, the program's own counters summed over the window's
`serve_decode_step` spans; both parts and the row's mean context are printed
beside it. Whether the traffic works the read (a few hundred would mean rows
too short for it to matter), and the compression it buys (entries read
against positions in context). None where the spans carry no such counter."""

from benchmark import eva_work

LAYER = "compressed-window attention layer"
UNIT = "entries"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_counter"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    spans = eva_work.counted_spans(obs, "serve_decode_step")
    rows = sum(s["tokens"] for s in spans)
    if not rows:
        return None
    model = obs["cell"].model
    per = rows * model["num_hidden_layers"]
    window = sum(s[eva_work.WINDOW] for s in spans) / per
    summary = sum(s[eva_work.SUMMARY] for s in spans) / per
    # a row at position p reads (p // W) x (W / C) pooled entries and p mod W
    # + 1 exact ones: its context is the first times C plus the second
    context = summary * model["chunk_size"] + window
    print(f"eva_visible_per_row.serve: a decoding row reads {window:.1f} "
          f"exact entries of its window and {summary:.1f} pooled ones a "
          f"layer ({100.0 * summary / (window + summary):.1f}% pooled), of a "
          f"mean context of {context:.0f} positions: "
          f"{context / (window + summary):.1f} positions an entry read",
          flush=True)
    return window + summary
