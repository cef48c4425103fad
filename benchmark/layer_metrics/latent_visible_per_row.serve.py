"""Positions a decoding row reads in one layer of a model whose every layer
reads its whole latent cache: `latent_visible` over `tokens` x layers, the
program's own counters summed over the window's `serve_decode_step` spans.
Whether the traffic works the dense read: a few hundred would mean rows too
short for it to matter. None where the spans carry no such counter."""

from benchmark import mla_work

LAYER = "latent attention layer"
UNIT = "positions"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_counter"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    spans = mla_work.counted_spans(obs, "serve_decode_step")
    rows = sum(s["tokens"] for s in spans)
    if not rows:
        return None
    return sum(s[mla_work.COUNTER] for s in spans) / (
        rows * obs["cell"].model["num_hidden_layers"])
