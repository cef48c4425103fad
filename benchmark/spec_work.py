"""What a verify tick of a model that drafts needs and what the host can
count of it, from the shapes run and the program's own counters alone
(beside `kernel_work.py`, whose `roofline_percent` turns work into a share,
and `latent_work.py`, whose one-query tick this is the two-query form of).

Needed work, not executed work: the sparse read of a verify tick is charged
one read of the index key of every position one of its queries can SEE
(`index_visible`: both trunk queries in every trunk layer and the module's
positions in its own layer) and one read of the entry of every position one
SELECTED (`index_selected`), with the indexer's and the absorbed attention's
products over them, whatever the program gathers twice, pads or sorts on the
way and whatever kernel does it. So the share cannot pass 100%, and a later
kernel that reads a row's entries once for both queries is measured against
the same work.
"""

from __future__ import annotations

MODULE = ("mtp_embed", "mtp_proj", "mtp_layer", "mtp_head")
COUNTERS = ("spec_offered", "spec_accepted", "spec_tokens",
            "spec_dead_entries", "mtp_positions")


def verify_read_work(index_visible: float, index_selected: float,
                     model: dict, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one tick's selections and sparse attention,
    counts summed over the tick's rows, queries and caches. Per visible
    position: its index key read once (`index_head_dim` numbers: 256 B) and
    one product a head of the indexer. Per selected position: its entry read
    once (`kv_lora_rank + qk_rope_head_dim` numbers: 1,152 B), a score
    product over the whole entry and a weighted sum over the latent, a
    head."""
    width = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    key = model["index_head_dim"]
    flops = (index_visible * model["index_n_heads"] * key * 2
             + index_selected * model["num_attention_heads"]
             * (width + model["kv_lora_rank"]) * 2)
    hbm = (index_visible * key + index_selected * width) * dtype_bytes
    return flops, hbm


def spec_spans(obs_or_spans, name: str = "serve_decode_step") -> list:
    """The spans of `name` that carry a drafting family's counters."""
    spans = (obs_or_spans.get("spans", ()) if isinstance(obs_or_spans, dict)
             else obs_or_spans)
    return [s for s in spans if s["name"] == name and "spec_offered" in s]


def host_verify_counts(runs: list, model: dict) -> dict:
    """What the verify ticks' counters must sum to, from what the HOST saw
    alone. `runs`: one (prompt tokens, [tokens each verify tick the device
    ran for the request made, 1 or 2, in order]) a request (off the spans'
    `verify_rows`, overruns among them). Before a tick the row
    holds K tokens (its prompt, its first token, what the ticks before made):
    its first query sees K positions and its second K + 1, in every trunk
    layer; the module runs where the first query stood and, behind an
    accepted draft, where the second did, in its own layer. A query selects
    min(what it sees, `index_topk`). Every row-tick is offered a draft (a
    request's first comes from `first_draft`, which counts on no span)."""
    layers, topk = model["num_hidden_layers"], model["index_topk"]
    experts, k = layers - model["first_k_dense_replace"], \
        model["num_experts_per_tok"]
    out = dict.fromkeys(("row_ticks", "tokens", "accepted", "index_visible",
                         "index_selected", "routed_total", "mtp_positions",
                         "dead_entries"), 0)
    for prompt, ticks in runs:
        held = prompt + 1
        for made in ticks:
            seen = [held] * layers + [held + 1] * layers + [held] + (
                [held + 1] if made == 2 else [])
            out["index_visible"] += sum(seen)
            out["index_selected"] += sum(min(s, topk) for s in seen)
            out["row_ticks"] += 1
            out["tokens"] += made
            out["accepted"] += made == 2
            held += made
    out["mtp_positions"] = out["row_ticks"] + out["accepted"]
    out["routed_total"] = k * (2 * experts * out["row_ticks"]
                               + out["mtp_positions"])
    out["dead_entries"] = (out["row_ticks"] - out["accepted"]) * layers
    return out


def host_unit_positions(units: list) -> int:
    """`mtp_positions` over prefill units, from each unit's own place
    (`bucket`, `prompt`, `offset`, `chunk`): the valid prompt positions it
    holds whose next id lies in the bucket, which leaves out the prompt's
    last (its next token is drawn later: `first_draft`)."""
    total = 0
    for u in units:
        pad = u["bucket"] - u["prompt"]
        first = max(u["offset"], pad)
        last = min(u["offset"] + u["chunk"], u["bucket"] - 1)
        total += max(last - first, 0)
    return total
