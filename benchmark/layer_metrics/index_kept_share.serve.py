"""Share of the positions its queries could see that the indexer let them
read: `index_selected` over `index_visible`, the program's own counters
summed over the window's `serve_decode_step` and `serve_prefill` spans
(queries x full layers). 100% would mean the traffic never works the
selection (no row longer than `index_topk`); a run prints the ticks' and the
prefills' shares apart. None where the spans carry no such counter."""

from benchmark import latent_scopes

LAYER = "sparse-attention indexer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_counter"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    sums = latent_scopes.index_sums(obs)
    if not sums or not sums["index_visible"]:
        return None
    for name in ("serve_decode_step", "serve_prefill"):
        part = latent_scopes.index_sums(obs, (name,))
        if part and part["index_visible"]:
            print(f"index_kept_share.serve: {name} spans saw "
                  f"{part['index_visible']} positions and selected "
                  f"{part['index_selected']}: "
                  f"{100.0 * part['index_selected'] / part['index_visible']:.2f}%",
                  flush=True)
    return 100.0 * sums["index_selected"] / sums["index_visible"]
