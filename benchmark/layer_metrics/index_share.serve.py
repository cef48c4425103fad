"""Share of device busy time the learned selection costs: `index_proj` (the
indexer's queries, key and head weights), `index_score` (every query against
every index key its row can see, float32) and `index_topk` (the exact
selection: a sort); a traced run prints each part, the decode tick and the
prefills apart. None where the program carries no such name."""

from benchmark import latent_scopes

LAYER = "sparse-attention indexer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = latent_scopes.latent_trace(obs)
    if trace is None:
        return None
    return latent_scopes.print_and_sum(
        "index_share.serve",
        latent_scopes.split_shares(trace, latent_scopes.INDEXER))
