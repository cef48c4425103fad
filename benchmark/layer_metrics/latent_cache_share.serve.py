"""Share of device busy time spent moving the three stores of a model that
keeps latent pages, index pages and a ring a slot: `latent_write` and
`latent_gather` (entries and index keys into and out of their pages: the
index keys of a row's whole table for the scores, the chosen entries for the
attention) and `ring_gather` / `ring_write`; a traced run prints each part,
the decode tick and the prefills apart. What `kv_pool_share.serve` is to the
dense decoder and `state_cache_share.serve` to the hybrid. None where the
program carries no such name."""

from benchmark import latent_scopes

LAYER = "latent attention layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = latent_scopes.latent_trace(obs)
    if trace is None:
        return None
    return latent_scopes.print_and_sum(
        "latent_cache_share.serve",
        latent_scopes.split_shares(trace, latent_scopes.CACHE))
