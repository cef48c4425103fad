"""Share of device busy time under the compressed-window attention's own
scopes: `eva_pool` (a finished window's chunks pooled), `eva_summary_write`
(the pooled entries to their pages), `eva_attn` (the tick's one softmax over
summary and window pages) and `eva_attn_prefill` (a prefill unit's); a traced
run prints each part, the decode tick and the prefills apart. None where the
program carries no such name."""

from benchmark import eva_work, latent_scopes

LAYER = "compressed-window attention layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = eva_work.eva_trace(obs)
    if trace is None:
        return None
    return latent_scopes.print_and_sum(
        "eva_attn_share.serve",
        latent_scopes.split_shares(trace, eva_work.SCOPES))
