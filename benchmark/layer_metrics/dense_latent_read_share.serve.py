"""Share of device busy time under the dense read of a latent cache: a full
layer without an indexer attending every position its queries can see,
`latent_read` in the decode tick (the absorbed form over the row's live
pages, kernel `paged_latent_decode_attn`) and `latent_read_prefill` in a
prefill or a chunk (the projected form, kernel `latent_prefill_attn`); a
traced run prints the two apart. None where the program carries no such
name."""

from benchmark import latent_scopes, mla_work

LAYER = "latent attention layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = mla_work.dense_trace(obs)
    if trace is None:
        return None
    return latent_scopes.print_and_sum(
        "dense_latent_read_share.serve", latent_scopes.split_shares(
            trace, (mla_work.TICK_SCOPE, mla_work.PREFILL_SCOPE)))
