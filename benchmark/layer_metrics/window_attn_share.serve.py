"""Share of device busy time under the window layers' attention: the tick's
one query a row over the slot's ring (`window_decode_attn`) and a prefill's
or a chunk's banded attention (`window_prefill_attn`), the decode tick and
the prefill units apart; a traced run prints each part. The projections, the
ring's writes and gathers and the feed-forwards are not in it. None where
the program carries no such name."""

from benchmark import latent_scopes, window_work

LAYER = "window and full attention layer"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = window_work.window_trace(obs)
    if trace is None:
        return None
    parts = latent_scopes.split_shares(trace, window_work.WINDOW_ATTN)
    return latent_scopes.print_and_sum("window_attn_share.serve", parts)
