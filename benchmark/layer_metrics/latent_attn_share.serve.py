"""Share of device busy time under the latent-attention mixers' scopes:
`mla_proj` (down and up projections, norms, rope, absorption), `sparse_attn`
(a full layer's attention over the chosen entries), `window_attn` (a sliding
layer's), `attn_gate` and `attn_out`; a traced run prints each part, the
decode tick and the prefills apart. None where the program carries no such
name."""

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
        "latent_attn_share.serve",
        latent_scopes.split_shares(trace, latent_scopes.ATTENTION))
