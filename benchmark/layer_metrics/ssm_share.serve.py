"""Share of device busy time under the state-space layers' scopes: the
Mamba-2 mixer (`ssm_proj`, `ssm_conv`, `ssm_norm`, `ssm_scan` in a prefill
unit, `ssm_step` in the tick) and its recurrent store (`state_gather`,
`state_write`), the decode tick and the prefill units apart; a traced run
prints each part. None where the program carries no such name."""

from benchmark import hybrid_scopes, latent_scopes, ssm_work

LAYER = "state-space layer"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = ssm_work.ssm_trace(obs)
    if trace is None:
        return None
    parts = latent_scopes.split_shares(trace,
                                       ssm_work.SSM + hybrid_scopes.STATE)
    return latent_scopes.print_and_sum("ssm_share.serve", parts)
