"""Share of device busy time under the latent expert layers' scopes: the
five `moe_*` (`moe_router`, `moe_dispatch`, `moe_experts`, `moe_shared`,
`moe_combine`) and the two projections round the routed experts
(`moe_latent_in`, `moe_latent_out`), the decode tick and the prefill units
apart; a traced run prints each part. None where the program carries no
such name."""

from benchmark import hybrid_scopes, latent_scopes, ssm_work

LAYER = "expert layer"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = ssm_work.ssm_trace(obs)
    if trace is None:
        return None
    parts = latent_scopes.split_shares(trace,
                                       hybrid_scopes.MOE + ssm_work.LATENT)
    return latent_scopes.print_and_sum("latent_expert_share.serve", parts)
