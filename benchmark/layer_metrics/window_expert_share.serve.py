"""Share of device busy time under the expert layers' scopes (`moe_router`,
`moe_dispatch`, `moe_experts`, `moe_combine`; `moe_shared` reads 0: this
family adds nothing beside the routed sum) in a run of the window / full
softmax family, the decode tick and the prefill units apart; a traced run
prints each part. With the two attention shares it says where a tick and a
chunk spend what attention does not take. None where the program carries
none of the family's names."""

from benchmark import hybrid_scopes, latent_scopes, window_work

LAYER = "expert layer"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = window_work.window_trace(obs)
    if trace is None:
        return None
    parts = latent_scopes.split_shares(trace, hybrid_scopes.MOE)
    return latent_scopes.print_and_sum("window_expert_share.serve", parts)
