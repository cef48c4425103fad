"""Share of the device's busy time spent in the multi-token-prediction
module: self time under `mtp_embed`, `mtp_proj`, `mtp_layer` (the layer's own
scopes nest under it) and `mtp_head`, over busy time, the decode tick's and
the prefill units' (and the first draft's) apart, parts printed. None without
a device trace or where no operation carries the names."""

from benchmark import hybrid_scopes, latent_scopes, scopes, spec_work

LAYER = "multi-token prediction module"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = scopes.for_observation(obs, "serve")
    if trace is None or not any(
            hybrid_scopes.scope_of(op, spec_work.MODULE)
            for events in trace["devices"].values() for op in events):
        return None
    return latent_scopes.print_and_sum(
        "mtp_share.serve", latent_scopes.split_shares(trace, spec_work.MODULE))
