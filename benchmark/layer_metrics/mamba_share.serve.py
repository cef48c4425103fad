"""Share of device busy time under the dense state-space block's Mamba-2
scopes: the mixer (`ssm_proj`, `ssm_conv`, `ssm_norm`, `ssm_scan` in a
prefill unit, `ssm_step` in the tick), its recurrent store in the tick
(`state_gather`, `state_write`) and a chunk's read and write of its slot's
row (`state_carry_in`, `state_carry_out`), the decode tick and the prefill
units apart; a traced run prints each part, and the dense halves' (`mlp`,
`decode_mlp`) and the softmax layers' shares beside them. None where the
program carries no such name or the spans none of the family's counters."""

from benchmark import granite_work, latent_scopes

LAYER = "state-space layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = granite_work.mamba_trace(obs)
    if trace is None:
        return None
    for label, names in (("mlp", granite_work.MLP),
                         ("attention", granite_work.ATTENTION)):
        parts = latent_scopes.split_shares(trace, names)
        print(f"mamba_share.serve: beside it, {label} "
              f"{sum(t for t, _ in parts.values()):.2f}% of busy time in the "
              f"tick + {sum(f for _, f in parts.values()):.2f}% in prefill "
              f"units", flush=True)
    return latent_scopes.print_and_sum(
        "mamba_share.serve",
        latent_scopes.split_shares(trace, granite_work.MAMBA))
