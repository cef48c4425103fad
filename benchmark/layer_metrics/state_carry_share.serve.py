"""Share of device busy time a prefill chunk spends reading its slot's row of
the recurrent store and writing it back (`state_carry_in`,
`state_carry_out`): the alarm for a chunk that copies more than its slot's
row (75.5 MB in and out where the store is 3.6 GB). A traced run prints each
part. None where the program carries no such name or the spans none of the
family's counters."""

from benchmark import granite_work, hybrid_scopes

LAYER = "state-space layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = granite_work.mamba_trace(obs)
    if trace is None:
        return None
    parts = hybrid_scopes.part_shares(trace, granite_work.CARRY)
    print("state_carry_share.serve parts, % of busy time: " + ", ".join(
        f"{name} {share:.3f}" for name, share in parts.items()), flush=True)
    return sum(parts.values())
