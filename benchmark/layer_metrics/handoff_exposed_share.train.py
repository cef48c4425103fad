"""Share of the traced window a chip spends in the stage hand-offs' own
operations (`pp_handoff`: the ring `ppermute`s). On the one operations line
that is the part of a hand-off no compute hides. Mean over the chips; None
where the program ran no hand-off (one stage)."""

from benchmark import scopes

LAYER = "sharding / collectives"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = scopes.for_observation(obs, "train")
    if trace is None:
        return None
    return scopes.share_under(trace, ("pp_handoff",), of="window") or None
