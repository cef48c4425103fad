"""Share of device busy time under `optimizer` and `grad_clip`: the global
norm, the clip and the AdamW update of every leaf. Mean over the chips."""

from benchmark import scopes

LAYER = "optimizer"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = scopes.for_observation(obs, "train")
    if trace is None:
        return None
    return scopes.share_under(trace, ("optimizer", "grad_clip"))
