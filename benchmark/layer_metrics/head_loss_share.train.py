"""Share of device busy time under `lm_head_loss`: the output head's
projection and the loss, forward, recompute and backward together. Mean
over the cell's chips (with pp > 1 only the last stage's head does work)."""

from benchmark import scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = scopes.for_observation(obs, "train")
    if trace is None:
        return None
    return scopes.share_under(trace, ("lm_head_loss",))
