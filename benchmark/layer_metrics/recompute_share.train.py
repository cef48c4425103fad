"""Share of device busy time spent on operations the step had already done
once: everything under the schedule's `pp_recompute` (a B or W unit running
its stage forward again) or under JAX's remat marker, by `scopes.classify`.
Mean over the cell's chips."""

from benchmark import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = scopes.for_observation(obs, "train")
    if trace is None:
        return None
    return scopes.class_shares(trace).get("recompute", 0.0)
