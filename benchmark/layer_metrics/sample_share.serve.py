"""Share of device busy time under `sample`: the tick's key split and
`sample_rowwise`, and the first-token sampler's branches where their paths
carry the name. The program chooses the sampler's work from the batch's
knobs (an argmax a row for an all-greedy tick, a sort a row only where a
sampling row has a top-k or a top-p), so on greedy traffic this is the alarm
for a sort coming back. None where the program carries no such name."""

from benchmark import scopes

LAYER = "serving engine decode tick"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = scopes.for_observation(obs, "serve")
    if trace is None:
        return None
    return scopes.share_under(trace, ("sample",))
