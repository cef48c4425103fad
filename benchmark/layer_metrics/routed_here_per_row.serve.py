"""Of the experts a decoding row chooses in an expert layer, how many land
on the experts held here: `routed_here` over the rows that decoded (`tokens`)
times the expert layers, from the program's own counters on the
`serve_decode_step` spans. 5.5 of 22 where the router spreads evenly over
512 and 128 are held; the alarm for traffic or a router that does not work
this chip's share. None where the spans carry no counters."""

from benchmark import hybrid_scopes, ssm_work

LAYER = "expert layer"
UNIT = "experts"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    sums = hybrid_scopes.counter_sums(obs)
    if not sums or not sums["tokens"]:
        return None
    layers = ssm_work.sizes(obs["cell"].model)["expert_layers"]
    return sums["routed_here"] / (sums["tokens"] * layers)
