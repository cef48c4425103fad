"""90th percentile, over requests submitted in the traced run's window, of
the client's `submit` to the first token on its stream (a failed or refused
request counts as infinite). What a user feels, but a tail over the few tens
of requests a window holds: it swings by a fifth from run to run at full
occupancy, so it stands here and not among the bounded end-to-end metrics."""

from benchmark.stats import percentile

LAYER = "serving engine admission"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def read(obs: dict):
    if obs.get("kind") != "serve" or not obs["client"]["ttft_s"]:
        return None
    return 1e3 * percentile(obs["client"]["ttft_s"], 90)
