"""90th percentile of the engine's own queue wait (submit to admission, its
`serve_queue_wait` spans) over requests admitted in the window."""

from benchmark.stats import percentile

LAYER = "serving engine admission"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    waits = [s["dur"] for s in obs["spans"] if s["name"] == "serve_queue_wait"]
    return 1e3 * percentile(waits, 90) if waits else None
