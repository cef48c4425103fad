"""Mean host-clock time of one decode tick: the sum of `dur` over the sum of
`ticks` of the engine's own aggregated `serve_decode_step` spans that began
in the window."""

LAYER = "serving engine decode tick"
UNIT = "ms"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_span"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    spans = [s for s in obs["spans"] if s["name"] == "serve_decode_step"]
    ticks = sum(s["ticks"] for s in spans)
    return 1e3 * sum(s["dur"] for s in spans) / ticks if ticks else None
