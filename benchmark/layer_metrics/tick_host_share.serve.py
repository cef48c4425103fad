"""Share of a decode tick's wall time in which the engine's thread works
rather than waits for the device: (`stage_s` + `dispatch_s` + `emit_s`) over
those plus `wait_s`, summed over the window's `serve_decode_step` spans."""

LAYER = "serving engine decode tick"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_span"

HOST = ("stage_s", "dispatch_s", "emit_s")


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    spans = [s for s in obs["spans"]
             if s["name"] == "serve_decode_step" and "wait_s" in s]
    host = sum(s[k] for s in spans for k in HOST)
    total = host + sum(s["wait_s"] for s in spans)
    return 100.0 * host / total if total else None
