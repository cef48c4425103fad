"""Share of the traced seconds of steady ticks in which no operation ran on
the device."""

from benchmark import xplane

LAYER = "device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    if obs.get("kind") != "serve" or not (obs.get("xplane") or {}).get(
            "devices"):
        return None
    return xplane.idle_share_percent(obs["xplane"])
