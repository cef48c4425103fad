"""Share of the traced steps in which no operation ran on a device (mean
over the cell's chips). Masked pipeline slots are device work, so a pp cell's
bubble does not show here."""

from benchmark import xplane

LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    if obs.get("kind") != "train" or not (obs.get("xplane") or {}).get(
            "devices"):
        return None
    return xplane.idle_share_percent(obs["xplane"])
