"""Model FLOP/s utilisation of the traced run's window: the benchmark's own
count per token (benchmark/flops.py: no embedding lookup, causal attention,
no credit for recompute) x tokens/s over chips x the published bf16 peak."""

from benchmark import flops, peaks

LAYER = "model step"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(obs: dict):
    if obs.get("kind") != "train":
        return None
    devices = obs["devices"]
    peak = peaks.peaks_for(devices[0].device_kind)["bf16_flops_per_s"]
    per_token = flops.train_flops_per_token(obs["cell"].model,
                                            obs["seq_length"])
    return 100.0 * per_token * obs["tokens_per_s"] / (len(devices) * peak)
