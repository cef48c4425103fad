"""Device memory the compiled train step needs, from the compiler's own
analysis on the `compiled_memory` the trainer put on its `profile_window`
span: the peak of the compiler's buffer assignment (`compiler_peak_bytes`,
`memory_analysis().peak_memory_in_bytes`) where the span carries it. Where it
does not (a backend that does not say), argument + output + temporary -
aliased bytes, which on XLA:TPU counts the donated outputs twice (19.4 GB for
a step whose peak is 16.0 GB on a 16.9 GB chip, PR 24) and is an upper bound
only. The allocator's `memory_peak_bytes` is a floor on this runtime
(PERF.md); the compiler's peak is what decides whether a configuration fits."""

LAYER = "memory"
UNIT = "GB"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(obs: dict):
    if obs.get("kind") != "train":
        return None
    memory = next((s["compiled_memory"] for s in obs["spans"]
                   if s["name"] == "profile_window"
                   and s.get("compiled_memory")), None)
    if memory is None:
        return None
    peak = memory.get("compiler_peak_bytes") or (
        memory["argument_bytes"] + memory["output_bytes"]
        + memory["temp_bytes"] - memory["alias_bytes"])
    return peak / 1e9
