"""What the dense state-space block's cell counts and what its per-layer
readers share: the sizes of a `granitemoehybrid` configuration under the
names `benchmark/ssm_work.py`'s `step_work` and `scan_work` take (called as
they are: the recurrence's step and the chunked scan are the expert block's
code), the host's counts that the program's own counters must meet, and the
spans and the trace this family's readers read.

The program's counters (`models/ssm_moe/model.py` COUNTERS), each summed
over layers, on `serve_decode_step` and on every `serve_prefill` unit:
`ssm_rows` (rows a Mamba-2 layer advanced one step), `ssm_positions` (valid
positions a Mamba-2 layer scanned), `kv_entries_read` (entries a softmax
layer's queries read), `state_carries` and `state_bytes_carried` (a chunk
that started from the row the chunk before it left).
"""

from __future__ import annotations

import collections

from benchmark import hybrid_scopes, scopes, ssm_work

SSM = ssm_work.SSM                              # ssm_proj, _conv, _scan, _step, _norm
STATE = hybrid_scopes.STATE                     # state_gather, state_write
CARRY = ("state_carry_in", "state_carry_out")
MAMBA = SSM + STATE + CARRY
MLP = ("mlp", "decode_mlp")
ATTENTION = ("attn_qkv", "attn_core", "attn_out", "decode_attn", "kv_write",
             "kv_gather")
COUNTER = "ssm_positions"                       # a span of this family carries it
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


# -- the counts -----------------------------------------------------------------

def sizes(model: dict) -> dict:
    """The numbers of the configuration the counts need, the Mamba-2 ones
    under the names `ssm_work.step_work` / `scan_work` read."""
    types = model["layer_types"]
    inner = model["mamba_n_heads"] * model["mamba_d_head"]
    return {
        "ssm_layers": types.count("mamba"),
        "softmax_layers": types.count("attention"),
        "heads": model["mamba_n_heads"], "head_dim": model["mamba_d_head"],
        "state": model["mamba_d_state"], "groups": model["mamba_n_groups"],
        "conv": model["mamba_d_conv"], "chunk": model["mamba_chunk_size"],
        "conv_width": inner + 2 * model["mamba_n_groups"]
        * model["mamba_d_state"],
        "kv_heads": model["num_key_value_heads"],
        "kv_head_dim": model["hidden_size"] // model["num_attention_heads"]}


def slot_row_bytes(sz: dict, dtype_bytes: int = 2) -> int:
    """Bytes of one slot's row of the recurrent store over all Mamba-2
    layers: the float32 state and the convolution's `conv - 1` inputs."""
    return sz["ssm_layers"] * (
        4 * sz["heads"] * sz["head_dim"] * sz["state"]
        + dtype_bytes * (sz["conv"] - 1) * sz["conv_width"])


def host_tick_counts(records: list, warm_buckets, sz: dict) -> dict:
    """What the ticks of a run must have counted, from the lengths alone: a
    request of n prompt tokens whose client received m tokens went through
    m - 1 ticks, the j-th with n + j places to see; each warm-up request (a
    prompt the bucket long, two tokens) through one. {"rows": decoded rows,
    "ssm_rows": rows x Mamba-2 layers, "kv_entries_read": the sum of the
    rows' contexts x softmax layers}."""
    rows = len(list(warm_buckets))
    contexts = sum(b + 1 for b in warm_buckets)
    for r in records:
        n, ticks = len(r["request"]["prompt"]), len(r["tokens"]) - 1
        if ticks < 1:
            continue
        rows += ticks
        contexts += ticks * n + ticks * (ticks + 1) // 2
    return {"rows": rows, "ssm_rows": rows * sz["ssm_layers"],
            "kv_entries_read": contexts * sz["softmax_layers"]}


def host_unit_counts(units: list, sz: dict, dtype_bytes: int = 2) -> dict:
    """What the given `serve_prefill` spans must have counted, from their own
    places alone: a unit of `chunk` places at `offset` of a bucket whose
    request has `prompt` tokens (left-padded) holds the valid places past
    the pad; a chunk of a larger bucket that is not its request's first
    (`chunks_skipped` rides the first) carries the row in, `dtype_bytes` a
    number of its convolution inputs."""
    positions = carries = 0
    for span in units:
        pad = span["bucket"] - span["prompt"]
        positions += max(0, span["offset"] + span["chunk"]
                         - max(pad, span["offset"]))
        carries += (span["chunk"] < span["bucket"]
                    and "chunks_skipped" not in span)
    return {"ssm_positions": positions * sz["ssm_layers"],
            "state_carries": carries * sz["ssm_layers"],
            "state_bytes_carried": carries * slot_row_bytes(sz, dtype_bytes)}


def prompts_not_scanned_whole(units: list, prompts, sz: dict) -> int:
    """How many of `prompts` (the lengths of the requests the clients
    received whole, and of the warm-up's) have NO request of their own among
    the given `serve_prefill` spans whose units' `ssm_positions` add up to
    the prompt's length x Mamba-2 layers: a chunk of real tokens that was
    skipped, or a unit that never ran, leaves its request short. Requests
    are the spans' `request` ids; one answers one prompt of its length."""
    scanned = collections.Counter()
    length = {}
    for span in units:
        scanned[span["request"]] += span["ssm_positions"]
        length[span["request"]] = span["prompt"]
    whole = collections.Counter(
        n for request, n in length.items()
        if scanned[request] == n * sz["ssm_layers"])
    return sum((collections.Counter(prompts) - whole).values())


# -- what the readers share -----------------------------------------------------

def family_spans(obs: dict, name: str) -> list:
    """The observation's spans of `name` that carry this family's counters
    (another family's, or a build before them, carries none)."""
    if obs.get("kind") != "serve":
        return []
    return [s for s in obs.get("spans") or ()
            if s["name"] == name and COUNTER in s]


def chunk_spans(obs: dict) -> list:
    """The `serve_prefill` spans that ran a piece of a larger bucket."""
    return [s for s in family_spans(obs, "serve_prefill")
            if s.get("chunk", 0) < s.get("bucket", 0)]


def mamba_trace(obs: dict):
    """The scoped trace of a traced serving run whose programs carry the
    state-space names AND whose spans carry this family's counters, else
    None (another kind of cell, an untraced run, a program without either:
    the parent of the PR that added them)."""
    if not family_spans(obs, "serve_decode_step") \
            and not family_spans(obs, "serve_prefill"):
        return None
    trace = scopes.for_observation(obs, "serve")
    if trace is None:
        return None
    named = any(hybrid_scopes.scope_of(op, SSM)
                for events in trace["devices"].values() for op in events)
    return trace if named else None


def unit_positions(obs: dict):
    """Mean places (`chunk`) of the window's prefill units, None where the
    window saw none."""
    units = family_spans(obs, "serve_prefill")
    return sum(s["chunk"] for s in units) / len(units) if units else None
