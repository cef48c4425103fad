"""What plain multi-head latent attention needs when every query reads every
position it can see (a full layer without an indexer: models/latent_moe/),
from the model's sizes and the program's own counter alone (beside
`kernel_work.py`, whose `roofline_percent` turns these into a share), and
what the per-layer readers of such a cell share: the two scopes of the dense
read, the counter's sums over the window's spans, a kernel's time in the
trace.

Needed work, not executed work. `latent_visible` counts, summed over rows
and layers, the positions a decoding or prefilling row can see (pads and
rows that do not decode count for nothing): the tick is charged one read of
each such entry as published (`kv_lora_rank + qk_rope_head_dim` numbers:
1152 B, not the 1280 B it is stored in) and the absorbed products over it; a
prefill unit the projected products of each visible pair, its own queries and
outputs once and the expanded keys and values of its row so far once, however
often the kernel's blocks re-read them. So neither share can pass 100%.
"""

from __future__ import annotations

from benchmark import hybrid_scopes, scopes

TICK_SCOPE, PREFILL_SCOPE = "latent_read", "latent_read_prefill"
TICK_KERNEL, PREFILL_KERNEL = "paged_latent_decode_attn", "latent_prefill_attn"
COUNTER = "latent_visible"


def dense_tick_work(latent_visible: float, model: dict,
                    dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one tick's dense read, `latent_visible` summed
    over the tick's rows and layers. Per visible position: its entry read
    once, a score product over the whole entry and a weighted sum over the
    latent, a head."""
    rank, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    flops = latent_visible * model["num_attention_heads"] * (
        2 * rank + rope) * 2
    return flops, latent_visible * (rank + rope) * dtype_bytes


def prefill_unit_work(latent_visible: float, queries: float, keys: float,
                      model: dict, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one prefill unit's attention in the projected
    form, all layers: `latent_visible` visible (query, position) pairs summed
    over the unit's queries and layers, each a score product over nope + rope
    numbers and a weighted sum over v numbers a head; `queries` tokens'
    queries and outputs and `keys` positions' keys and values (one roped part
    for all heads), once a layer."""
    layers, H = model["num_hidden_layers"], model["num_attention_heads"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    flops = latent_visible * H * (nope + rope + v) * 2
    hbm = layers * dtype_bytes * (queries * H * (nope + rope + v)
                                  + keys * (H * (nope + v) + rope))
    return flops, hbm


# -- what the readers share -----------------------------------------------------

def dense_trace(obs: dict):
    """The scoped trace of a traced serving run whose programs carry the
    dense read's names, else None (another kind of cell, an untraced run, a
    program without the names: the parent of the PR that added them)."""
    trace = scopes.for_observation(obs, "serve")
    if trace is None:
        return None
    named = any(hybrid_scopes.scope_of(op, (TICK_SCOPE, PREFILL_SCOPE))
                for events in trace["devices"].values() for op in events)
    return trace if named else None


def counted_spans(obs: dict, name: str) -> list:
    """The observation's spans of `name` that carry the counter."""
    return [s for s in obs.get("spans", ())
            if s["name"] == name and COUNTER in s]


def kernel_calls(trace: dict, kernel: str) -> tuple:
    """(seconds, calls) of the kernel's events on the first device plane:
    instruction `<kernel>` or `<kernel>.<n>`."""
    events = trace["devices"][sorted(trace["devices"])[0]]
    took = [op.end_ns - op.start_ns for op in events
            if op.instruction == kernel
            or op.instruction.startswith(kernel + ".")]
    return 1e-9 * sum(took), len(took)
