"""What the state-space / expert block's layers need, from the shapes run and
the program's own counters alone (beside `kernel_work.py`, whose
`roofline_percent` turns these into a share), and what this family's
per-layer readers share: the names its programs give their device work
(`utils/trace.py` SSM_SCOPES beside the hybrid family's `state_*` and
`moe_*`), and the time a traced tick or prefill unit spends under some of
them.

Needed work, not executed work: the recurrence's step is charged one read and
one write of the float32 state and of the convolution's inputs of the rows
that decoded, whatever passes the program makes over them; the chunked scan
the products under the causal mask alone and its operands at the
activations' width, whatever precision and copies the program takes; the
expert products the weights of the experts that HAD a row and the rows
routed here, whatever tiles the grouped product visits. So no share can
pass 100%.
"""

from __future__ import annotations

from benchmark import hybrid_scopes, scopes

SSM = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_step", "ssm_norm")
LATENT = ("moe_latent_in", "moe_latent_out")
STEP = ("ssm_step",) + hybrid_scopes.STATE
PREFILL_EVENT = "serve_prefill_enqueue"     # one a prefill unit, on the host


# -- the counts -----------------------------------------------------------------

def sizes(model: dict) -> dict:
    """The numbers of the configuration the counts need."""
    pattern = model["hybrid_override_pattern"]
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    return {
        "ssm_layers": pattern.count("M"), "expert_layers": pattern.count("E"),
        "heads": model["mamba_num_heads"], "head_dim": model["mamba_head_dim"],
        "state": model["ssm_state_size"], "groups": model["n_groups"],
        "conv": model["conv_kernel"], "chunk": model["chunk_size"],
        "conv_width": inner + 2 * model["n_groups"] * model["ssm_state_size"],
        "latent": model["moe_latent_size"],
        "width": model["moe_intermediate_size"]}


def step_work(rows: float, sz: dict, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one tick's recurrence steps: per decoding row
    and Mamba-2 layer the float32 state [heads, head_dim, state] and the
    convolution's `conv - 1` inputs, each read once and written once; the
    decay, the rank-one update and the product with C, 5 FLOPs a state
    element."""
    elements = rows * sz["ssm_layers"] * sz["heads"] * sz["head_dim"] * sz["state"]
    conv = rows * sz["ssm_layers"] * (sz["conv"] - 1) * sz["conv_width"]
    return 5 * elements, 2 * (4 * elements + dtype_bytes * conv)


def scan_work(tokens: float, sz: dict, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one prefill unit's chunked scans over `tokens`
    positions (whole chunks): a chunk and layer the products under the
    causal mask, `chunk (chunk + 1) / 2` (query, key) pairs: C B^T a group
    (2 x state a pair) and its product with x a head (2 x head_dim a pair),
    then the chunk's state out and the state coming in applied to its
    queries (each 2 x chunk x head_dim x state a head). Bytes: x, B, C and
    dt read and y written at the activations' width, and a layer's float32
    state read and written once a unit."""
    H, P, N, G, C = (sz[k] for k in ("heads", "head_dim", "state", "groups",
                                     "chunk"))
    chunks = tokens / C
    pairs = C * (C + 1) / 2
    a_chunk = (2 * pairs * N * G + 2 * pairs * P * H + 2 * 2 * C * P * N * H)
    flops = sz["ssm_layers"] * chunks * a_chunk
    hbm = sz["ssm_layers"] * (
        tokens * dtype_bytes * (2 * H * P + 2 * G * N + H) + 2 * 4 * H * P * N)
    return flops, hbm


def expert_tick_work(experts_hit: float, routed_here: float, sz: dict,
                     dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one tick's expert products: per routed row the
    two products of one expert (2 x 2 x latent x width FLOPs), its input row
    read and its output row written in the latent width; per expert hit its
    two matrices read once. Counts summed over the tick's expert layers."""
    flops = routed_here * 2 * 2 * sz["latent"] * sz["width"]
    hbm = (experts_hit * 2 * sz["latent"] * sz["width"]
           + routed_here * 2 * sz["latent"]) * dtype_bytes
    return flops, hbm


# -- what the readers share -----------------------------------------------------

def ssm_trace(obs: dict):
    """The scoped trace of a traced serving run whose programs carry this
    family's names, else None (another kind of cell, an untraced run, a
    program without the names: the parent of the PR that added them)."""
    trace = scopes.for_observation(obs, "serve")
    if trace is None:
        return None
    named = any(hybrid_scopes.scope_of(op, SSM + LATENT)
                for events in trace["devices"].values() for op in events)
    return trace if named else None


def unit_seconds_under(obs: dict, trace: dict, names):
    """(seconds a prefill unit spends under `names` outside the decode-tick
    program, units traced): self time on the first device plane over the
    number of `serve_prefill_enqueue` host events in the device window; None
    where no unit was traced."""
    window = scopes.window_of(trace)
    units = sum(1 for name, s, e in obs["xplane"]["host"]
                if name == PREFILL_EVENT and e > window[0] and s < window[1])
    if not units:
        return None
    events = trace["devices"][sorted(trace["devices"])[0]]
    in_tick = hybrid_scopes.tick_ops(events)
    wanted = {id(op) for i, op in enumerate(events)
              if i not in in_tick
              and hybrid_scopes.scope_of(op, names) is not None}
    hit = scopes.self_time_by(events, lambda op: id(op) in wanted, window)
    return 1e-9 * hit.get(True, 0.0) / units, units


def prefill_tokens(obs: dict):
    """Mean positions (the bucket) of the window's prefill units, None where
    the window saw none."""
    buckets = [s["bucket"] for s in obs.get("spans", ())
               if s["name"] == "serve_prefill"]
    return sum(buckets) / len(buckets) if buckets else None
