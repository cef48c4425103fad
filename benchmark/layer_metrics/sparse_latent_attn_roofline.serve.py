"""Share of the chip's roofline the sparse latent attention kernel reaches
(`ops/sparse_latent_attention.py`, instruction `sparse_latent_attn.<n>`):
over every call in the traced window, the least time its shapes allow
(benchmark/latent_work.py: each query's gathered entries read once, two
products a head; the queries of a call are the first size of its result, a
block of a chunk's or a tick's rows) over the time the calls took;
bytes-bound at 128 heads. None where the trace holds no such kernel."""

import re

from benchmark import kernel_work, latent_scopes, latent_work, peaks

LAYER = "latent attention layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"
KERNEL = "sparse_latent_attn"
_SHAPE = re.compile(r"\[(\d+),(\d+),(\d+)\]")


def read(obs: dict):
    trace = latent_scopes.latent_trace(obs)
    if trace is None:
        return None
    model = obs["cell"].model
    peak = peaks.peaks_for(obs["devices"][0].device_kind)
    least = taken = 0.0
    calls: dict = {}
    for events in trace["devices"].values():
        for op in events:
            shape = _SHAPE.search(op.result)
            if not shape or not (op.instruction == KERNEL
                                 or op.instruction.startswith(KERNEL + ".")):
                continue
            queries, heads, width = (int(g) for g in shape.groups())
            flops, hbm = latent_work.sparse_read_kernel_work(
                queries, heads, model["index_topk"], width)
            seconds = 1e-9 * (op.end_ns - op.start_ns)
            least += kernel_work.roofline_percent(flops, hbm, 1.0, peak)[0] / 100.0
            taken += seconds
            n, total = calls.get(queries, (0, 0.0))
            calls[queries] = (n + 1, total + seconds)
    if not taken:
        return None
    print("sparse_latent_attn_roofline.serve: " + "; ".join(
        f"{n} calls of {q} queries, {1e3 * total / n:.3f} ms a call"
        for q, (n, total) in sorted(calls.items())), flush=True)
    return 100.0 * least / taken
