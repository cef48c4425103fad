"""The operations and bytes a kernel call needs, from its shapes alone, and
the share of the chip's roofline a measured duration reaches.

Needed work, not executed work: a kernel that visits masked tiles, or keeps
its in-tile products in float32, is charged against the same count.
"""

from __future__ import annotations


def flash_fwd_work(seq: int, head_dim: int, heads: int, kv_heads: int,
                   rows: int, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one causal flash-attention forward call over
    `rows` sequences: QK^T and PV are each 2 * S * S * head_dim FLOPs a head
    over the full square, half of it under the causal mask. Bytes: q and the
    output once, k and v once a KV head, plus the float32 log-sum-exp row."""
    flops = 2 * seq * seq * head_dim * heads * rows
    hbm = rows * seq * (head_dim * dtype_bytes * 2 * (heads + kv_heads)
                        + 4 * heads)
    return flops, hbm


def roofline_percent(flops: float, hbm_bytes: float, seconds: float,
                     peaks: dict) -> tuple:
    """(percent of the roofline, which bound): the least time the chip could
    take, the larger of FLOPs over the bf16 peak and bytes over the HBM peak,
    over the time taken."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = hbm_bytes / peaks["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
