"""What the latent family's sparse read needs in a decode tick, from the
shapes run and the program's own counters alone (beside `kernel_work.py`,
whose `roofline_percent` turns these into a share).

Needed work, not executed work: the indexer is charged one read of the index
key of every position a decoding row can SEE (`index_visible`) and its
products against the row's query heads, the attention one read of the entry
of every position it SELECTED (`index_selected`) and the absorbed products
over it, whatever the program gathers, pads or sorts on the way. So the
share cannot pass 100%.
"""

from __future__ import annotations


def sparse_tick_work(index_visible: float, index_selected: float,
                     model: dict, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one tick's selection and sparse attention,
    counts summed over the tick's rows and full layers. Per visible
    position: its index key read once (`index_head_dim` numbers) and one
    product a query head of the indexer. Per selected position: its entry
    read once (`kv_lora_rank + qk_rope_head_dim` numbers), a score product
    over the whole entry and a weighted sum over the latent, a head."""
    width = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    key = model["index_head_dim"]
    flops = (index_visible * model["index_n_heads"] * key * 2
             + index_selected * model["num_attention_heads"]
             * (width + model["kv_lora_rank"]) * 2)
    hbm = (index_visible * key + index_selected * width) * dtype_bytes
    return flops, hbm


def sparse_read_kernel_work(queries: int, heads: int, places: int,
                            width: int, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of one call of the sparse latent attention kernel
    (`ops/sparse_latent_attention.py`) from its shapes alone: per query, its
    `places` gathered entries of `width` numbers read once, a score product
    and a weighted sum over them a head, the query and the output once, the
    float32 bias row."""
    flops = queries * heads * places * width * 2 * 2
    hbm = queries * ((places * width + 2 * heads * width) * dtype_bytes
                     + places * 4)
    return flops, hbm
