"""What the serving stack asks of a block family: the programs that run its
layers over the page pool, the stores they keep, and what it cannot do yet.
What touches only the mask or the pool's page axis is `serve/pages.py`'s own.

`serve/engine.py` and `serve/pages.py` take these from `family_of(cfg)` and
call them with the arguments they always passed; they name no family's
functions. The dense decoder (`models/llama/`) is one family, the hybrid
block with recurrent layers and sparse experts (`models/hybrid_moe/`) another,
the latent-attention block (MLA layers, with or without an indexer and
window layers, as its configuration says) and sparse experts
(`models/latent_moe/`) the third, the compressed-window block (an exact
window beside pooled chunk summaries, `models/eva/`) the fourth, the
state-space block (every letter of a published order ONE of a Mamba-2 mixer,
a grouped-query softmax layer, a latent expert feed-forward or a dense gated
feed-forward: the expert block, and the dense block whose layer is a mixer
AND a feed-forward half under four scalar multipliers and a tied head,
`models/ssm_moe/`) the fifth, the window / full softmax block
(grouped-query layers of two kinds in a published order, each kind with its
own KV heads, rotary base and mask, the window kind with a learned sink in
its softmax; keys wider than values; sigmoid-routed experts and nothing
beside the routed sum, `models/window_moe/`) the sixth: six families, one of
them (`ssm_moe`) serving two published shapes. A seventh registers its
configuration class below.

One family DRAFTS where its configuration says so (`drafts`: a latent model
published with a multi-token-prediction module, `num_nextn_predict_layers`
1): its tick runs the trunk on two queries a row, the row's last token and
the module's draft for the next, emits one or two tokens, and the module
leaves the next draft (`models/latent_moe/draft.py`); the emitted stream is
that of one-token ticks. The other five families, and a latent model without
a module, emit one token a row a tick.

A family also states what a slot's PAGES are (`table_width`,
`table_columns`): how wide a slot's row of the page table is and which of its
columns hold pages once so many places of the row are written. Five families
keep one entry a position for the life of the request (a page every
`page_size` places, in order: the defaults below); the compressed-window
family keeps a ring of window pages that is reused and summary pages that grow
at a sixteenth of the rate. `serve/pages.py` reserves, grows and sizes from
these two and names no family.

A family may keep a store with one row a SLOT beside the page pool
(`init_recurrent_store`: the hybrid and the state-space block's recurrent
state and convolution inputs, the latent block's rings of its window
layers, the window block's rings of keys and values, whose shape is another
than its pages': 8 KV heads a ring place beside 4 a page row): what a layer
keeps of a sequence there is of constant size, so it is a row a slot and not
pages. What a family states may depend on the
configuration: a latent model without window layers keeps no such store.
Whether it can prefill in chunks is a separate fact (`paged_prefill_chunk`):
the latent and the window block's chunks carry their rings forward from
chunk to chunk, and of the two recurrent families the state-space block's
chunk carries the slot's state and convolution inputs forward (a row whose
mask holds no token before the chunk starts from zeros, whatever the last
occupant left); the hybrid block's prefill still takes neither from the
chunk before it, so it does not chunk yet.

`GenerationConfig`, `sample_rowwise` and `sampler_branch` are the same for
every family (the sampling of a row of logits, and what a batch's knobs ask of
it) and are re-exported here.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Callable

import numpy as np

from llama_pipeline_parallel_tpu.models import tick_io
from llama_pipeline_parallel_tpu.models.llama.decode import (  # noqa: F401
    GenerationConfig,
    sample_rowwise,
    sampler_branch,
)


class UnsupportedForFamily(ValueError):
    """A serving feature this family's layers cannot run yet, named."""


def row_table_width(cfg, max_len: int, page_size: int) -> int:
    """A slot's row of the page table where every place of the logical row
    keeps its entry: a page every `page_size` places of `max_len`."""
    return max_len // page_size


def row_table_columns(cfg, tokens: int, max_len: int,
                      page_size: int) -> np.ndarray:
    """The columns that hold pages once `tokens` places are written, for
    such a row: the leading `ceil(tokens / page_size)`."""
    return np.arange(-(-tokens // page_size))


@dataclasses.dataclass(frozen=True)
class ServingFamily:
    name: str
    # the three programs of the normal path (the tick with its thirteen
    # arguments: tests and the benchmark's checks call it so; an engine runs
    # `decode_tick`), and the splice of a prefilled row into the paged stores
    prefill_prompt: Callable
    paged_decode_step: Callable
    write_pages: Callable
    init_page_pool: Callable            # (cfg, num_pages, page_size, quant)
    # (cfg, max_slots) -> the leaves of the family's per-slot store (a
    # recurrent state, a ring of the last positions), carried in the same
    # donated tree as the page pool; None: the family keeps no such state
    init_recurrent_store: Callable | None = None
    # (rng, cfg) -> the parameter tree as a checkpoint holds it, for a family
    # whose tree is saved as it stands; None: the dense decoder, whose
    # loader undoes the trainer's pipeline stacking
    init_params: Callable | None = None
    # (params, cfg) -> the same tree with the leaves this family's programs
    # convert at every use already converted, made once when an engine is
    # built; None: the tree is served in the dtype it is stored in
    serving_weights: Callable | None = None
    # chunked prefill, and the span prefill that recomputes the tail of a
    # prefix-cache hit: None where the family's layers cannot run them
    paged_prefill_chunk: Callable | None = None
    paged_prefill_span: Callable | None = None
    kv_quants: tuple = ("fp",)
    # names of the int32 counters a tick returns under "counters", in order
    counters: tuple = ()
    # what a slot's pages are. (cfg, max_len, page_size) -> entries of a
    # slot's row of the page table; (cfg, tokens, max_len, page_size) -> the
    # ascending columns of that row that hold pages once `tokens` places of
    # the slot's logical row are written (a superset for more tokens: a
    # request's worst-case demand is their count at its last write)
    table_width: Callable = row_table_width
    table_columns: Callable = row_table_columns
    # why a shared prefix page cannot serve this family, where the reason is
    # not the recurrent store's or a missing program's
    prefix_cache_why: str = ""
    # whether the model DRAFTS: its tick verifies a drafted token beside the
    # row's own and emits one or two tokens a row, the same stream as
    # one-token ticks (the configuration says so, no option does). Then the
    # tick's vector is `tick_io`'s wider one, a chunk takes the id that
    # follows it (`next_id`), and a row may write one place past its budget
    drafts: bool = False
    # a drafting family: (params, the last unit's "hidden", the vector
    # `first_token` wrote the row's token into, pool, the slot's table row,
    # slot, kv_mask, rope position and place of the prompt's last token, cfg)
    # -> the pool with the row's first draft
    first_draft: Callable | None = None

    @property
    def recurrent(self) -> bool:
        return self.init_recurrent_store is not None

    @property
    def decode_tick(self) -> Callable:
        """The tick as an engine runs it: `paged_decode_step`'s body behind
        one staged buffer in and one fetched vector out, a row's token and
        key fed back from the tick before on the device
        (`models/tick_io.py`), the same jitted program for every engine of
        the family."""
        if self.drafts:
            return tick_io.packed_drafting(self.paged_decode_step)
        return tick_io.packed(self.paged_decode_step)

    @property
    def fetch_rows(self) -> int:
        """Parts of `slots` int32 in front of the counters of the tick's
        fetched vector."""
        return tick_io.fetch_rows(self.drafts)

    def check_serve_config(self, kv_quant: str, prefill_chunk_tokens: int,
                           prefix_cache: bool) -> None:
        """Refuse, by name, what this family cannot run."""
        why = (f"the {self.name} family keeps, for this configuration, a "
               f"store with one row a slot (a recurrent state, or a ring of "
               f"the last positions) beside the page pool" if self.recurrent
               else f"the {self.name} family")
        refused = []
        if kv_quant not in self.kv_quants:
            refused.append(f"kv_quant: {kv_quant} (pages are {self.kv_quants} "
                           f"only)")
        if prefill_chunk_tokens and self.paged_prefill_chunk is None:
            refused.append("prefill_chunk_tokens > 0 (its programs cannot "
                           "carry the slot's row from chunk to chunk yet: "
                           "of the six families' recurrent ones ssm_moe "
                           "does, hybrid_moe does not)")
        if prefix_cache and self.paged_prefill_span is None:
            refused.append(
                "prefix_cache (a shared page holds what its layers page "
                "only: the slot's row at the divergence point is not kept, "
                "and the span prefill that recomputes a tail cannot start "
                "from it)" if self.recurrent else
                f"prefix_cache ({self.prefix_cache_why})"
                if self.prefix_cache_why else
                "prefix_cache (the family has no span prefill, the program "
                "that recomputes the tail of a prefix-cache hit)")
        if refused:
            raise UnsupportedForFamily(
                f"{why}: cannot run yet: " + "; ".join(refused))


def _llama(cfg) -> ServingFamily:
    from llama_pipeline_parallel_tpu.models.llama import decode

    return ServingFamily(
        name="llama", prefill_prompt=decode.prefill_prompt,
        paged_decode_step=decode.paged_decode_step,
        write_pages=decode.write_pages, init_page_pool=decode.init_page_pool,
        serving_weights=decode.serving_weights,
        paged_prefill_chunk=decode.paged_prefill_chunk,
        paged_prefill_span=decode.paged_prefill_span,
        kv_quants=("fp", "int8"))


def _hybrid_moe(cfg) -> ServingFamily:
    from llama_pipeline_parallel_tpu.models.hybrid_moe import decode, model

    return ServingFamily(
        name="hybrid_moe", prefill_prompt=decode.prefill_prompt,
        paged_decode_step=decode.paged_decode_step,
        write_pages=decode.write_pages, init_page_pool=decode.init_page_pool,
        init_recurrent_store=decode.init_recurrent_store,
        init_params=model.init_params, counters=decode.COUNTERS)


def _latent_moe(cfg) -> ServingFamily:
    from llama_pipeline_parallel_tpu.models.latent_moe import (
        decode,
        draft,
        model,
    )

    return ServingFamily(
        name="latent_moe", prefill_prompt=decode.prefill_prompt,
        paged_decode_step=decode.paged_decode_step,
        write_pages=decode.write_pages, init_page_pool=decode.init_page_pool,
        # a ring a slot only where the model has window layers; the draft's
        # row where it drafts
        init_recurrent_store=(decode.init_recurrent_store
                              if cfg.window_layers or cfg.drafts else None),
        init_params=model.init_params,
        paged_prefill_chunk=decode.paged_prefill_chunk,
        counters=decode.counters(cfg), drafts=cfg.drafts,
        first_draft=draft.first_draft if cfg.drafts else None)


def _eva(cfg) -> ServingFamily:
    from llama_pipeline_parallel_tpu.models.eva import decode, model

    return ServingFamily(
        name="eva", prefill_prompt=decode.prefill_prompt,
        paged_decode_step=decode.paged_decode_step,
        write_pages=decode.write_pages, init_page_pool=decode.init_page_pool,
        init_params=model.init_params,
        paged_prefill_chunk=decode.paged_prefill_chunk,
        counters=decode.COUNTERS, table_width=decode.table_width,
        table_columns=decode.table_columns,
        prefix_cache_why=(
            "a slot's window pages are a ring that is overwritten window "
            "after window: a shared page would need the ring as it stood at "
            "the divergence point, which is not kept, and the family has no "
            "span prefill to recompute a tail from it"))


def _window_moe(cfg) -> ServingFamily:
    from llama_pipeline_parallel_tpu.models.window_moe import decode, model

    return ServingFamily(
        name="window_moe", prefill_prompt=decode.prefill_prompt,
        paged_decode_step=decode.paged_decode_step,
        write_pages=decode.write_pages, init_page_pool=decode.init_page_pool,
        init_recurrent_store=decode.init_recurrent_store,
        init_params=model.init_params,
        paged_prefill_chunk=decode.paged_prefill_chunk,
        counters=decode.COUNTERS)


def _ssm_moe(cfg) -> ServingFamily:
    from llama_pipeline_parallel_tpu.models.ssm_moe import decode, model

    return ServingFamily(
        name="ssm_moe", prefill_prompt=decode.prefill_prompt,
        paged_decode_step=decode.paged_decode_step,
        # narrow KV heads packed a page row: the pages are matrices
        write_pages=(decode.write_pages if cfg.kv_pack == 1
                     else decode.write_packed_pages),
        init_page_pool=decode.init_page_pool,
        init_recurrent_store=decode.init_recurrent_store,
        init_params=model.init_params,
        paged_prefill_chunk=decode.paged_prefill_chunk,
        counters=decode.COUNTERS)


_FAMILIES = {"llama": _llama, "hybrid_moe": _hybrid_moe,
             "latent_moe": _latent_moe, "eva": _eva, "ssm_moe": _ssm_moe,
             "window_moe": _window_moe}


def family_of(cfg) -> ServingFamily:
    """The family of a configuration object, by its `family` attribute, as
    it stands for THIS configuration (its stores, its counters)."""
    if cfg.family not in _FAMILIES:
        raise KeyError(f"no serving family {cfg.family!r}; known: "
                       f"{sorted(_FAMILIES)}")
    return _FAMILIES[cfg.family](cfg)


# families served in the dtype they are stored in: configuration class by
# package under `models/`
_STORED_DTYPE_CONFIGS = {"hybrid_moe": "HybridMoEConfig",
                         "latent_moe": "LatentMoEConfig", "eva": "EvaConfig",
                         "ssm_moe": "SsmMoEConfig",
                         "window_moe": "WindowMoEConfig"}


def config_from_meta(model_config: dict):
    """The configuration object a checkpoint's `meta.json` describes:
    `model_config["family"]` names its family (absent: the dense decoder).
    The hybrid, the latent and the compressed-window family keep the saved
    dtypes (they are served in the dtype they are stored in); the dense one
    drops them, as its loader always has."""
    mc = dict(model_config)
    name = mc.pop("family", "llama")
    if name == "llama":
        from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig

        mc.pop("dtype", None), mc.pop("param_dtype", None)
        return LlamaConfig(**mc)
    if name in _STORED_DTYPE_CONFIGS:
        import jax.numpy as jnp

        config_class = getattr(importlib.import_module(
            f"llama_pipeline_parallel_tpu.models.{name}.config"),
            _STORED_DTYPE_CONFIGS[name])
        for key in ("dtype", "param_dtype"):
            if key in mc:
                mc[key] = jnp.dtype(mc[key]).type
        return config_class(**mc)
    raise KeyError(f"meta.json names family {name!r}; known: "
                   f"{sorted(_FAMILIES)}")
