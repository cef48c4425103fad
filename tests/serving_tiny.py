"""One tiny engine a served family (the latent and the state-space one in both
their kinds), from
the families' own fixtures: what `tests/test_prefill_in_flight.py` serves and
`tests/test_lowered_pins.py` lowers. float32 on the CPU."""

import jax

import eva_tiny
import granite_tiny
import hybrid_tiny
import latent_tiny
import mla_tiny
import ssm_tiny
import window_tiny
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig

FAMILIES = ("llama", "hybrid_moe", "latent_moe.dots3", "latent_moe.a.x-k1",
            "eva", "ssm_moe", "ssm_moe.dense", "window_moe")
SLOTS = 3
_ROWS = dict(max_len=48, prompt_buckets=(8, 16), page_size=8, num_pages=32)
_LATENT = dict(max_len=64, prompt_buckets=(8, 16, 32), page_size=4,
               num_pages=64, prefill_chunk_tokens=8)
_WINDOW = dict(max_len=64, prompt_buckets=(8, 16, 32),
               page_size=window_tiny.PAGE, num_pages=64,
               prefill_chunk_tokens=8)
_CHUNKED = dict(max_len=64, prompt_buckets=(8, 16, 32), page_size=8,
                num_pages=48, prefill_chunk_tokens=8)
_TINY = {"hybrid_moe": (hybrid_tiny, _ROWS), "ssm_moe": (ssm_tiny, _ROWS),
         "ssm_moe.dense": (granite_tiny, _CHUNKED),
         "window_moe": (window_tiny, _WINDOW),
         "latent_moe.dots3": (latent_tiny, _LATENT),
         "latent_moe.a.x-k1": (mla_tiny, _LATENT)}


def build(family: str) -> tuple:
    """(configuration, parameters, the engine's shape) of `family`."""
    if family == "llama":
        cfg = LlamaConfig.tiny()
        return cfg, llama.init_params(jax.random.PRNGKey(0), cfg), dict(
            max_len=32, prompt_buckets=(8, 16), page_size=8, num_pages=32)
    if family == "eva":
        cfg = eva_tiny.tiny_config()
        return cfg, eva_tiny.tiny_params(cfg), dict(
            max_len=96, prompt_buckets=(16, 32, 64), page_size=eva_tiny.PAGE,
            num_pages=40, prefill_chunk_tokens=32)
    tiny, shape = _TINY[family]
    return tiny.config(), tiny.both_sides()[0], dict(shape)


def engine(family: str, **knobs):
    cfg, params, shape = build(family)
    return serve.ServeEngine(params, cfg, serve.ServeConfig(
        max_slots=SLOTS, max_queue=16, **{**shape, **knobs}))
