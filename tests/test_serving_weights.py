"""The engine holds the weights its programs use in the dtype they use them
in (serve/engine.py `_serving_weights`, models/llama/decode.py
`serving_weights`): the leaves every dense serving program converts to
`cfg.dtype` at the point of use are converted once, when the engine is built.

Served tokens must be bit for bit what the float32 tree serves (`astype` of
the same value gives the same value whenever it runs), and the programs given
the held tree must hold no conversion of a weight: nothing but a device trace
would notice the 6.6 GB of float32 converted every tick coming back (PERF.md,
PR 27), so the lowered text is held to it here. The model computes in
bfloat16 from float32 weights, as the serving cell does; `LlamaConfig.tiny()`
alone computes in float32 and has nothing to convert.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_tiny
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models.llama import decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.serve import (
    ServeConfig,
    ServeEngine,
    ServeRequest,
)
from llama_pipeline_parallel_tpu.utils import trace

BUCKET, PAGE, SLOTS, PAGES_PER_SLOT, NUM_PAGES = 8, 4, 2, 4, 16
MAX_LEN = PAGES_PER_SLOT * PAGE
NORMS = (("layers", "input_norm"), ("layers", "post_norm"), ("norm",))


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _paths(tree) -> dict:
    return {tuple(k.key for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _engine(cfg, params) -> ServeEngine:
    return ServeEngine(params, cfg, ServeConfig(
        max_slots=SLOTS, max_len=MAX_LEN, prompt_buckets=(BUCKET,),
        max_queue=8, metrics_every=1, decode_span_every=1, page_size=PAGE,
        num_pages=NUM_PAGES))


def _built(cfg, params, make=None):
    """(engine, its `serve_weights_cast` spans): built under a listener."""
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        engine = make() if make else _engine(cfg, params)
    finally:
        trace.recorder().remove_listener(listener)
    return engine, [s for s in spans if s["name"] == "serve_weights_cast"]


# -- (a) the served tokens -----------------------------------------------------


def test_served_tokens_are_those_of_the_float32_tree(setup):
    """Greedy and sampled requests, staggered over two slots: the engine
    that holds converted weights serves what `generate()` computes from the
    float32 tree, and what the same engine serves when every program is
    handed the float32 tree itself (the engine before PR 27)."""
    cfg, params = setup
    rs = np.random.RandomState(7)
    gens = [decode.GenerationConfig(max_new_tokens=6),
            decode.GenerationConfig(max_new_tokens=5, temperature=0.9,
                                    top_k=6),
            decode.GenerationConfig(max_new_tokens=4, temperature=1.1,
                                    top_p=0.8)]
    prompts = [rs.randint(3, cfg.vocab_size, (n,)).tolist() for n in (5, 8, 3)]

    def serve(engine):
        handles = [engine.submit(ServeRequest(input_ids=p, gen=g, seed=i))
                   for i, (p, g) in enumerate(zip(prompts, gens))]
        engine.drain(timeout_s=120)
        return [h.result(timeout=1) for h in handles]

    held = _engine(cfg, params)
    assert held.params["lm_head"].dtype == jnp.bfloat16
    given = _engine(cfg, params)
    given.params = params           # every program converts at each use again
    served = serve(held)
    assert served == serve(given)
    for i, (prompt, gen) in enumerate(zip(prompts, gens)):
        pad = BUCKET - len(prompt)
        ids = np.asarray([[0] * pad + prompt], np.int32)
        mask = np.asarray([[0] * pad + [1] * len(prompt)], np.int32)
        want = decode.generate(params, jnp.asarray(ids), jnp.asarray(mask),
                               cfg, gen, rng=jax.random.PRNGKey(i))
        assert served[i] == np.asarray(want["tokens"])[0].tolist()


# -- (b) the programs' text ----------------------------------------------------


def _program_args(program: str, cfg, params):
    z = jnp.zeros((SLOTS,), jnp.int32)
    kv_mask = jnp.zeros((SLOTS, MAX_LEN), jnp.int32)
    knobs = (jnp.zeros((SLOTS, 2), jnp.uint32),
             jnp.zeros((SLOTS,), jnp.float32), z,
             jnp.ones((SLOTS,), jnp.float32))
    if program == "prefill_prompt":
        ids = jnp.ones((1, BUCKET), jnp.int32)
        return (params, ids, ids, cfg, MAX_LEN)
    pool = decode.init_page_pool(cfg, NUM_PAGES, PAGE, "fp")
    if program == "paged_decode_step":
        return (params, z, pool,
                jnp.zeros((SLOTS, PAGES_PER_SLOT), jnp.int32), z, z, kv_mask,
                z, *knobs, cfg)
    # a whole number of pages for the chunk, neither start nor length a page
    # multiple for the span
    n, start = (8, 0) if program == "paged_prefill_chunk" else (5, 2)
    ids = jnp.ones((1, n), jnp.int32)
    return (params, ids, ids, jnp.broadcast_to(jnp.arange(n), (1, n)), pool,
            jnp.arange(PAGES_PER_SLOT, dtype=jnp.int32), jnp.int32(0),
            kv_mask, jnp.int32(start), cfg)


def _weight_converts(text: str, params) -> list:
    """`stablehlo.convert` lines of float32 operands with the shape of a leaf
    the programs convert, whole or one layer of it (the layer loop's slice)."""
    leaves, shapes = _paths(params), set()
    for path in decode._CAST_AT_USE:
        shape = leaves[path].shape
        shapes |= {shape, shape[1:]} if path[0] == "layers" else {shape}
    operands = {"x".join(map(str, s)) + "xf32" for s in shapes}
    found = re.findall(r"stablehlo\.convert [^\n]*: \(tensor<([0-9x]+xf32)>\)",
                       text)
    return [t for t in found if t in operands]


@pytest.mark.parametrize("program", [
    "paged_decode_step", "prefill_prompt", "paged_prefill_chunk",
    "paged_prefill_span"])
def test_no_serving_program_converts_a_weight_it_is_given(setup, program):
    cfg, params = setup
    held = _engine(cfg, params).params
    fn = getattr(decode, program)

    def text(tree):
        return fn.lower(*_program_args(program, cfg, tree)).as_text(
            debug_info=True)

    # on the float32 tree the test sees them: nine leaves, under the scope
    before = text(params)
    assert trace.SCOPE_CAST_WEIGHTS in before
    assert len(_weight_converts(before, params)) == 9, program
    after = text(held)
    assert trace.SCOPE_CAST_WEIGHTS not in after
    assert _weight_converts(after, params) == []


# -- (c) the tree --------------------------------------------------------------


def test_the_held_tree_is_the_given_one_with_nine_leaves_converted(setup):
    cfg, params = setup
    held = decode.serving_weights(params, cfg)
    assert jax.tree.structure(held) == jax.tree.structure(params)
    given_leaves, held_leaves = _paths(params), _paths(held)
    assert len(decode._CAST_AT_USE) == 9
    assert set(decode._CAST_AT_USE) | set(NORMS) == set(given_leaves)
    for path in decode._CAST_AT_USE:
        assert held_leaves[path].dtype == cfg.dtype, path
        assert held_leaves[path].shape == given_leaves[path].shape
        np.testing.assert_array_equal(
            np.asarray(held_leaves[path]),
            np.asarray(given_leaves[path].astype(cfg.dtype)))
    for path in NORMS:      # used in float32 (ops/rmsnorm.py): the caller's own
        assert held_leaves[path] is given_leaves[path], path
        assert held_leaves[path].dtype == jnp.float32
    # the caller's arrays are neither donated nor deleted
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(params))


def test_a_tree_in_the_compute_dtype_comes_back_as_it_is(setup):
    cfg, params = setup
    held = decode.serving_weights(params, cfg)
    again = decode.serving_weights(held, cfg)
    assert again is held
    # one leaf left to convert: the others are passed through, not copied;
    # a leaf that is no float (quantised, say) is left alone
    mixed = {**held, "lm_head": params["lm_head"],
             "embed": {"embedding": jnp.zeros((4, 4), jnp.int8)}}
    out = _paths(decode.serving_weights(mixed, cfg))
    assert out[("lm_head",)].dtype == cfg.dtype
    assert out[("embed", "embedding")] is mixed["embed"]["embedding"]
    assert out[("layers", "attn", "wq")] is held["layers"]["attn"]["wq"]
    # float32 compute: nothing to convert at all
    cfg32 = LlamaConfig.tiny()
    assert decode.serving_weights(params, cfg32) is params


# -- (d), (e) the engine, the other family, the span -----------------------------


def test_the_hybrid_engine_holds_the_callers_own_tree():
    cfg = hybrid_tiny.config()
    assert families.family_of(cfg).serving_weights is None
    params = hybrid_tiny.both_sides()[0]
    scfg = ServeConfig(max_slots=2, max_len=48, prompt_buckets=(8, 16),
                       page_size=8, num_pages=12)
    engine, spans = _built(cfg, params,
                           make=lambda: ServeEngine(params, cfg, scfg))
    assert engine.params is params
    n, size = (len(jax.tree.leaves(params)),
               sum(x.nbytes for x in jax.tree.leaves(params)))
    assert [(s["leaves_cast"], s["leaves_kept"], s["bytes_given"],
             s["bytes_held"]) for s in spans] == [(0, n, size, size)]


def test_the_dense_engine_records_what_it_converted(setup):
    cfg, params = setup
    assert families.family_of(cfg).serving_weights is decode.serving_weights
    engine, (span,) = _built(cfg, params)
    assert engine.params is not params
    assert (span["leaves_cast"], span["leaves_kept"]) == (9, 3)
    leaves = _paths(params)
    cast = sum(leaves[p].nbytes for p in decode._CAST_AT_USE)
    kept = sum(leaves[p].nbytes for p in NORMS)
    assert span["bytes_given"] == cast + kept
    assert span["bytes_held"] == cast // 2 + kept
    assert span["dur"] > 0 and span["depth"] == 0
    # an engine built from the held tree has nothing left to convert
    second, (again,) = _built(cfg, engine.params)
    assert second.params is engine.params
    assert (again["leaves_cast"], again["leaves_kept"]) == (0, 12)
    assert again["bytes_given"] == again["bytes_held"] == span["bytes_held"]
