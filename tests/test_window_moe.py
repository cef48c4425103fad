"""The window / full softmax block against its plain reference
(benchmark/reference/window_moe_decoder.py) on the benchmark's seeded
weights, and its kernels in interpret mode against `ops/attention.attention`.
float32 on the CPU.

Tolerances, and where they come from. Program and reference are both
float32 and differ in the order of their sums alone (a kernel's running
softmax over key blocks against one softmax over the row; the grouped
product against a masked dense one): 1e-4 on logits of order 1 (readings
1e-6), 1e-5 on a kernel's outputs of order 1. The reference computed in
float8, the nearest precision below, is 0.3 away on the same logits: four
thousand times the tolerance (`test_the_float8_reference_is_far_outside`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_tiny
import window_tiny as tiny
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models.window_moe import decode
from llama_pipeline_parallel_tpu.models.window_moe import model as window
from llama_pipeline_parallel_tpu.ops import gqa_prefill_attention as gqa
from llama_pipeline_parallel_tpu.ops import paged_attention
from llama_pipeline_parallel_tpu.ops.attention import attention

TOL = 1e-4
KERNEL_TOL = dict(rtol=1e-5, atol=1e-5)
MINUS_INF = -1e30          # a sink that weighs nothing (the kernels' NEG_INF)


def _padded(prompt, bucket):
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    return ids, mask


def _prefill(prompt, bucket, params, cfg):
    ids, mask = _padded(prompt, bucket)
    return decode.prefill_prompt(params, jnp.asarray(ids), jnp.asarray(mask),
                                 cfg, bucket)


def _reference_last(prompt, precision="float32"):
    _, top, layer_fn = tiny.both_sides()
    return np.asarray(tiny.reference.logits_fn(
        top, layer_fn, jnp.asarray([prompt]), tiny.MODEL, precision)[0, -1])


# -- the programs against the reference -------------------------------------------

@pytest.mark.parametrize("tokens,bucket", [(3, 8), (8, 8), (13, 16), (16, 16),
                                           (29, 32)])
def test_a_whole_bucket_prefill_is_the_reference(tokens, bucket):
    """Left pads, a prompt shorter than the window, one that fills its
    bucket, one whose ring wraps three times."""
    cfg, params = tiny.config(), tiny.both_sides()[0]
    prompt = np.random.default_rng(tokens).integers(0, 128, tokens).tolist()
    out = _prefill(prompt, bucket, params, cfg)
    np.testing.assert_allclose(np.asarray(out["logits"][0]),
                               _reference_last(prompt), atol=TOL)
    seen = np.arange(1, tokens + 1)
    counted = np.asarray(out["counters"])
    assert counted[0] == tokens * 4 * 6          # top-4 in six expert layers
    assert counted[6] == 5 * np.minimum(seen, tiny.WINDOW).sum()
    assert counted[7] == 2 * seen.sum()


def test_the_float8_reference_is_far_outside():
    prompt = np.random.default_rng(13).integers(0, 128, 13).tolist()
    cfg, params = tiny.config(), tiny.both_sides()[0]
    got = np.asarray(_prefill(prompt, 16, params, cfg)["logits"][0])
    assert np.abs(got - _reference_last(prompt, "fp8")).max() > 1000 * TOL


def _chunked(prompt, bucket, chunk, params, cfg, slot=0, cache=None):
    """The prompt through `paged_prefill_chunk`, a chunk at a time, into
    `slot` of a cache: (last chunk's output, the cache)."""
    cache = cache or serve.PagedKVCache(cfg, 2, 48, tiny.PAGE, 24)
    ids, mask = _padded(prompt, bucket)
    positions = np.clip(np.cumsum(mask, axis=1) - 1, 0, None).astype(np.int32)
    cache.reset_mask_row(slot)
    for c0 in range(0, bucket, chunk):
        c1 = c0 + chunk
        cache.ensure_capacity(slot, c1)
        out = decode.paged_prefill_chunk(
            params, jnp.asarray(ids[:, c0:c1]), jnp.asarray(mask[:, c0:c1]),
            jnp.asarray(positions[:, c0:c1]), cache.pool,
            jnp.asarray(cache.page_table[slot]), jnp.int32(slot),
            cache.kv_mask, jnp.int32(c0), cfg)
        cache.pool, cache.kv_mask = out["pool"], out["kv_mask"]
    return out, cache


def _acquire(cache, name, bucket, new):
    demand = cache.demand_pages(bucket, new)
    assert cache.reserve(demand)
    return cache.acquire(name, demand)


def _slot_rows(cache, slot, bucket):
    """The slot's stores as a whole-bucket prefill lays them out."""
    cfg = cache.cfg
    pages = cache.page_table[slot, :bucket // tiny.PAGE]
    k = np.asarray(cache.pool["k"])[:, pages].reshape(
        cfg.full_layers, bucket, cfg.full_kv_heads, -1)
    v = np.asarray(cache.pool["v"])[:, pages].reshape(
        cfg.full_layers, bucket, cfg.full_kv_heads, -1)
    return {"k": k, "v": v,
            "ring_k": np.asarray(cache.pool["ring_k"])[:, slot],
            "ring_v": np.asarray(cache.pool["ring_v"])[:, slot]}


@pytest.mark.parametrize("tokens,chunk", [(27, 8), (32, 8), (19, 16), (9, 8)])
def test_a_chunked_prefill_is_the_whole_one(tokens, chunk):
    """Chunks of two and of four pages through the pages and the ring
    (which every chunk wraps) against one whole-bucket prefill: the same
    logits, the same pages, the same ring at every place that holds a
    token, the same counts. (9, 8): the first three chunks are pads."""
    cfg, params = tiny.config(), tiny.both_sides()[0]
    prompt = np.random.default_rng(tokens).integers(0, 128, tokens).tolist()
    whole = _prefill(prompt, 32, params, cfg)
    cache = serve.PagedKVCache(cfg, 2, 48, tiny.PAGE, 24)
    slot = _acquire(cache, "r", 32, 4)
    out, cache = _chunked(prompt, 32, chunk, params, cfg, slot, cache)
    np.testing.assert_allclose(np.asarray(out["logits"][0]),
                               np.asarray(whole["logits"][0]), atol=1e-5)
    got = _slot_rows(cache, slot, 32)
    pad = 32 - tokens
    for name in ("k", "v"):
        np.testing.assert_allclose(
            got[name][:, pad:], np.asarray(whole["cache"][name])[:, 0, pad:],
            atol=1e-5)
    held = sorted({p % tiny.WINDOW for p in range(max(pad, 32 - tiny.WINDOW), 32)})
    for name in ("ring_k", "ring_v"):
        np.testing.assert_allclose(
            got[name][:, held], np.asarray(whole["cache"][name])[:, 0][:, held],
            atol=1e-5)


def test_a_left_padded_prompt_leaves_the_stores_of_the_unpadded_one():
    """Eight tokens in a bucket of 8 and behind eight pads in a bucket of
    16 (the ring's places p % 8 coincide): the same logits, keys, values and
    ring. Rope takes the token's own position, and a pad is seen by no
    query."""
    cfg, params = tiny.config(), tiny.both_sides()[0]
    prompt = np.random.default_rng(2).integers(0, 128, 8).tolist()
    bare, padded = (_prefill(prompt, b, params, cfg) for b in (8, 16))
    np.testing.assert_allclose(np.asarray(padded["logits"]),
                               np.asarray(bare["logits"]), atol=1e-5)
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(padded["cache"][name])[:, :, 8:],
            np.asarray(bare["cache"][name]), atol=1e-5)
    for name in ("ring_k", "ring_v"):
        np.testing.assert_allclose(np.asarray(padded["cache"][name]),
                                   np.asarray(bare["cache"][name]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(padded["counters"]),
                                  np.asarray(bare["counters"]))


def test_a_chunk_of_nothing_but_pads_changes_no_visible_state():
    cfg, params = tiny.config(), tiny.both_sides()[0]
    cache = serve.PagedKVCache(cfg, 2, 48, tiny.PAGE, 24)
    slot = _acquire(cache, "r", 32, 4)
    cache.reset_mask_row(slot)
    cache.ensure_capacity(slot, 8)
    zeros = jnp.zeros((1, 8), jnp.int32)
    out = decode.paged_prefill_chunk(
        params, zeros, zeros, zeros, cache.pool,
        jnp.asarray(cache.page_table[slot]), jnp.int32(slot), cache.kv_mask,
        jnp.int32(0), cfg)
    assert not np.asarray(out["kv_mask"]).any()
    assert not np.asarray(out["counters"])[[0, 1, 6, 7]].any()


# -- the kernels in interpret mode --------------------------------------------------

def _qkv(seed, b, T, S, H, G, dk=24, dv=16):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return arr(b, T, H, dk), arr(b, S, G, dk), arr(b, S, G, dv)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 8 queries and 8 keys: several of each at the tests' sizes."""
    for name in ("BLOCK_Q", "BLOCK_K", "WINDOW_BLOCK_K"):
        monkeypatch.setattr(gqa, name, 8)


@pytest.mark.parametrize("q_start", [0, 8, 24])
@pytest.mark.parametrize("G", [2, 8])
def test_the_full_kernel_is_causal_attention_at_an_offset(small_blocks, G,
                                                          q_start):
    """A span of 16 queries at `q_start` of a row of 40 places with left
    pads, 8 heads over `G` KV heads, keys wider than values."""
    q, k, v = _qkv(0, 2, 16, 40, 8, G)
    valid = np.ones((2, 40), np.int32)
    valid[0, :5], valid[1, :11] = 0, 0
    got = gqa.full_prefill_attention(q, k, v, jnp.asarray(valid),
                                     jnp.int32(q_start))
    want = attention(q, k, v, jnp.asarray(valid), causal=True,
                     q_offset=q_start)
    # `attention` softens a query that sees nothing to a uniform softmax;
    # the kernel gives it zeros, and nothing downstream reads either
    sees = (np.cumsum(valid, axis=1)[:, q_start:q_start + 16] > 0)[..., None]
    np.testing.assert_allclose(np.asarray(got) * sees,
                               np.asarray(want).reshape(2, 16, -1) * sees,
                               **KERNEL_TOL)
    assert not np.asarray(got)[~sees[..., 0]].any()


@pytest.mark.parametrize("q_start", [0, 8])
def test_the_full_kernels_key_axis_ends_with_the_spans_last_place(
        small_blocks, q_start):
    """The span is handed its row's whole length (a chunk gets the slot's
    row of pages, whatever its offset): the key axis is a bound of the grid
    read at run time, and no block past the span's own last place is read:
    NaNs there reach no output."""
    q, k, v = _qkv(1, 1, 16, 40, 8, 2)
    valid = jnp.ones((1, 40), jnp.int32)
    end = q_start + 16
    poisoned = lambda a: a.at[:, end:].set(jnp.nan)
    run = jax.jit(gqa.full_prefill_attention)
    got = run(q, poisoned(k), poisoned(v), valid, jnp.int32(q_start))
    want = run(q, k[:, :end], v[:, :end], valid[:, :end], jnp.int32(q_start))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _banded(q, k, v, valid, before, window, sink):
    """Each query alone through `ops/attention.attention` over the keys of
    its own window, one head at a time; the sink takes its share of the
    softmax from the head's own scores."""
    b, T, H, dk = q.shape
    g = H // k.shape[2]
    out = np.zeros((b, T, H, v.shape[-1]), np.float32)
    for t in range(T):
        lo, hi = before + t - window + 1, before + t + 1
        keys, values, mask = k[:, lo:hi], v[:, lo:hi], valid[:, lo:hi]
        plain = np.asarray(attention(q[:, t:t + 1], keys, values,
                                     jnp.asarray(mask), causal=False))[:, 0]
        scores = np.einsum("bhd,bshd->bhs", np.asarray(q[:, t]),
                           np.repeat(np.asarray(keys), g, axis=2)) * dk ** -0.5
        scores = np.where(np.asarray(mask)[:, None, :] > 0, scores, -np.inf)
        total = np.exp(scores).sum(-1)                            # [b, H]
        share = np.divide(total, total + np.exp(sink), out=np.zeros_like(total),
                          where=total > 0)   # a query that sees nothing: 0
        out[:, t] = plain * share[..., None]
    return out.reshape(b, T, -1)


@pytest.mark.parametrize("sink", ["learned", "minus_infinity"])
@pytest.mark.parametrize("G", [2, 4])
def test_the_banded_kernel_is_attention_over_each_querys_window(
        small_blocks, G, sink):
    """24 queries behind a context of 8 places (what a chunk reads from the
    ring), a window of 8, left pads that reach into the span. A sink of
    minus infinity is the plain softmax."""
    q, k, v = _qkv(1, 2, 24, 32, 8, G)
    valid = np.ones((2, 32), np.int32)
    valid[0, :3], valid[1, :13] = 0, 0
    logits = (np.random.default_rng(3).normal(size=8) if sink == "learned"
              else np.full(8, MINUS_INF)).astype(np.float32)
    got = gqa.window_prefill_attention(q, k, v, jnp.asarray(valid),
                                       jnp.asarray(logits), 8)
    want = _banded(q, k, v, valid, 8, 8, logits.astype(np.float64))
    np.testing.assert_allclose(np.asarray(got), want, **KERNEL_TOL)


def test_the_banded_kernel_visits_only_the_bands_key_blocks(small_blocks):
    """The grid's key axis is two steps a query block whatever the span."""
    q, k, v = _qkv(2, 1, 64, 72, 8, 4)
    jaxpr = jax.make_jaxpr(lambda *a: gqa.window_prefill_attention(*a, 8))(
        q, k, v, jnp.ones((1, 72), jnp.int32), jnp.zeros((8,), jnp.float32))
    call = next(e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call")
    assert tuple(call.params["grid_mapping"].grid) == (1, 4, 8, 2)


@pytest.mark.parametrize("sink", [None, "learned", "minus_infinity"])
def test_the_widened_tick_kernel_takes_wider_keys_a_scale_and_a_sink(sink):
    """Keys of 128 (24 numbers padded, the scale of 24) beside values of 16
    over two live pages with holes, 8 heads over 2 KV heads: the gathered
    rows through `attention`, the sink sharing the softmax as above."""
    rng = np.random.default_rng(5)
    L, pages, page, G, H, dk, W, dv = 2, 6, 4, 2, 8, 24, 128, 16
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    k = window.stored_key(arr(L, pages, page, G, dk), W)
    v, q = arr(L, pages, page, G, dv), arr(2, H, dk)
    table = np.asarray([[3, 1, 5], [0, 4, 5]], np.int32)
    live = np.asarray([2, 2], np.int32)
    mask = np.zeros((2, 12), np.int32)
    mask[0, :7], mask[1, 2:8] = 1, 1
    mask[0, 3] = 0
    logits = None if sink is None else (
        rng.normal(size=H) if sink == "learned"
        else np.full(H, MINUS_INF)).astype(np.float32)
    got = paged_attention.paged_decode_attention(
        window.stored_key(q, W), k, v, jnp.int32(1), jnp.asarray(table),
        jnp.asarray(live), jnp.asarray(mask),
        None if logits is None else jnp.asarray(logits), dk ** -0.5)
    rows = lambda pool: jnp.stack(
        [pool[1, table[s]].reshape(12, G, -1) for s in range(2)])
    keys = rows(k)[..., :dk]
    want = np.asarray(attention(q[:, None], keys, rows(v), jnp.asarray(mask),
                                causal=False))[:, 0]
    if logits is not None and sink == "learned":
        scores = np.einsum("bhd,bshd->bhs", np.asarray(q),
                           np.repeat(np.asarray(keys), H // G, axis=2))
        scores = np.where(mask[:, None, :] > 0, scores * dk ** -0.5, -np.inf)
        total = np.exp(scores).sum(-1)
        want = want * (total / (total + np.exp(logits)))[..., None]
    np.testing.assert_allclose(np.asarray(got), want, **KERNEL_TOL)


# -- the expert layer -----------------------------------------------------------------

def test_the_sixteen_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """The deployment at a tiny width: 64 experts over sixteen chips of
    four, top-8, no scaling factor and NO shared expert: the sum of what
    `moe_block` computes for each share is the uncut reference's layer, with
    nothing counted twice and nothing beside the routed sum (guide
    section 4)."""
    model = {**tiny.MODEL, "n_routed_experts": 64, "router_experts": 64,
             "expert_offset": 0, "num_experts_per_tok": 8}
    layer = tiny.weights.make_layer(tiny.SEED, 2, model, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32), jnp.float32)
    dm = tiny.reference.dims(model)
    hidden = tiny.reference.rms_norm(x, layer["post_norm"], dm["eps"])
    want = tiny.reference.moe_layer(layer, hidden, dm, "float32")
    valid = jnp.ones(x.shape[:2], bool)
    total, here = jnp.zeros_like(x), 0
    for lo in range(0, 64, 4):
        cfg = tiny.config({**model, "n_routed_experts": 4,
                           "expert_offset": lo})
        share = {**layer, **{name: layer[name][lo:lo + 4]
                             for name in ("gate", "up", "down")}}
        out, counters = window.feed_forward(share, x, valid, 1, cfg, "mlp")
        total = total + (out - x)
        here += int(counters[1])
        assert int(counters[0]) == x.shape[0] * x.shape[1] * 8
    assert here == x.shape[0] * x.shape[1] * 8   # every assignment, once
    np.testing.assert_allclose(total, want, atol=TOL)


def test_the_selection_bias_moves_some_choices_and_no_weight():
    """The seeded bias changes which experts some tokens choose (so a
    program that left it out would fail the comparisons above) and enters no
    weight: the chosen scores still sum to one."""
    layer = tiny.weights.make_layer(tiny.SEED, 3, tiny.MODEL, jnp.float32)
    dm = tiny.reference.dims(tiny.MODEL)
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 32), jnp.float32)
    with_bias = tiny.reference.route(layer, h, dm)
    without = tiny.reference.route(
        {**layer, "router_bias": jnp.zeros_like(layer["router_bias"])}, h, dm)
    moved = np.asarray(((with_bias > 0) != (without > 0)).any(-1)).sum()
    assert 0 < moved < 64
    np.testing.assert_allclose(np.asarray(with_bias.sum(-1)), 1.0, atol=1e-6)


def test_the_expert_half_is_the_hybrid_blocks_with_nothing_beside_it():
    """`feed_forward` on an expert layer is `moe_block` without a shared
    expert, a stack of one layer at place 0."""
    cfg = tiny.config()
    layer = tiny.weights.make_layer(tiny.SEED, 1, tiny.MODEL, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 32), jnp.float32)
    valid = jnp.ones((2, 5), bool)
    got, counted = window.feed_forward(layer, x, valid, 1, cfg, "mlp")
    want, counters = hybrid_tiny.moe_block_alone(layer, x, valid, cfg,
                                                 shared=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(counted), np.asarray(counters))


# -- the sizes -------------------------------------------------------------------------

def test_the_published_sizes_give_the_published_parameter_count():
    """The equations at the catalog's sizes: 89.1M a full layer's attention,
    94.4M a window layer's, 25.17M an expert, 308.8B in all and 15.4B active
    (published as 309B-A15B)."""
    d, H, dk, dv, F, f, V = 4096, 64, 192, 128, 16384, 2048, 152576
    attn = lambda G: d + d * H * dk + d * G * (dk + dv) + H * dv * d
    full, win = attn(4), attn(8) + H
    expert, router = 3 * d * f, d * 256 + 256
    assert round(full / 1e6, 1) == 89.1 and round(win / 1e6, 1) == 94.4
    assert round(expert / 1e6, 2) == 25.17
    pattern = [0] + ([1] * 5 + [0]) * 7 + [1] * 4 + [0]
    assert len(pattern) == 48 and pattern.count(0) == 9
    mixers = sum(win if p else full for p in pattern) + 48 * d   # post norms
    total = (mixers + 3 * d * F + 47 * (router + 256 * expert)
             + 2 * V * d + d)
    active = total - 47 * (256 - 8) * expert
    assert round(total / 1e9, 1) == 308.8 and round(active / 1e9, 1) == 15.4


def test_the_checkpoints_tree_is_the_benchmarks_tree():
    """`model.init_params` (what a checkpoint of the family holds) and the
    benchmark's seeded weights have one layout: the same leaves, shapes and
    dtypes (sinks, router and its bias float32 whatever the tree's dtype)."""
    cfg = tiny.config(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    ours = jax.eval_shape(lambda: window.init_params(jax.random.PRNGKey(0), cfg))
    theirs = jax.eval_shape(lambda: tiny.weights.make_program_weights(
        tiny.SEED, tiny.MODEL, jnp.bfloat16))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    window_layer = ours["layers"][1]
    assert window_layer["sink"].dtype == jnp.float32
    assert window_layer["router_bias"].dtype == jnp.float32
    assert "sink" not in ours["layers"][0] and "mlp" in ours["layers"][0]
