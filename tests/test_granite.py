"""The dense state-space block (`models/ssm_moe/` with a dense gated half a
layer, four multipliers, a tied head, narrow KV heads packed a page row)
against its plain reference (`benchmark/reference/granite_hybrid_decoder.py`,
which imports nothing of the program), and the shapes its kernels had not
met. float32 on the CPU; logits are compared at 1e-4 (both sides float32;
they differ in the order of sums: the chunked scan against the token-by-token
recurrence, a softmax summed in tiles)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import granite_tiny as tiny
import ssm_tiny
from llama_pipeline_parallel_tpu.models.ssm_moe import decode as ssm_decode
from llama_pipeline_parallel_tpu.models.ssm_moe import model as ssm
from llama_pipeline_parallel_tpu.models.ssm_moe.config import SsmMoEConfig
from llama_pipeline_parallel_tpu.ops import ssm_state_step as step_kernel
from llama_pipeline_parallel_tpu.ops.paged_attention import (
    paged_decode_attention,
)

TOL = 1e-4


def _padded(prompt, bucket):
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    return jnp.asarray(ids), jnp.asarray(mask)


def _prefill_logits(params, cfg, prompt, bucket):
    ids, mask = _padded(prompt, bucket)
    return np.asarray(ssm_decode.prefill_prompt(params, ids, mask, cfg,
                                                bucket)["logits"][0])


@pytest.fixture(scope="module")
def sound():
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    prompt = np.random.default_rng(3).integers(0, 128, 21).tolist()
    want = np.asarray(tiny.reference.logits_fn(
        top, layer_fn, jnp.asarray([prompt], jnp.int32), tiny.MODEL)[0, -1])
    return cfg, params, prompt, want


# -- the configuration ------------------------------------------------------------

def test_the_configuration_reads_the_published_keys_of_a_layer_of_two_halves():
    cfg = tiny.config()
    assert cfg.pattern == "M-M-*-M-M-" and cfg.num_hidden_layers == 5
    assert cfg.recurrent_layers == 4 and cfg.kv_cache_layers == 1
    assert cfg.expert_layers == 0
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (8, 64, 16, 1, 4, 8)
    # two heads of 64 fill a 128-lane row: the packing follows from the head
    assert cfg.head_dim == 256 // 4 and cfg.kv_pack == 2
    assert cfg.dense_intermediate_size == 128
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (3, 0.6, 0.25, 2)
    assert cfg.attn_scale == 0.25 and cfg.tie_word_embeddings
    assert cfg.family == "ssm_moe"
    # the expert block's configuration states none of this and gets the
    # neutral values
    plain = ssm_tiny.config()
    assert (plain.embedding_multiplier, plain.residual_multiplier,
            plain.attention_multiplier, plain.logits_scaling,
            plain.tie_word_embeddings, plain.kv_pack) == (
        1.0, 1.0, None, 1.0, False, 1)
    assert plain.attn_scale == plain.head_dim ** -0.5
    assert plain.num_hidden_layers == len(plain.pattern)


def test_the_tiny_variant_of_the_package_is_the_same_shape():
    assert SsmMoEConfig.tiny(dense=True) == tiny.config()
    assert SsmMoEConfig.tiny().pattern == "MEM*EME"


@pytest.mark.parametrize("change,named", [
    ({"layer_types": ["mamba"] * 4}, "layer_types"),
    ({"layer_types": ["mamba", "mamba", "window", "mamba", "mamba"]},
     "layer_types"),
    ({"num_local_experts": 8}, "no experts"),
    ({"position_embedding_type": "rope"}, "nope"),
    ({"hidden_act": "gelu"}, "SiLU"),
    ({"mamba_conv_bias": False}, "bias"),
    ({"mamba_n_heads": 4}, "mamba_expand"),
])
def test_a_published_configuration_of_another_shape_is_refused_by_name(
        change, named):
    with pytest.raises(ValueError, match=named):
        tiny.config({**tiny.MODEL, **change})


def test_a_dense_half_stands_behind_a_mixer():
    base = dataclasses.asdict(tiny.config())
    for pattern in ("-M-", "M--", "ME-"):
        with pytest.raises(ValueError, match="dense half"):
            SsmMoEConfig(**{**base, "pattern": pattern})
    with pytest.raises(ValueError, match="dense_intermediate_size"):
        SsmMoEConfig(**{**base, "dense_intermediate_size": 0})


def test_init_params_draws_the_tree_the_benchmark_weights_have():
    cfg = tiny.config()
    shapes = lambda tree: jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    mine = jax.eval_shape(lambda: ssm.init_params(jax.random.PRNGKey(0), cfg))
    theirs = jax.eval_shape(lambda: tiny.both_sides()[0])
    assert shapes(mine) == shapes(theirs)
    assert "lm_head" not in mine                      # tied to the table
    assert len(mine["layers"]) == 10
    assert sorted(mine["layers"][1]) == ["mlp", "post_norm"]


# -- against the reference --------------------------------------------------------

@pytest.mark.parametrize("bucket", [24, 32, 40])
def test_prefill_is_the_reference_whatever_the_left_padding(sound, bucket):
    cfg, params, prompt, want = sound
    np.testing.assert_allclose(_prefill_logits(params, cfg, prompt, bucket),
                               want, atol=TOL)


def test_a_whole_bucket_runs_its_softmax_layers_blocked_over_keys(sound):
    """A whole bucket's attention is the chunk's kernel
    (`ops/gqa_prefill_attention.py`), which never forms a bucket's scores,
    whatever the bucket's size."""
    cfg, params, prompt, want = sound
    ids = jax.ShapeDtypeStruct((1, 56), jnp.int32)
    assert "full_chunk_attn" in ssm_decode.prefill_prompt.lower(
        params, ids, ids, cfg, 56).as_text(debug_info=True)
    np.testing.assert_allclose(_prefill_logits(params, cfg, prompt, 56), want,
                               atol=TOL)


@pytest.mark.parametrize("field,neutral", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", None), ("logits_scaling", 1.0)])
def test_each_multiplier_set_to_one_in_the_program_alone_is_seen(
        sound, field, neutral):
    """The comparison that passes with the published multipliers fails, by
    two hundred times its tolerance or more, with any ONE of them at its
    neutral value on the program's side."""
    cfg, params, prompt, want = sound
    got = _prefill_logits(params, dataclasses.replace(cfg, **{field: neutral}),
                          prompt, 24)
    assert np.max(np.abs(got - want)) > 200 * TOL


def test_the_head_is_the_table(sound):
    """An untied head of the same draw is another model: the comparison
    sees it; and the tied program reads no `lm_head` at all."""
    cfg, params, prompt, want = sound
    untied = dataclasses.replace(cfg, tie_word_embeddings=False)
    head = jax.random.normal(jax.random.PRNGKey(5), (256, 128)) * 0.05
    got = _prefill_logits({**params, "lm_head": head}, untied, prompt, 24)
    assert np.max(np.abs(got - want)) > 200 * TOL
    np.testing.assert_allclose(
        _prefill_logits({**params, "lm_head": head}, cfg, prompt, 24), want,
        atol=TOL)


def test_neutral_multipliers_add_no_operation():
    """The expert block's programs lower to the same text with the four
    fields at their neutral values stated (tests/lowered_pins.json pins the
    text itself)."""
    cfg = ssm_tiny.config()
    stated = dataclasses.replace(
        cfg, embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=None, logits_scaling=1.0)
    params = jax.eval_shape(lambda: ssm.init_params(jax.random.PRNGKey(0), cfg))
    ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    text = lambda c: ssm_decode.prefill_prompt.lower(params, ids, ids, c,
                                                    16).as_text()
    assert text(cfg) == text(stated)
    scaled = dataclasses.replace(cfg, residual_multiplier=0.5)
    assert text(cfg) != text(scaled)


# -- the tick's attention at narrow heads -----------------------------------------

def _softmax_rows(q, k, v, scale):
    """q: [h, hd]; k, v: [n, kv_h, hd] -> [h, hd], keys repeated to heads."""
    g = q.shape[0] // k.shape[1]
    k, v = np.repeat(k, g, axis=1), np.repeat(v, g, axis=1)
    s = np.einsum("hd,nhd->hn", q, k) * scale
    p = np.exp(s - s.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    return np.einsum("hn,nhd->hd", p, v)


def test_the_ticks_attention_reads_packed_pages_of_narrow_heads():
    """Heads of 64, 4 query heads a KV head, scale 1/64 (the published
    shape): two KV heads lie side by side in a 128-lane row of a page stored
    as its matrix; the one kernel every family runs reads it through a view
    and gives each query head its own KV head's softmax."""
    cfg = SsmMoEConfig.tiny(
        dense=True, hidden_size=32 * 64, num_attention_heads=32,
        num_key_value_heads=8, head_dim=64,
        attention_multiplier=1 / 64)
    page, pages, slots = 8, 6, 2
    rng = np.random.default_rng(0)
    pool = ssm_decode.init_page_pool(cfg, pages, page)
    assert pool["k"].shape == (1, pages + 1, page * 4, 128)
    lengths = [19, 8]
    table = np.asarray([[3, 1, 5], [2, 0, 0]], np.int32)
    k = rng.normal(size=(slots, 3 * page, 8, 64)).astype(np.float32)
    v = rng.normal(size=(slots, 3 * page, 8, 64)).astype(np.float32)
    q = rng.normal(size=(slots, 32, 64)).astype(np.float32)
    kv_mask = np.zeros((slots, 3 * page), np.int32)
    for s, n in enumerate(lengths):
        kv_mask[s, :n] = 1
        for p in range(-(-n // page)):
            for name, rows in (("k", k), ("v", v)):
                block = jnp.asarray(rows[s, p * page:(p + 1) * page])
                pool[name] = pool[name].at[0, table[s, p]].set(
                    ssm.packed_kv(block, cfg).reshape(page * 4, 128))
    live = jnp.asarray([-(-n // page) for n in lengths], jnp.int32)
    out = ssm.unpacked_heads(paged_decode_attention(
        ssm.packed_queries(jnp.asarray(q), cfg),
        ssm_decode._by_head(pool["k"], cfg, page),
        ssm_decode._by_head(pool["v"], cfg, page), jnp.int32(0),
        jnp.asarray(table), live, jnp.asarray(kv_mask), None,
        cfg.attn_scale), cfg)
    assert out.shape == (slots, 32, 64)
    for s, n in enumerate(lengths):
        want = _softmax_rows(q[s], k[s, :n], v[s, :n], 1 / 64)
        np.testing.assert_allclose(np.asarray(out[s]), want, atol=2e-5)
        # another scale is another softmax: the stated one is what ran
        wrong = _softmax_rows(q[s], k[s, :n], v[s, :n], 64 ** -0.5)
        assert np.max(np.abs(np.asarray(out[s]) - wrong)) > 1e-2


# -- the recurrence's step at one wide group ----------------------------------------

def _step_formula(store, index, x, dt, A, B, C):
    H, G = x.shape[1], B.shape[1]
    Bh, Ch = (np.repeat(a, H // G, axis=1) for a in (B, C))     # [s, H, N]
    S = (np.exp(dt * A)[..., None, None] * store[index]
         + (x * dt[..., None])[..., None] * Bh[:, :, None, :])
    out = store.copy()
    out[index] = S
    return np.einsum("shpn,shn->shp", S, Ch), out


def _step_operands(heads, groups, seed=0, slots=3, P=8, N=128, layers=2):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)
    store = f(layers, slots, heads, P, N)
    dt = np.abs(f(slots, heads)) * 0.1
    dt[1] = 0.0                                   # a row that is not decoding
    A = -np.abs(f(heads)) - 0.5
    return store, f(slots, heads, P), dt, A, f(slots, groups, N), f(slots, groups, N)


def test_a_step_at_one_group_of_64_heads_is_the_formula():
    """One group of 64 heads (the published shape; half a vreg of lanes): the
    group is walked in four runs of 16 unrolled heads, each rotated to the
    front, all reading the group's one B and C."""
    store, x, dt, A, B, C = _step_operands(heads=64, groups=1)
    assert step_kernel.head_block(64, 1, 8, 128) == 64
    y, out = step_kernel.ssm_state_step(jnp.asarray(store), 1, *map(
        jnp.asarray, (x, dt, A, B, C)))
    want_y, want = _step_formula(store, 1, x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out[0]), store[0])
    np.testing.assert_array_equal(np.asarray(out[1, 1]), store[1, 1])


def test_runs_of_a_group_are_the_group_bit_for_bit(monkeypatch):
    """The expert block's shape (groups of 16) walked a group a pass, as it
    always was, and in runs of 4: the same numbers, bit for bit, so the walk
    in runs changes no result."""
    store, x, dt, A, B, C = _step_operands(heads=32, groups=2, seed=1)
    args = [jnp.asarray(a) for a in (x, dt, A, B, C)]
    y_whole, whole = step_kernel.ssm_state_step(jnp.asarray(store), 0, *args)
    monkeypatch.setattr(step_kernel, "_UNROLL", 4)
    y_runs, runs = step_kernel.ssm_state_step(jnp.asarray(store), 0, *args)
    np.testing.assert_array_equal(np.asarray(y_whole), np.asarray(y_runs))
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(runs))


def test_a_group_of_16_heads_lowers_to_the_text_it_lowered_to():
    """A group no wider than the unrolled run takes the branch it always
    took: the kernel's jaxpr at the expert block's shape names no division
    of the pass's index (the one operation the walk in runs adds)."""
    store, x, dt, A, B, C = _step_operands(heads=32, groups=2)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in (store, x, dt, A, B, C)]
    text = lambda: str(jax.make_jaxpr(
        lambda s, *rest: step_kernel.ssm_state_step(s, 0, *rest))(*shapes))
    narrow = text()
    assert " div " not in narrow and " rem " in narrow
    wide = str(jax.make_jaxpr(
        lambda s, *rest: step_kernel.ssm_state_step(s, 0, *rest))(
            *[jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in _step_operands(heads=64, groups=1)]))
    assert " div " in wide
