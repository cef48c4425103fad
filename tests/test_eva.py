"""The compressed-window block (`models/eva/`) against its plain reference
(`benchmark/reference/eva_decoder.py`) on seeded weights at a tiny size
(window 32, chunk 4, pages of 8: `eva_tiny.py`), float32 on the CPU.

Tolerances, and where they come from. Both sides are float32 and differ in
the order of their sums (a softmax summed block by block against one summed
whole; pooled entries made once against made in one expression): 1e-4 on
logits of order 1 to 5, as the other families' tests keep. The kernel's tests
compare with `ops/attention.attention` over an explicit mask at 1e-5 (one
softmax, two orders of summation). bfloat16 cases use the paged kernel's own
tolerance (tests/test_paged_attention.py): two ulps of the result.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import eva_tiny as tiny
from benchmark.reference import eva_decoder
from llama_pipeline_parallel_tpu.models.eva import decode, model as eva
from llama_pipeline_parallel_tpu.models.eva.config import EvaConfig
from llama_pipeline_parallel_tpu.ops import eva_prefill_attention as kernel
from llama_pipeline_parallel_tpu.ops.attention import attention
from llama_pipeline_parallel_tpu.ops.paged_attention import (
    paged_decode_attention,
)

TOL = 1e-4
W, C, PAGE = tiny.WINDOW, tiny.CHUNK, tiny.PAGE


def _model_dict(cfg):
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if isinstance(v, (int, float))}


def _padded(seq, bucket):
    pad = bucket - len(seq)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = seq
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    return jnp.asarray(ids), jnp.asarray(mask)


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("n,bucket", [
    (30, 32),      # inside the first window: no summary exists
    (32, 32),      # ends on a window's edge
    (61, 64),      # ends inside a chunk of the second window
    (64, 64),      # two whole windows, no pad
    (66, 72),      # two positions into the third window
    (73, 80),      # left pad that is no whole chunk
    (96, 96),      # three windows
])
def test_a_whole_prompt_is_the_references_forward_pass(n, bucket):
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    seq = np.random.default_rng(n).integers(0, cfg.vocab_size, n).tolist()
    out = decode.prefill_prompt(params, *_padded(seq, bucket), cfg, bucket)
    ref = eva_decoder.sequence_logits(params, seq, _model_dict(cfg), 128)
    np.testing.assert_allclose(out["logits"][0], ref[-1], atol=TOL, rtol=TOL)
    assert int(out["next_pos"][0]) == n
    # what the queries read, from the lengths alone, times two layers
    pos = np.arange(n)
    assert out["counters"].tolist() == [
        2 * int((pos % W + 1).sum()), 2 * int((pos // W * (W // C)).sum()),
        2 * (n // W) * (W // C)]


def test_the_norm_scale_is_one_plus_the_stored_offset():
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    view = eva.with_unit_offset(params, cfg)
    np.testing.assert_allclose(view["norm"], 1.0 + params["norm"])
    np.testing.assert_allclose(view["layers"]["post_norm"],
                               1.0 + params["layers"]["post_norm"])
    assert view["lm_head"].shape == (cfg.hidden_size, cfg.vocab_size)
    assert params["lm_head"].shape == (
        cfg.hidden_size, cfg.num_pred_heads * cfg.vocab_size)
    # a model whose offsets are dropped is another model
    seq = list(range(40))
    ref = eva_decoder.sequence_logits(params, seq, _model_dict(cfg), 64)
    flat = jax.tree.map(lambda x: x, params)
    flat["norm"] = jnp.zeros_like(params["norm"])
    other = eva_decoder.sequence_logits(flat, seq, _model_dict(cfg), 64)
    assert float(jnp.abs(ref - other).max()) > 1e-2


def test_the_other_output_heads_are_read_by_nothing():
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    seq = list(range(37))
    out = decode.prefill_prompt(params, *_padded(seq, 40), cfg, 40)
    scrambled = dict(params)
    scrambled["lm_head"] = params["lm_head"].at[:, cfg.vocab_size:].set(7.0)
    again = decode.prefill_prompt(scrambled, *_padded(seq, 40), cfg, 40)
    np.testing.assert_array_equal(out["logits"], again["logits"])


def test_the_residual_stream_is_float32_and_products_are_the_compute_dtype():
    cfg = tiny.tiny_config(dtype=jnp.bfloat16)
    params = tiny.tiny_params(cfg)
    ids, mask = _padded(list(range(40)), 40)
    jaxpr = jax.make_jaxpr(
        lambda p: eva.forward_prompt(p, ids, mask, cfg)["logits"])(params)
    dots = [e for e in jax.tree.leaves(
        jaxpr.jaxpr.eqns, is_leaf=lambda e: hasattr(e, "primitive"))
        if e.primitive.name == "scan"]
    body = dots[0].params["jaxpr"].jaxpr
    products = [e for e in body.eqns if e.primitive.name == "dot_general"]
    dtypes = [{v.aval.dtype for v in e.invars} for e in products]
    # q, k, v, o, gate, up, down in the compute dtype; the two pooling
    # scores (keys against `mu` and `phi`) in float32
    assert dtypes.count({jnp.dtype(jnp.bfloat16)}) == 7
    assert dtypes.count({jnp.dtype(jnp.float32)}) == 2 and len(dtypes) == 9
    # the carried hidden state
    assert body.outvars[0].aval.dtype == jnp.float32
    out = eva.forward_prompt(params, ids, mask, cfg)
    assert out["logits"].dtype == jnp.float32


# -- the pooling and the rule, each against its twin --------------------------

def test_pooling_is_the_references_and_which_vector_pools_what_matters():
    cfg = tiny.tiny_config()
    rng = np.random.default_rng(0)
    k, v = (jnp.asarray(rng.normal(size=(W, 4, 8)), jnp.float32)
            for _ in range(2))
    mu, phi = (jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
               for _ in range(2))
    sk, sv = eva.pool_chunks(k, v, mu, phi, cfg)
    rk, rv = eva_decoder.pool_chunks(k, v, mu, phi, C)
    assert sk.shape == (W // C, 4, 8)
    np.testing.assert_allclose(sk, rk, atol=1e-6)
    np.testing.assert_allclose(sv, rv, atol=1e-6)
    # by hand, one chunk of one head: softmax over the chunk's four keys
    s = 8 ** -0.5
    w = jax.nn.softmax(s * k[:C, 2] @ phi[2])
    np.testing.assert_allclose(sv[0, 2], w @ v[:C, 2], atol=1e-6)
    swapped = eva.pool_chunks(k, v, phi, mu, cfg)
    assert float(jnp.abs(swapped[0] - sk).max()) > 1e-2


@pytest.mark.parametrize("p", [0, 3, 31, 32, 33, 63, 64, 100])
def test_the_rule_of_what_a_query_sees_is_the_references_two_sets(p):
    cfg = tiny.tiny_config()
    lo, hi = eva.visible_interval(jnp.asarray([p]), jnp.asarray([True]), cfg)
    positions = jnp.arange(128)
    exact = (positions >= lo) & (positions <= hi)
    np.testing.assert_array_equal(
        exact, eva_decoder.exact_set(jnp.asarray([p]), positions, W)[0])
    tags = eva.summary_tags(128 // C, cfg)
    np.testing.assert_array_equal(
        tags < lo, eva_decoder.summary_set(jnp.asarray([p]), 128 // C, W, C)[0])
    # none of its own window, every chunk of every earlier one
    assert int((tags < lo).sum()) == p // W * (W // C)
    assert int(exact.sum()) == p % W + 1
    seen = eva.visible_counts(jnp.asarray([p, 5]), jnp.asarray([True, False]),
                              cfg)
    assert [int(x) for x in seen] == [p % W + 1, p // W * (W // C)]


def test_a_pad_query_sees_nothing():
    cfg = tiny.tiny_config()
    lo, hi = eva.visible_interval(jnp.asarray([9]), jnp.asarray([False]), cfg)
    assert (int(lo[0]), int(hi[0])) == (kernel.PAD_LO, kernel.PAD_HI)


# -- the prefill kernel against plain attention over an explicit mask -------------

def _explicit(q, ks, vs, ts, ke, ve, te, lo, hi, heads):
    """`ops/attention.attention` over [summaries | exact keys] with the
    visibility written out as a mask a query."""
    b, T, width = q.shape
    hd = width // heads
    split = lambda x: x.reshape(*x.shape[:2], -1, hd)
    keys = jnp.concatenate([split(ks), split(ke)], axis=1)
    values = jnp.concatenate([split(vs), split(ve)], axis=1)
    seen = jnp.concatenate([
        (ts[:, None, :] >= 0) & (ts[:, None, :] < lo[:, :, None]),
        (te[:, None, :] >= lo[:, :, None]) & (te[:, None, :] <= hi[:, :, None])],
        axis=-1)                                        # [b, T, S]
    outs = []
    for t in range(T):
        outs.append(attention(split(q)[:, t:t + 1], keys, values,
                              seen[:, t].astype(jnp.int32), causal=False))
    out = jnp.concatenate(outs, axis=1)
    return jnp.where(seen.any(-1)[..., None, None], out, 0.0).reshape(b, T, -1)


@pytest.mark.parametrize("case", ["aligned", "crosses_a_window", "pads_first",
                                  "grouped_heads", "odd_sizes"])
def test_the_prefill_kernel_is_one_softmax_over_both_kinds_of_key(case):
    rng = np.random.default_rng(3)
    heads, kv_heads, hd, T = 4, 4, 8, 32
    n_sum, first, n_valid = 24, 64, 32
    if case == "crosses_a_window":
        first = 50                  # positions 50 .. 81: windows 1 and 2
    if case == "pads_first":
        first, n_valid = 0, 19      # 13 pads, then positions 0 .. 18
    if case == "grouped_heads":
        kv_heads = 2
    if case == "odd_sizes":
        T, n_sum, first, n_valid = 24, 10, 40, 24
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q = f(1, T, heads * hd)
    ks, vs = f(1, n_sum, kv_heads * hd), f(1, n_sum, kv_heads * hd)
    ke, ve = f(1, W + T, kv_heads * hd), f(1, W + T, kv_heads * hd)
    ts = jnp.arange(n_sum)[None] * C
    pos = first + np.arange(T) - (T - n_valid)
    valid = np.arange(T) >= T - n_valid
    window = max(first, 0) // W
    before = first - window * W
    ring = np.where(np.arange(W) < before, window * W + np.arange(W), -1)
    te = jnp.asarray(np.concatenate([ring, np.where(valid, pos, -1)]))[None]
    cfg = tiny.tiny_config()
    lo, hi = eva.visible_interval(jnp.asarray(pos)[None],
                                  jnp.asarray(valid)[None], cfg)
    got = kernel.eva_prefill_attention(q, ks, vs, ts, ke, ve, te, lo, hi,
                                       heads, hd ** -0.5)
    want = _explicit(q, jnp.repeat(ks.reshape(1, n_sum, kv_heads, hd),
                                   heads // kv_heads, 2).reshape(1, n_sum, -1),
                     jnp.repeat(vs.reshape(1, n_sum, kv_heads, hd),
                                heads // kv_heads, 2).reshape(1, n_sum, -1),
                     ts, jnp.repeat(ke.reshape(1, W + T, kv_heads, hd),
                                    heads // kv_heads, 2).reshape(1, W + T, -1),
                     jnp.repeat(ve.reshape(1, W + T, kv_heads, hd),
                                heads // kv_heads, 2).reshape(1, W + T, -1),
                     te, lo, hi, heads)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    if case == "pads_first":
        assert float(jnp.abs(got[0, :13]).max()) == 0.0


def test_block_runs_never_skip_a_tile_that_holds_a_visible_pair():
    rng = np.random.default_rng(5)
    for _ in range(20):
        first = int(rng.integers(0, 200))
        pos = first + np.arange(16)
        lo = jnp.asarray(pos // W * W)[None]
        hi = jnp.asarray(pos)[None]
        ts = jnp.arange(32)[None] * C
        te = jnp.asarray(rng.permutation(np.arange(first - 16, first + 16)))[None]
        te = jnp.where(te < 0, -1, te)
        run = np.asarray(kernel.block_runs(lo, hi, ts, te, 8, 8, 8))[0]
        vis_s = np.asarray((ts[0][None, :] < lo[0][:, None]))
        vis_e = np.asarray((te[0][None, :] >= lo[0][:, None])
                           & (te[0][None, :] <= hi[0][:, None]))
        vis = np.concatenate([vis_s, vis_e], axis=1)
        tiles = vis.reshape(2, 8, 8, 8).any(axis=(1, 3))
        assert (run[tiles] == 1).all()


# -- the tick's read: the dense family's kernel over two live lengths -------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows", ["mixed", "first_window", "window_edge"])
def test_the_paged_kernel_over_two_live_lengths_is_plain_attention(rows, dtype):
    """`paged_decode_attention` as it stands, given `_live_pages`' table,
    count and mask, against `ops/attention.attention` over the same rows
    gathered by hand: summaries of the earlier windows, then the ring's live
    entries."""
    rng = np.random.default_rng(7)
    L, pages, h, hd, S, n_sum, ring = 2, 40, 4, 128, 3, 5, W // PAGE
    pos = {"mixed": [70, 5, 0], "first_window": [0, 17, 31],
           "window_edge": [63, 64, 95]}[rows]
    active = np.array([1, 1, 0 if rows == "mixed" else 1])
    pos = jnp.asarray(pos)
    pool_k, pool_v = (jnp.asarray(rng.normal(size=(L, pages + 1, PAGE, h, hd)),
                                  dtype) for _ in range(2))
    table = jnp.asarray(rng.permutation(pages)[:S * (n_sum + ring)].reshape(
        S, n_sum + ring), jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, h, hd)), dtype)
    n_window = jnp.where(active > 0, pos % W + 1, 0)
    n_summaries = jnp.where(active > 0, pos // W * (W // C), 0)
    live_table, live, mask = decode._live_pages(table, n_summaries, n_window,
                                                n_sum, PAGE)
    got = paged_decode_attention(q, pool_k, pool_v, jnp.int32(1), live_table,
                                 live, mask)
    for s in range(S):
        if not active[s]:
            assert float(jnp.abs(got[s].astype(jnp.float32)).max()) == 0.0
            continue
        ns, nw = int(n_summaries[s]), int(n_window[s])
        rows_of = lambda pool, cols, n: pool[1, table[s, cols]].reshape(
            -1, h, hd)[:n]
        keys = jnp.concatenate([
            rows_of(pool_k, slice(0, n_sum), ns),
            rows_of(pool_k, slice(n_sum, None), nw)])[None]
        values = jnp.concatenate([
            rows_of(pool_v, slice(0, n_sum), ns),
            rows_of(pool_v, slice(n_sum, None), nw)])[None]
        want = attention(q[s][None, None], keys, values,
                         jnp.ones((1, ns + nw), jnp.int32), causal=False)
        tol = (dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32
               else dict(rtol=2 ** -6, atol=2 ** -7))
        np.testing.assert_allclose(np.asarray(got[s], np.float32),
                                   np.asarray(want[0, 0], np.float32), **tol)


# -- the configuration ----------------------------------------------------------

def test_the_published_keys_make_the_configuration_and_others_are_refused():
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "evabyte-6.5b.pp4-d8.json")) as f:
        published = json.load(f)
    cfg = EvaConfig.from_published(published)
    assert (cfg.window_size, cfg.chunk_size, cfg.num_pred_heads) == (2048, 16, 8)
    assert (cfg.hidden_size, cfg.head_dim, cfg.kv_heads) == (4096, 128, 32)
    assert cfg.num_hidden_layers == 8 and cfg.vocab_size == 320
    assert cfg.rope_theta == 100000 and cfg.rms_norm_eps == 1e-5
    assert cfg.chunks_per_window == 128 and cfg.family == "eva"
    with pytest.raises(ValueError, match="norm_add_unit_offset"):
        EvaConfig.from_published({**published, "norm_add_unit_offset": False})
    with pytest.raises(ValueError, match="attention_class"):
        EvaConfig.from_published({**published, "attention_class": "softmax"})
    with pytest.raises(ValueError, match="whole number of chunks"):
        EvaConfig(window_size=100, chunk_size=16)
