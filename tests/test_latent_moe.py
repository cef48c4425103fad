"""Layers of the latent-attention block against the plain reference, float32
on the CPU at a tiny size (`index_topk` 8, a window of 5): the mixer of
either kind in its absorbed and its projected form, the exact selection, the
ring's mask, the expert layer's shares at this family's sizes, and every
term of the model under the seeded draw (an alteration of one breaks the
comparison)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_tiny
import latent_tiny as tiny
import mla_tiny
from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.latent_moe import decode, model as latent
from llama_pipeline_parallel_tpu.models.latent_moe.config import LatentMoEConfig
from llama_pipeline_parallel_tpu.ops import rope
from llama_pipeline_parallel_tpu.ops.latent_prefill_attention import (
    latent_prefill_attention,
)
from llama_pipeline_parallel_tpu.ops.paged_latent_attention import (
    paged_latent_decode_attention,
)

TOL = 2e-5


def _layer(index: int, model=tiny.MODEL):
    """(reference layer, the program's mixer leaves) of layer `index`."""
    ref = tiny.weights.make_layer(tiny.SEED, index, model, jnp.float32)
    return ref, {"input_norm": ref["input_norm"], **ref["mixer"]}


def _inputs(b=2, s=24, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, s, 32), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return x, positions


def _reference_mixer(ref, x, positions, kind, model=tiny.MODEL, alter=()):
    dm = tiny.reference.dims(model)
    h = tiny.reference.rms_norm(x, ref["input_norm"], dm["eps"])
    mixed, mask = tiny.reference.mla_mixer(ref["mixer"], h, positions, dm,
                                           kind, "float32", alter)
    return x + mixed, mask


def _full(layer, x, positions, cfg, absorbed):
    b, s, _ = x.shape
    valid = jnp.ones((b, s), bool)
    pr = latent.project(layer, x, positions, cfg.kind(False), cfg)
    pr["index"] = latent.index_project(layer, pr["hidden"], pr["cq"],
                                       positions, cfg)
    return latent.full_span(layer, x, valid, positions, pr, pr["entry"],
                            pr["index"][1], valid, cfg, absorbed=absorbed)


@pytest.mark.parametrize("absorbed", [True, False], ids=["absorbed", "projected"])
def test_a_full_layer_in_either_form_is_the_reference(absorbed):
    """24 positions against `index_topk` 8: most queries select. The
    program's counts are the host's, and the last query's selection is the
    reference's own."""
    cfg = tiny.config()
    ref, layer = _layer(1)
    x, positions = _inputs()
    want, mask = _reference_mixer(ref, x, positions, "full")
    got, counted, (chosen, ok) = _full(layer, x, positions, cfg, absorbed)
    np.testing.assert_allclose(got, want, atol=TOL)
    seen = 2 * sum(t + 1 for t in range(24))
    kept = 2 * sum(min(t + 1, 8) for t in range(24))
    assert counted.tolist() == [seen, kept]
    for row in range(2):
        mine = sorted(np.asarray(chosen[row])[np.asarray(ok[row])].tolist())
        assert mine == np.flatnonzero(np.asarray(mask[row, -1])).tolist()
        assert 23 in mine and len(mine) == 8


@pytest.mark.parametrize("absorbed", [False, True], ids=["projected", "absorbed"])
def test_a_sliding_layer_in_either_form_is_the_reference(absorbed):
    cfg = tiny.config()
    ref, layer = _layer(2)
    x, positions = _inputs()
    want, _ = _reference_mixer(ref, x, positions, "sliding")
    b, s, _ = x.shape
    pr = latent.project(layer, x, positions, cfg.kind(True), cfg)
    got = latent.window_span(
        layer, x, pr, jnp.zeros((b, 4, cfg.ring_width)), jnp.zeros((b, 4), bool),
        jnp.ones((b, s), bool), cfg, absorbed=absorbed)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_a_short_row_selects_every_visible_position_without_a_sort():
    """At most `index_topk` places: the selection is every visible one, the
    same set the sort would give."""
    scores = jnp.asarray(np.random.default_rng(0).standard_normal((3, 8)),
                         jnp.float32)
    before = jnp.asarray([[1, 1, 0, 1, 0, 0, 0, 0]] * 3, bool)
    own = jnp.asarray([[0, 0, 0, 0, 1, 0, 0, 0]] * 3, bool)
    chosen, ok = latent.select(scores, before, own, 8)
    assert chosen.shape == (3, 8)
    for row in range(3):
        assert sorted(np.asarray(chosen[row])[np.asarray(ok[row])]) == [0, 1, 3, 4]
    sorted_chosen, sorted_ok = latent.select(scores, before, own, 7)
    for row in range(3):
        assert sorted(np.asarray(sorted_chosen[row])[
            np.asarray(sorted_ok[row])]) == [0, 1, 3, 4]


def test_the_selection_is_exact_with_ties_to_the_lower_position():
    """Equal scores: the lower position wins a place; the query's own
    position holds one whatever its score; nothing not yet visible is
    ever chosen."""
    scores = jnp.asarray([[5.0, 1.0, 5.0, 1.0, 1.0, 1.0, -9.0, 7.0, 7.0, 7.0]])
    before = jnp.asarray([[1, 1, 1, 1, 0, 1, 0, 0, 0, 0]], bool)
    own = jnp.asarray([[0, 0, 0, 0, 0, 0, 1, 0, 0, 0]], bool)
    chosen, ok = latent.select(scores, before, own, 4)
    assert np.asarray(chosen[0])[np.asarray(ok[0])].tolist() == [6, 0, 2, 1]
    chosen, ok = latent.select(scores, before & False, own, 4)
    assert np.asarray(chosen[0])[np.asarray(ok[0])].tolist() == [6]


def test_a_rings_place_is_visible_only_inside_the_window():
    """A ring of 6 under a window of 5: the place of the position six back
    was just overwritten by the query's own entry, the place of the position
    five back holds a token outside the window, and left pads are seen by
    nobody."""
    cfg = tiny.config()
    assert (cfg.ring_len, cfg.sliding_window_size) == (6, 5)
    row_valid = jnp.asarray([[0, 0, 0] + [1] * 13, [1] * 16], jnp.int32)
    seen = np.asarray(latent.ring_mask(jnp.asarray([4, 10]), row_valid, cfg))
    # row 0 at place 4: holds 0..4, of which 0..2 are pads
    assert seen[0].tolist() == [False, False, False, True, True, False]
    # row 1 at place 10: places hold 6, 7, 8, 9, 10, 5; 5 is outside
    assert seen[1].tolist() == [True, True, True, True, True, False]


def _uncut_moe():
    model = {**tiny.MODEL, "n_routed_experts": 16, "router_experts": 16,
             "expert_offset": 0}
    layer = tiny.weights.make_layer(tiny.SEED, 3, model, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32), jnp.float32)
    return model, layer, x


def test_the_eight_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Eight chips of two experts each at this family's sizes, the shared
    expert counted once: the sum of what `moe_block` computes for each is the
    uncut reference's layer (guide §4)."""
    model, layer, x = _uncut_moe()
    dm = tiny.reference.dims(model)
    hidden = tiny.reference.rms_norm(x, layer["post_norm"], dm["eps"])
    want = tiny.reference.moe_layer(layer["moe"], hidden, dm, "float32")
    valid = jnp.ones(x.shape[:2], bool)
    total, here = jnp.zeros_like(x), 0
    for lo in range(0, 16, 2):
        cfg = tiny.config({**model, "n_routed_experts": 2, "expert_offset": lo})
        cut = lambda name: layer["moe"][name][lo:lo + 2]
        moe = {"post_norm": layer["post_norm"], **layer["moe"],
               "gate": cut("gate"), "up": cut("up"), "down": cut("down")}
        out, counters = hybrid_tiny.moe_block_alone(moe, x, valid, cfg,
                                                    shared=lo == 0)
        total = total + (out - x)
        here += int(counters[1])
        assert int(counters[0]) == x.shape[0] * x.shape[1] * 4
    assert here == x.shape[0] * x.shape[1] * 4   # every assignment, once
    np.testing.assert_allclose(total, want, atol=TOL)


@pytest.mark.parametrize("case", ["seeded", "idle", "one"])
@pytest.mark.parametrize("place", [0, 1, 2])
@pytest.mark.parametrize("family", ["dots3", "a.x-k1"])
def test_a_layer_in_a_stack_of_three_is_the_layer_alone(family, place, case):
    """At both tiny configurations of this family (`routed_scaling_factor` 1
    and 2.5): `moe_block` on the stack of three periods' experts at `place`
    is, bit for bit in output and in all six counters, `moe_block` on that
    layer's own leaves as a stack of one. `idle`: held expert 6 gets no row;
    `one`: every row on held expert 5 (and three experts that are not held);
    two positions are not valid."""
    mod = {"dots3": tiny, "a.x-k1": mla_tiny}[family]
    cfg = mod.config()
    layers = [mod.weights.make_layer(mod.SEED, index, mod.MODEL, jnp.float32)
              for index in (1, 2, 3)]
    stack = {name: jnp.stack([layer["moe"][name] for layer in layers])
             for name in hybrid.EXPERT_LEAVES}
    layer = layers[place]
    moe = hybrid_tiny.biased(
        {"post_norm": layer["post_norm"], **layer["moe"]}, case)
    x = jax.random.normal(jax.random.PRNGKey(place), (2, 12, 32), jnp.float32)
    valid = jnp.ones(x.shape[:2], bool).at[1, :2].set(False)
    block = jax.jit(hybrid.moe_block, static_argnames=("cfg",))   # place traced
    want, counted = block(moe, hybrid_tiny.stack_of_one(moe), jnp.int32(0), x,
                          valid, cfg=cfg)
    got, counters = block(moe, stack, jnp.int32(place), x, valid, cfg=cfg)
    assert counters.tolist() == counted.tolist()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    live = int(valid.sum())
    assert counters[0] == live * 4 and counters[4] == cfg.held == 8
    if case == "idle":
        assert counters[2] < cfg.held
    if case == "one":
        assert counters.tolist() == [live * 4, live, 1, live, cfg.held, 1]


# -- every term matters: an alteration of one breaks the comparison -------------

def _prefill_logits(model, seed=tiny.SEED, **cfg_kw):
    cfg = tiny.config(model, **cfg_kw)
    params = tiny.weights.make_program_weights(seed, model, jnp.float32)
    ids = np.random.default_rng(5).integers(0, 128, (1, 32)).astype(np.int32)
    out = decode.prefill_prompt(params, jnp.asarray(ids),
                                jnp.ones((1, 32), jnp.int32), cfg, 32)
    return ids, np.asarray(out["logits"][0])


def _reference_logits(ids, model=tiny.MODEL, alter=()):
    """The reference's last logits under `model`, always with the weights of
    the unaltered one (the draw divides the rescale out of `wqb` / `wkb`, so
    weights drawn for an altered model would hide the alteration)."""
    top = tiny.weights.make_top(tiny.SEED, tiny.MODEL, jnp.float32)
    layer_fn = tiny.weights.layer_fn(tiny.SEED, tiny.MODEL, jnp.float32)
    return np.asarray(tiny.reference.logits_fn(
        top, layer_fn, jnp.asarray(ids), model, alter=alter)[0, -1])


ALTERATIONS = {
    # the reference's side altered: what it then computes is another model
    "rescale": ({"apply_mla_qkv_lora_rescale": False}, ()),
    "gate": ({}, ("no_gate",)),
    "rope_theta": ({"rope_theta": 10000}, ()),
    "swa_rope_theta": ({"swa_rope_theta": 500}, ()),
    "window_edge": ({"sliding_window_size": 4}, ()),
    "index_topk": ({"index_topk": 7}, ()),
    "most_recent": ({}, ("most_recent",)),
    "held_range": ({"expert_offset": 5}, ()),
}


def test_the_unaltered_model_is_the_reference():
    ids, got = _prefill_logits(tiny.MODEL)
    np.testing.assert_allclose(got, _reference_logits(ids), atol=1e-4)


@pytest.mark.parametrize("what", sorted(ALTERATIONS))
def test_every_term_matters_under_the_seeded_draw(what):
    """Rescale, gate, either rope theta, the window's edge by one, the
    number selected, "the largest" replaced by "the most recent", the held
    range: each moves the last position's logits by far more than the
    comparison's tolerance, so none can drop out unseen."""
    changed, alter = ALTERATIONS[what]
    ids, got = _prefill_logits(tiny.MODEL)
    altered = _reference_logits(ids, {**tiny.MODEL, **changed}, alter)
    assert np.max(np.abs(got - altered)) > 100 * 1e-4, what


def test_the_configuration_refuses_what_is_not_this_shape():
    with pytest.raises(ValueError, match="whole number of periods"):
        LatentMoEConfig.tiny(num_hidden_layers=8)
    with pytest.raises(ValueError, match="outside the router"):
        LatentMoEConfig.tiny(expert_offset=12, experts_held=8)
    bad = {**tiny.MODEL, "layer_types": ["sliding_attention"] * 13}
    with pytest.raises(ValueError, match="layer_types"):
        tiny.config(bad)
    with pytest.raises(ValueError, match="one leading dense layer"):
        tiny.config({**tiny.MODEL, "first_k_dense_replace": 0})
    with pytest.raises(ValueError, match="one number a head"):
        tiny.config({**tiny.MODEL, "attention_gate_type": "elementwise"})
    with pytest.raises(ValueError, match="layer_types"):
        tiny.reference.dims(bad)
    cfg = tiny.config()
    assert (cfg.full_layers, cfg.window_layers, cfg.expert_layers) == (3, 6, 8)
    assert (cfg.latent_width, cfg.ring_width, cfg.ring_len) == (12, 16, 6)
    published = LatentMoEConfig()
    assert (published.latent_width, published.ring_width,
            published.ring_len) == (576, 1088, 576)


def test_the_seeded_trees_of_both_sides_hold_the_same_leaves():
    """The program's tree from the benchmark's draw has the shapes
    `init_params` gives, so a checkpoint of one loads as the other."""
    cfg = tiny.config()
    drawn = tiny.weights.make_program_weights(tiny.SEED, tiny.MODEL, jnp.float32)
    made = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(drawn) == jax.tree.structure(made)
    for a, b in zip(jax.tree.leaves(drawn), jax.tree.leaves(made)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    counts = tiny.weights.param_count(tiny.MODEL)
    assert counts["total"] == latent.param_count(cfg)


# -- the kernel of the read by token ----------------------------------------------

@pytest.mark.parametrize("shape", [(6, 4, 8, 16), (3, 128, 256, 128)],
                         ids=["tiny", "tiles"])
def test_the_sparse_read_kernel_is_the_xla_form(shape):
    """`ops/sparse_latent_attention.py` (interpreted here) against
    `attend_entries` over each query's own entries, places that hold no
    position among them."""
    from llama_pipeline_parallel_tpu.models.latent_moe.config import MixerDims

    n, h, k, w = shape
    kd = MixerDims(h, 0, w - 4, 8, 4, 8, 1e4, 1.0, 1.0)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (1, n, h, w - 2), jnp.float32)
    entries = jax.random.normal(keys[1], (1, n, k, w), jnp.float32)
    ok = jax.random.uniform(keys[2], (1, n, k)) < 0.7
    ok = ok.at[..., 0].set(True)
    want = latent.attend_entries(q, entries, ok, kd)
    got = latent.attend_chosen(q, entries, ok, kd)
    assert got.shape == (1, n, h, w - 4)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# -- dots3's block computes what it computed before the family was generalised ----

# the parent's values (commit 2b9ffa3, this file's tiny model and seed, the
# prompt of `_prefill_logits`), pinned before the refactor of PR 32
PINNED_PREFILL_LOGITS = [-0.41476768, -0.44767633, 0.11468326, -0.24117644,
                         0.32801443, 0.7254978, -0.5284773, 0.6174224]
# re-pinned at PR 43, which put `expert_visits` sixth: the 128 sorted rows of
# this prompt are one row tile, so every layer's product visits exactly the
# experts that have a row (60 over the eight expert layers). The logits were
# NOT re-pinned: in float32 the kernel's sums differ from `ragged_dot`'s by
# their order alone, inside the 2e-6 these were held to
PINNED_PREFILL_COUNTERS = [1024, 461, 60, 134, 64, 60, 1584, 684]


def test_dots3s_tiny_configuration_gives_the_logits_it_gave():
    cfg = tiny.config()
    assert cfg.period == ("full", "sliding", "sliding", "sliding")
    assert cfg.has_indexer and cfg.attention_gate and cfg.lora_rescale
    assert cfg.rope_scaling is None
    assert decode.counters(cfg)[-2:] == ("index_visible", "index_selected")
    params = tiny.weights.make_program_weights(tiny.SEED, tiny.MODEL, jnp.float32)
    ids = np.random.default_rng(5).integers(0, 128, (1, 32)).astype(np.int32)
    out = decode.prefill_prompt(params, jnp.asarray(ids),
                                jnp.ones((1, 32), jnp.int32), cfg, 32)
    np.testing.assert_allclose(out["logits"][0, :8], PINNED_PREFILL_LOGITS,
                               atol=2e-6, rtol=0)
    assert out["counters"].tolist() == PINNED_PREFILL_COUNTERS


# -- one kind of layer: plain MLA under YaRN, no indexer, window or gate ----------


AXK1_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
             "mscale_all_dim": 1, "original_max_position_embeddings": 4096}


def test_yarn_is_the_written_out_table():
    """A.X-K1's numbers (rope 64, theta 1e4, factor 32 from 4096, 32 and 1
    rotations): the correction dimensions are 64 ln(4096 / (r 2 pi)) / (2 ln
    1e4) = 10.47 and 22.51, so frequencies 0..10 are kept, 23..31 divided by
    32, and j between them blends by (j - 10) / 13."""
    got = np.asarray(rope.yarn_inv_freq(64, 1e4, AXK1_YARN), np.float64)
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64.0)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 32, rtol=1e-6)
    for j in (11, 16, 22):
        ramp = (j - 10) / 13
        np.testing.assert_allclose(
            got[j], plain[j] / 32 * ramp + plain[j] * (1 - ramp), rtol=1e-6)
    # both sides' own tables, at the tiny sizes too
    dm = mla_tiny.reference.dims(mla_tiny.MODEL)
    cfg = mla_tiny.config()
    np.testing.assert_allclose(
        rope.yarn_inv_freq(8, 100.0, dict(cfg.rope_scaling)),
        mla_tiny.reference.yarn_inv_freq(dm), rtol=1e-6)
    # the attention factor: 0.1 ln 32 + 1, squared on the softmax scale
    assert rope.yarn_mscale(32, 1) == pytest.approx(1.34657359)
    full = LatentMoEConfig(rope_scaling=tuple(sorted(
        (k, float(v)) for k, v in AXK1_YARN.items()))).kind(False)
    assert full.softmax_scale == pytest.approx(192 ** -0.5 * 1.34657359 ** 2)
    # cos and sin carry mscale / mscale_all_dim, 1 for A.X-K1
    positions = jnp.arange(40, dtype=jnp.int32)[None]
    cos, sin = rope.rope_cos_sin(positions, 8, 100.0,
                                 scaling=dict(cfg.rope_scaling))
    angle = np.arange(40)[:, None] * np.asarray(
        rope.yarn_inv_freq(8, 100.0, dict(cfg.rope_scaling)))
    amplitude = rope.yarn_mscale(4, 1) / rope.yarn_mscale(4, 0.5)
    np.testing.assert_allclose(cos[0, :, :4], np.cos(angle) * amplitude,
                               atol=1e-6)
    np.testing.assert_allclose(sin[0, :, 4:], np.sin(angle) * amplitude,
                               atol=1e-6)


def test_no_rope_scaling_is_the_table_it_always_was():
    positions = jnp.arange(300, dtype=jnp.int32).reshape(2, 150)
    cos, sin = rope.rope_cos_sin(positions, 64, 8e7, dtype=jnp.bfloat16)
    inv = 1.0 / (8e7 ** (jnp.arange(0, 64, 2, dtype=jnp.float32) / 64))
    freqs = positions.astype(jnp.float32)[..., None] * inv
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    assert bool(jnp.all(cos == jnp.cos(emb).astype(jnp.bfloat16)))
    assert bool(jnp.all(sin == jnp.sin(emb).astype(jnp.bfloat16)))
    assert LatentMoEConfig().kind(False).softmax_scale == 192 ** -0.5


def test_the_published_keys_of_a_model_of_one_kind_of_layer_are_read_as_they_are():
    cfg = mla_tiny.config()
    assert cfg.period == ("full",) and cfg.periods == 4
    assert (cfg.full_layers, cfg.window_layers, cfg.expert_layers) == (5, 0, 4)
    assert not cfg.has_indexer and not cfg.attention_gate
    assert not cfg.lora_rescale and cfg.kind(False).rq_scale == 1.0
    assert dict(cfg.rope_scaling)["factor"] == 4.0
    assert (cfg.held, cfg.router_experts, cfg.expert_offset) == (8, 16, 4)
    assert decode.counters(cfg)[-1] == "latent_visible"
    assert len(decode.counters(cfg)) == 7
    # what is neither a latent model's rope nor this block is refused
    with pytest.raises(ValueError, match="YaRN"):
        mla_tiny.config({**mla_tiny.MODEL, "rope_scaling": {
            **mla_tiny.MODEL["rope_scaling"], "type": "linear"}})
    with pytest.raises(ValueError, match="YaRN"):
        tiny.config({**tiny.MODEL,
                     "rope_scaling": mla_tiny.MODEL["rope_scaling"]})
    with pytest.raises(ValueError, match="one full layer, then"):
        LatentMoEConfig.tiny(period=("sliding", "full"), num_hidden_layers=5)
    # the tree holds what the configuration has: no gate, no indexer, no
    # sliding layers; the benchmark's draw has the same leaves
    made = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    assert made["periods"]["win"] == [] and len(made["periods"]["moe"]) == 1
    assert not {"wg", "wqi", "wki", "ww"} & set(made["first"]["attn"])
    drawn = mla_tiny.weights.make_program_weights(
        mla_tiny.SEED, mla_tiny.MODEL, jnp.float32)
    assert jax.tree.structure(drawn) == jax.tree.structure(made)
    for a, b in zip(jax.tree.leaves(drawn), jax.tree.leaves(made)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert mla_tiny.weights.param_count(mla_tiny.MODEL)["total"] == \
        latent.param_count(cfg)


def _mla_layer(index=1):
    ref = mla_tiny.weights.make_layer(mla_tiny.SEED, index, mla_tiny.MODEL,
                                      jnp.float32)
    return ref, {"input_norm": ref["input_norm"], **ref["mixer"]}


def test_the_projected_and_the_absorbed_form_are_the_references_mixer():
    """A full layer without an indexer over a span: the projected form
    through its kernel (`dense_span`), the absorbed form over the same
    entries (`attend_entries`, the tick's arithmetic), and the reference's
    mixer, left pads masked on the program's side."""
    cfg = mla_tiny.config()
    kd = cfg.kind(False)
    ref, layer = _mla_layer()
    x, positions = _inputs(s=40, seed=3)
    want = x + mla_tiny.reference.mla_mixer(
        ref["mixer"], mla_tiny.reference.rms_norm(x, ref["input_norm"], 1e-6),
        positions, mla_tiny.reference.dims(mla_tiny.MODEL), "float32")
    valid = jnp.ones(x.shape[:2], bool)
    pr = latent.project(layer, x, positions, kd, cfg)
    projected = latent.dense_span(layer, x, jnp.int32(0), pr, pr["entry"],
                                  valid, cfg)
    np.testing.assert_allclose(projected, want, atol=TOL)
    causal = jnp.tril(jnp.ones((40, 40), bool))[None]
    q_abs = latent.absorb(layer, pr["q_nope"], pr["q_rope"], cfg)
    o = latent.attend_entries(q_abs, pr["entry"], jnp.broadcast_to(
        causal, (2, 40, 40)), kd)
    absorbed = latent.output(layer, x, pr["hidden"],
                             latent.unabsorb(layer, o, cfg), cfg)
    np.testing.assert_allclose(absorbed, want, atol=TOL)
    assert latent.visible_count(valid, positions, valid).tolist() == [
        2 * 40 * 41 // 2]


@pytest.mark.parametrize("shape,q_start,pads", [
    ((2, 3, 8, 24, 8, 4, 8), 16, (5, 0)),     # a chunk's queries, left pads
    ((1, 2, 16, 16, 8, 8, 4), 0, (16,)),      # a row of nothing but pads
    ((1, 4, 256, 768, 128, 64, 128), 512, (130,)),   # whole tiles, 2 x 2 blocks
], ids=["chunk", "all-pads", "tiles"])
def test_the_prefill_kernel_is_the_xla_form(shape, q_start, pads, monkeypatch):
    """`ops/latent_prefill_attention.py` (interpreted here) against
    `attend_projected` under the explicit mask: causal from `q_start`, the
    row's left pads invisible, blocks past the diagonal and inside the pads
    skipped."""
    from llama_pipeline_parallel_tpu.models.latent_moe.config import MixerDims
    from llama_pipeline_parallel_tpu.ops import latent_prefill_attention as lpa

    b, H, T, S, nope, rope_n, dv = shape
    monkeypatch.setattr(lpa, "BLOCK_Q", 128)
    monkeypatch.setattr(lpa, "BLOCK_K", 256)
    kd = MixerDims(H, 0, 0, nope, rope_n, dv, 1e4, 1.0, 1.0)
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    qn = jax.random.normal(keys[0], (b, H, T, nope), jnp.float32)
    qr = jax.random.normal(keys[1], (b, H, T, rope_n), jnp.float32)
    kn = jax.random.normal(keys[2], (b, H, S, nope), jnp.float32)
    kr = jax.random.normal(keys[3], (b, S, rope_n), jnp.float32)
    v = jax.random.normal(keys[4], (b, H, S, dv), jnp.float32)
    valid = jnp.arange(S)[None, :] >= jnp.asarray(pads)[:, None]
    got = latent_prefill_attention(qn, qr, kn, kr, v, valid,
                                   jnp.int32(q_start), kd.softmax_scale)
    q = jnp.moveaxis(jnp.concatenate([qn, qr], -1), 1, 2)
    k = jnp.moveaxis(jnp.concatenate(
        [kn, jnp.broadcast_to(kr[:, None], (b, H, S, rope_n))], -1), 1, 2)
    place = q_start + jnp.arange(T)
    mask = (jnp.arange(S)[None, None, :] <= place[None, :, None]) & \
        valid[:, None, :]
    want = latent.attend_projected(q, k, jnp.moveaxis(v, 1, 2), mask, kd)
    seen = np.asarray(jnp.any(mask, -1))                     # [b, T]
    got = np.asarray(got).reshape(b, T, H, dv)
    np.testing.assert_allclose(got[seen], np.asarray(want)[seen], atol=2e-5,
                               rtol=2e-5)
    assert not got[~seen].any()             # a query that sees nothing: zeros


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2 ** -6)])
def test_the_paged_latent_decode_kernel_is_the_xla_form(dtype, tol):
    """`ops/paged_latent_attention.py` (interpreted here) against
    `attend_entries` over each row's gathered logical row: pages out of
    order in the pool, left pads and a hole in the mask, a row that is not
    decoding (zeros), another layer's pages untouched."""
    from llama_pipeline_parallel_tpu.models.latent_moe.config import MixerDims

    L, pages, page, pmax, H, rank, width = 3, 40, 8, 6, 4, 24, 32
    kd = MixerDims(H, 0, rank, 8, 4, 8, 1e4, 1.0, 1.0)
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    pool = jax.random.normal(keys[0], (L, pages + 1, page, width),
                             jnp.float32).astype(dtype)
    q = jax.random.normal(keys[1], (3, H, width), jnp.float32).astype(dtype)
    q = q.at[..., rank + 4:].set(0)          # zeros past the entry
    table = np.full((3, pmax), pages, np.int32)
    table[0, :4] = [7, 3, 22, 9]
    table[1, :6] = [1, 30, 2, 39, 5, 11]
    mask = np.zeros((3, pmax * page), np.int32)
    mask[0, 5:27] = 1                        # left pads, ends inside page 4
    mask[0, 13] = 0
    mask[1, :48] = 1
    live = jnp.asarray([4, 6, 0])
    got = paged_latent_decode_attention(
        q, pool, jnp.int32(1), jnp.asarray(table), live, jnp.asarray(mask),
        kd.softmax_scale, rank)
    rows = pool[1][jnp.asarray(table)].reshape(3, pmax * page, width)
    want = latent.attend_entries(q[:, None], rows, jnp.asarray(mask)[:, None] > 0,
                                 kd)[:, 0]
    assert got.shape == (3, H, rank) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got[:2], np.float32),
                               np.asarray(want[:2], np.float32), atol=tol,
                               rtol=tol)
    assert not np.asarray(got[2], np.float32).any()


def test_the_sixteen_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """A.X-K1's deployment at a tiny width: 192 experts over sixteen chips
    of twelve, top-8, scaled 2.5, the shared expert counted once: the sum of
    what `moe_block` computes for each share is the uncut reference's layer
    (guide §4)."""
    model = {**mla_tiny.MODEL, "n_routed_experts": 192, "router_experts": 192,
             "expert_offset": 0, "num_experts_per_tok": 8}
    layer = mla_tiny.weights.make_layer(mla_tiny.SEED, 2, model, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32), jnp.float32)
    dm = mla_tiny.reference.dims(model)
    hidden = mla_tiny.reference.rms_norm(x, layer["post_norm"], dm["eps"])
    want = mla_tiny.reference.moe_layer(layer["moe"], hidden, dm, "float32")
    valid = jnp.ones(x.shape[:2], bool)
    total, here = jnp.zeros_like(x), 0
    for lo in range(0, 192, 12):
        cfg = mla_tiny.config({**model, "n_routed_experts": 12,
                               "expert_offset": lo})
        cut = lambda name: layer["moe"][name][lo:lo + 12]
        moe = {"post_norm": layer["post_norm"], **layer["moe"],
               "gate": cut("gate"), "up": cut("up"), "down": cut("down")}
        out, counters = hybrid_tiny.moe_block_alone(moe, x, valid, cfg,
                                                    shared=lo == 0)
        total = total + (out - x)
        here += int(counters[1])
        assert int(counters[0]) == x.shape[0] * x.shape[1] * 8
    assert here == x.shape[0] * x.shape[1] * 8   # every assignment, once
    np.testing.assert_allclose(total, want, atol=TOL)


def _mla_prefill_logits(model=None, **cfg_kw):
    model = model or mla_tiny.MODEL
    cfg = mla_tiny.config(model, **cfg_kw)
    params = mla_tiny.weights.make_program_weights(mla_tiny.SEED,
                                                   mla_tiny.MODEL, jnp.float32)
    ids = np.random.default_rng(5).integers(0, 128, (1, 32)).astype(np.int32)
    out = decode.prefill_prompt(params, jnp.asarray(ids),
                                jnp.ones((1, 32), jnp.int32), cfg, 32)
    return ids, np.asarray(out["logits"][0]), out


def _mla_reference_logits(ids, model=None, alter=()):
    top = mla_tiny.weights.make_top(mla_tiny.SEED, mla_tiny.MODEL, jnp.float32)
    layer_fn = mla_tiny.weights.layer_fn(mla_tiny.SEED, mla_tiny.MODEL,
                                         jnp.float32)
    return np.asarray(mla_tiny.reference.logits_fn(
        top, layer_fn, jnp.asarray(ids), model or mla_tiny.MODEL,
        alter=alter)[0, -1])


def test_the_model_of_one_kind_of_layer_is_the_reference():
    ids, got, out = _mla_prefill_logits()
    np.testing.assert_allclose(got, _mla_reference_logits(ids), atol=1e-4)
    # 32 tokens x 4 experts x 4 expert layers; 32 * 33 / 2 pairs x 5 layers
    assert int(out["counters"][0]) == 32 * 4 * 4
    assert int(out["counters"][-1]) == 5 * 32 * 33 // 2
    assert out["selection"] == () and set(out["cache"]) == {"latent"}


MLA_ALTERATIONS = {
    "yarn_factor": ({"rope_scaling": {**mla_tiny.MODEL["rope_scaling"],
                                      "factor": 2}}, ()),
    "yarn_softmax_scale": ({}, ("plain_scale",)),
    "yarn_amplitude": ({"rope_scaling": {**mla_tiny.MODEL["rope_scaling"],
                                         "mscale": 0.5}}, ()),
    "rope_theta": ({"rope_theta": 1000}, ()),
    "scaling_factor": ({"routed_scaling_factor": 1.0}, ()),
    "held_range": ({"expert_offset": 5}, ()),
}


@pytest.mark.parametrize("what", sorted(MLA_ALTERATIONS))
def test_every_term_of_the_one_kind_model_matters_under_the_seeded_draw(what):
    changed, alter = MLA_ALTERATIONS[what]
    ids, got, _ = _mla_prefill_logits()
    altered = _mla_reference_logits(ids, {**mla_tiny.MODEL, **changed}, alter)
    assert np.max(np.abs(got - altered)) > 100 * 1e-4, what
