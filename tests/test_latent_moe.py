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

import latent_tiny as tiny
from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.latent_moe import decode, model as latent
from llama_pipeline_parallel_tpu.models.latent_moe.config import LatentMoEConfig

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
        out, counters = hybrid.moe_block(moe, x, valid, cfg, shared=lo == 0)
        total = total + (out - x)
        here += int(counters[1])
        assert int(counters[0]) == x.shape[0] * x.shape[1] * 4
    assert here == x.shape[0] * x.shape[1] * 4   # every assignment, once
    np.testing.assert_allclose(total, want, atol=TOL)


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
