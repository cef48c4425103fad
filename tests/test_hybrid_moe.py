"""The hybrid block's layers (models/hybrid_moe/model.py) against the plain
reference (benchmark/reference/hybrid_moe_decoder.py) at a tiny size, float32
on the CPU. Tolerances: both sides compute in float32 and differ only in the
order of their sums (the chunked form against the token-by-token recurrence,
a sorted grouped product against a loop over experts), so 1e-4 absolute on
values of order 1 is loose by two orders of magnitude; an alteration of any
term moves the logits by more than 1e-2."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_tiny as tiny
from llama_pipeline_parallel_tpu.models.hybrid_moe import decode as hybrid_decode
from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.ops.grouped_matmul import row_tile

TOL = 1e-4


def _sequential(q, k, v, g, beta, state):
    """S_t = (I - b k k^T) diag(a) S_{t-1} + b k v^T; o_t = S_t^T q_t, in
    float64 numpy, straight from the definition."""
    q, k, v, g, beta, state = (np.asarray(x, np.float64)
                               for x in (q, k, v, g, beta, state))
    b, s, H, dk = q.shape
    out = np.zeros(v.shape)
    for t in range(s):
        for i in range(b):
            for h in range(H):
                kk = k[i, t, h]
                S = np.exp(g[i, t, h])[:, None] * state[i, h]
                S = S - beta[i, t, h] * np.outer(kk, kk @ S) \
                    + beta[i, t, h] * np.outer(kk, v[i, t, h])
                state[i, h] = S
                out[i, t, h] = S.T @ q[i, t, h]
    return out, state


def _draw(rng, b, s, H, dk, decay=1.0):
    normal = lambda *shape: rng.normal(size=shape).astype(np.float32)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(normal(b, s, H, dk)) * dk ** -0.5, unit(normal(b, s, H, dk)),
            normal(b, s, H, dk),
            -decay * rng.uniform(0.001, 1.0, (b, s, H, dk)).astype(np.float32),
            rng.uniform(0.0, 2.0, (b, s, H)).astype(np.float32),
            normal(b, H, dk, dk))


@pytest.mark.parametrize("chunk,length", [
    (4, 12), (8, 24), (16, 16), (32, 37), (64, 64), (64, 5), (64, 130)])
def test_chunked_kda_is_the_sequential_recurrence(chunk, length):
    rng = np.random.default_rng(chunk * 1000 + length)
    q, k, v, g, beta, state = _draw(rng, 2, length, 2, 8)
    want_o, want_s = _sequential(q, k, v, g, beta, state.copy())
    got_o, got_s = hybrid.kda_chunked(*(jnp.asarray(x) for x in (
        q, k, v, g, beta, state)), chunk=chunk)
    np.testing.assert_allclose(got_o, want_o, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)


@pytest.mark.parametrize("decay", [5.0, 40.0])
def test_chunked_kda_survives_decays_that_overflow_a_quotient(decay):
    """exp(-g) over a chunk of 64 would be exp(2560): every exponent the
    chunked form takes is <= 0, so nothing overflows and the result is the
    recurrence's."""
    rng = np.random.default_rng(7)
    q, k, v, g, beta, state = _draw(rng, 1, 64, 2, 8, decay=decay)
    want_o, want_s = _sequential(q, k, v, g, beta, state.copy())
    got_o, got_s = hybrid.kda_chunked(*(jnp.asarray(x) for x in (
        q, k, v, g, beta, state)))
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)


def test_one_step_form_is_the_recurrence():
    rng = np.random.default_rng(11)
    q, k, v, g, beta, state = _draw(rng, 3, 6, 2, 8)
    want_o, want_s = _sequential(q, k, v, g, beta, state.copy())
    s = jnp.asarray(state)
    for t in range(6):
        o, s = hybrid.kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        np.testing.assert_allclose(o, want_o[:, t], atol=TOL)
    np.testing.assert_allclose(s, want_s, atol=TOL)


def test_positions_that_are_not_valid_leave_the_state_alone():
    """b = 0, a = 1 in front of a sequence (left padding): the state and the
    outputs after it are those of the sequence alone, whatever q, k, v hold
    there."""
    rng = np.random.default_rng(13)
    q, k, v, g, beta, state = _draw(rng, 1, 20, 2, 8)
    g[:, :9], beta[:, :9] = 0.0, 0.0
    args = lambda lo: tuple(jnp.asarray(x[:, lo:]) for x in (q, k, v, g, beta))
    o_pad, s_pad = hybrid.kda_chunked(*args(0), jnp.asarray(state), chunk=8)
    o, s = hybrid.kda_chunked(*args(9), jnp.asarray(state), chunk=8)
    np.testing.assert_allclose(o_pad[:, 9:], o, atol=TOL)
    np.testing.assert_allclose(s_pad, s, atol=TOL)


# -- the expert layer ----------------------------------------------------------

def _uncut_moe(rng_seed=5, tokens=24):
    """An uncut expert layer (all 16 experts) in the reference's layout and
    a batch of inputs."""
    model = {**tiny.MODEL, "n_routed_experts": 16, "expert_offset": 0}
    layer = tiny.weights.make_layer(rng_seed, 1, model, jnp.float32)
    x = jnp.asarray(np.random.default_rng(rng_seed).normal(
        size=(2, tokens // 2, model["hidden_size"])), jnp.float32)
    return model, layer, x


def _share(moe, lo, n):
    cut = lambda name: moe[name][lo:lo + n]
    return {**moe, "gate": cut("gate"), "up": cut("up"), "down": cut("down")}


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Four chips of four experts each, the shared expert counted once: the
    sum of what they compute is the uncut reference's layer (guide §4)."""
    model, layer, x = _uncut_moe()
    dm = tiny.reference.dims(model)
    hidden = tiny.reference.rms_norm(x, layer["post_norm"], dm["eps"])
    want = tiny.reference.moe_layer(layer["moe"], hidden, dm, "float32")
    valid = jnp.ones(x.shape[:2], bool)
    total = jnp.zeros_like(x)
    here = 0
    for lo in range(0, 16, 4):
        cfg = tiny.config({**model, "n_routed_experts": 4, "router_experts": 16,
                           "expert_offset": lo})
        moe = {"post_norm": layer["post_norm"], **_share(layer["moe"], lo, 4)}
        out, counters = tiny.moe_block_alone(moe, x, valid, cfg,
                                             shared=lo == 0)
        total = total + (out - x)
        here += int(counters[1])
        assert int(counters[0]) == x.shape[0] * x.shape[1] * 4
    assert here == x.shape[0] * x.shape[1] * 4   # every assignment, once
    np.testing.assert_allclose(total, want, atol=TOL)


def test_every_token_on_one_held_expert_loses_none():
    """The worst skew: a selection bias sends every token to held expert 5
    (and to three experts that are not held). Its run is the whole batch;
    the result is the reference's."""
    model, layer, x = _uncut_moe()
    model = {**model, "n_routed_experts": 4, "expert_offset": 4}
    bias = np.zeros(16, np.float32)
    bias[[5, 0, 1, 2]] = 10.0
    moe = {**_share(layer["moe"], 4, 4), "router_bias": jnp.asarray(bias)}
    dm = tiny.reference.dims(model)
    hidden = tiny.reference.rms_norm(x, layer["post_norm"], dm["eps"])
    want = tiny.reference.moe_layer(moe, hidden, dm, "float32")
    cfg = tiny.config(model)
    out, counters = tiny.moe_block_alone(
        {"post_norm": layer["post_norm"], **moe}, x,
        jnp.ones(x.shape[:2], bool), cfg)
    tokens = x.shape[0] * x.shape[1]
    # one expert's run in one row tile: the product visits one pair
    assert counters.tolist() == [tokens * 4, tokens, 1, tokens, 4, 1]
    np.testing.assert_allclose(out - x, want, atol=TOL)


def test_positions_that_are_not_valid_are_routed_nowhere():
    model, layer, x = _uncut_moe()
    cfg = tiny.config(model)
    valid = jnp.ones(x.shape[:2], bool).at[:, :3].set(False)
    _, counters = tiny.moe_block_alone(
        {"post_norm": layer["post_norm"], **layer["moe"]}, x, valid, cfg)
    live = int(valid.sum())
    assert counters[0] == counters[1] == live * 4


@pytest.mark.parametrize("case", ["seeded", "idle", "one"])
@pytest.mark.parametrize("place", [0, 1, 2])
def test_a_layer_in_a_stack_of_three_is_the_layer_alone(place, case):
    """The grouped product takes the stack of every period's experts whole
    and gives the other periods' experts no rows: output and all six
    counters are, bit for bit, those of the layer's own leaves as a stack of
    one (the product visits the experts that have a row, wherever in the
    stack they lie). Among the cases a held expert that gets no row and
    every row on one expert; two positions are not valid."""
    cfg = tiny.config()
    layers = [tiny.weights.make_layer(tiny.SEED, index, tiny.MODEL, jnp.float32)
              for index in (1, 2, 3)]
    stack = {name: jnp.stack([layer["moe"][name] for layer in layers])
             for name in hybrid.EXPERT_LEAVES}
    assert stack["gate"].shape == (3, cfg.held, 32, 16)
    layer = layers[place]
    moe = tiny.biased({"post_norm": layer["post_norm"], **layer["moe"]}, case)
    x = jnp.asarray(np.random.default_rng(place).normal(size=(2, 12, 32)),
                    jnp.float32)
    valid = jnp.ones(x.shape[:2], bool).at[1, :2].set(False)
    block = jax.jit(hybrid.moe_block, static_argnames=("cfg",))   # place traced
    want, counted = block(moe, tiny.stack_of_one(moe), jnp.int32(0), x, valid,
                          cfg=cfg)
    got, counters = block(moe, stack, jnp.int32(place), x, valid, cfg=cfg)
    assert counters.tolist() == counted.tolist()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    live = int(valid.sum())
    assert counters[0] == live * 4 and counters[4] == cfg.held
    if case == "idle":
        assert counters[2] < cfg.held
    if case == "one":
        assert counters.tolist() == [live * 4, live, 1, live, cfg.held, 1]
    # an expert with a row is read once, and once more for each edge of a
    # row tile its run crosses; an expert without one never
    rows = x.shape[0] * x.shape[1] * 4
    tiles = rows // row_tile(rows)
    assert counters[2] <= counters[5] < counters[2] + tiles
    # and the neighbours' experts matter to nothing
    other = jax.tree.map(lambda a: a.at[(place + 1) % 3].set(7.0), stack)
    again, _ = block(moe, other, jnp.int32(place), x, valid, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(want))


def test_experts_stored_in_another_dtype_than_the_programs_are_refused():
    """A conversion inside the layer would convert every period's experts at
    every layer: a tree stored otherwise is refused by name."""
    model, layer, x = _uncut_moe()
    moe = {"post_norm": layer["post_norm"], **layer["moe"]}
    stored = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          tiny.stack_of_one(moe))
    with pytest.raises(ValueError, match="stored bfloat16"):
        hybrid.moe_block(moe, stored, 0, x, jnp.ones(x.shape[:2], bool),
                         tiny.config(model))


# -- the whole block ------------------------------------------------------------

def _prefill_logits(params, cfg, prompt, bucket):
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    return hybrid_decode.prefill_prompt(params, jnp.asarray(ids),
                                        jnp.asarray(mask), cfg, bucket)


@pytest.fixture(scope="module")
def sound():
    params, top, layer_fn = tiny.both_sides()
    prompt = np.random.default_rng(2).integers(0, 128, size=13).tolist()
    want = tiny.reference.logits_fn(top, layer_fn, jnp.asarray([prompt]),
                                    tiny.MODEL)[0, -1]
    return params, prompt, np.asarray(want)


@pytest.mark.parametrize("bucket", [13, 16, 32, 80])
def test_prefill_is_the_reference_whatever_the_left_padding(sound, bucket):
    params, prompt, want = sound
    out = _prefill_logits(params, tiny.config(), prompt, bucket)
    np.testing.assert_allclose(out["logits"][0], want, atol=TOL)
    assert int(out["next_pos"][0]) == len(prompt)
    # 8 layers x 4 experts a token, of the prompt's tokens alone
    assert int(out["counters"][0]) == len(prompt) * 4 * 8


def test_left_padding_leaves_the_stores_of_the_unpadded_prompt(sound):
    params, prompt, _ = sound
    cfg = tiny.config()
    bare = _prefill_logits(params, cfg, prompt, len(prompt))["cache"]
    padded = _prefill_logits(params, cfg, prompt, 32)["cache"]
    np.testing.assert_allclose(padded["state"], bare["state"], atol=TOL)
    np.testing.assert_allclose(padded["conv"], bare["conv"], atol=TOL)
    np.testing.assert_allclose(padded["k"][:, :, 32 - len(prompt):],
                               bare["k"], atol=TOL)


def _altered(params, what):
    """The program's parameters with one term of the mathematics changed."""
    periods = params["periods"]
    change = {
        "decay": lambda l: {**l, "A_log": l["A_log"] + 1.5},
        "b": lambda l: {**l, "wb": -l["wb"]},
        "conv": lambda l: {**l, "conv_k": l["conv_k"][:, ::-1]},
        "kda gate": lambda l: {**l, "wg2": jnp.zeros_like(l["wg2"])},
    }
    if what == "softmax gate":
        attn = {**periods["attn"], "wg": jnp.zeros_like(periods["attn"]["wg"])}
        return {**params, "periods": {**periods, "attn": attn}}
    kda = [change[what](layer) for layer in periods["kda"]]
    return {**params, "periods": {**periods, "kda": kda}}


@pytest.mark.parametrize("what", ["decay", "b", "conv", "kda gate",
                                  "softmax gate", "held range"])
def test_every_term_matters_under_the_seeded_draw(sound, what):
    """Each of the decay, b, the convolution, either gate and the held range
    moves the logits far outside the tolerance the sound comparison keeps:
    none could be left out unnoticed."""
    params, prompt, want = sound
    cfg = tiny.config()
    if what == "held range":
        cfg = tiny.config({**tiny.MODEL, "expert_offset": 5})
    else:
        params = _altered(params, what)
    got = _prefill_logits(params, cfg, prompt, 16)["logits"][0]
    assert np.abs(np.asarray(got) - want).max() > 100 * TOL
