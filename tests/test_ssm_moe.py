"""The state-space / expert block's layers (models/ssm_moe/model.py) against
the plain reference (benchmark/reference/ssm_moe_decoder.py) at a tiny size,
float32 on the CPU. Tolerances: both sides compute in float32 and differ only
in the order of their sums (the chunked form against the token-by-token
recurrence, a sorted grouped product against a loop over experts), so 1e-4
absolute on values of order 1 is loose by two orders of magnitude; an
alteration of any term moves the logits by more than 1e-2."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ssm_tiny as tiny
from llama_pipeline_parallel_tpu.models.ssm_moe import decode as ssm_decode
from llama_pipeline_parallel_tpu.models.ssm_moe import model as ssm
from llama_pipeline_parallel_tpu.models.ssm_moe.config import SsmMoEConfig
from llama_pipeline_parallel_tpu.ops.ssm_state_step import ssm_state_step

TOL = 1e-4


# -- the recurrence -------------------------------------------------------------

def _sequential(x, dt, A, B, C, state):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t, in float64
    numpy, straight from the definition, a head beside its group's B, C."""
    x, dt, A, B, C, state = (np.asarray(a, np.float64)
                             for a in (x, dt, A, B, C, state))
    b, s, H, P = x.shape
    per = H // B.shape[2]
    y = np.zeros(x.shape)
    for t in range(s):
        for i in range(b):
            for h in range(H):
                S = np.exp(dt[i, t, h] * A[h]) * state[i, h] + np.outer(
                    dt[i, t, h] * x[i, t, h], B[i, t, h // per])
                state[i, h] = S
                y[i, t, h] = S @ C[i, t, h // per]
    return y, state


@jax.jit
def _step(x, dt, A, B, C, state):
    """One position through the decode tick's kernel (interpreted here), on
    a store of one layer: (y, the rows' new state)."""
    y, store = ssm_state_step(state[None], 0, x, dt, A, B, C)
    return y, store[0]


def _draw(rng, b, s, H=4, P=8, G=2, N=8, decay=1.0):
    normal = lambda *shape: rng.normal(size=shape).astype(np.float32)
    return (normal(b, s, H, P),
            rng.uniform(0.001, 0.5, (b, s, H)).astype(np.float32),
            -decay * rng.uniform(1.0, 16.0, H).astype(np.float32),
            normal(b, s, G, N), normal(b, s, G, N), normal(b, H, P, N))


@pytest.mark.parametrize("chunk,length", [
    (4, 12), (8, 24), (16, 16), (32, 37), (128, 128), (128, 5), (128, 130),
    (8, 9)])
def test_chunked_scan_is_the_sequential_recurrence(chunk, length):
    """Also at lengths that are not a whole number of chunks: the sequence is
    padded on the left with steps that leave the state alone."""
    rng = np.random.default_rng(chunk * 1000 + length)
    x, dt, A, B, C, state = _draw(rng, 2, length)
    want_y, want_s = _sequential(x, dt, A, B, C, state.copy())
    got_y, got_s = ssm.ssm_chunked(*(jnp.asarray(a) for a in (
        x, dt, A, B, C, state)), chunk=chunk)
    np.testing.assert_allclose(got_y, want_y, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)


def test_chunked_scan_survives_decays_that_underflow_a_quotient():
    """exp(-cs) over a chunk of 128 at dt A = -40 a step would be exp(5120):
    every exponent the chunked form takes is <= 0, so nothing overflows and
    the result is the recurrence's."""
    rng = np.random.default_rng(7)
    x, dt, A, B, C, state = _draw(rng, 1, 128, decay=5.0)
    want_y, want_s = _sequential(x, dt, A, B, C, state.copy())
    got_y, got_s = ssm.ssm_chunked(*(jnp.asarray(a) for a in (
        x, dt, A, B, C, state)), chunk=128)
    assert np.isfinite(np.asarray(got_y)).all()
    np.testing.assert_allclose(got_y, want_y, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)


def test_one_step_form_is_the_recurrence():
    rng = np.random.default_rng(11)
    x, dt, A, B, C, state = _draw(rng, 3, 6)
    want_y, want_s = _sequential(x, dt, A, B, C, state.copy())
    s = jnp.asarray(state)
    for t in range(6):
        y, s = _step(x[:, t], dt[:, t], A, B[:, t], C[:, t], s)
        np.testing.assert_allclose(y, want_y[:, t], atol=TOL)
    np.testing.assert_allclose(s, want_s, atol=TOL)


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunked_scan_is_the_step_form_at_any_length(chunk):
    """The two forms the serving programs use, against each other, from a
    state that is not zero, at lengths on both sides of a chunk's edge."""
    rng = np.random.default_rng(chunk)
    for length in (chunk - 1, chunk + 1, 2 * chunk + 3):
        x, dt, A, B, C, state = (jnp.asarray(a)
                                 for a in _draw(rng, 2, length))
        got_y, got_s = ssm.ssm_chunked(x, dt, A, B, C, state, chunk=chunk)
        s = state
        for t in range(length):
            y, s = _step(x[:, t], dt[:, t], A, B[:, t], C[:, t], s)
            np.testing.assert_allclose(got_y[:, t], y, atol=TOL)
        np.testing.assert_allclose(got_s, s, atol=TOL)


def test_positions_that_are_not_valid_leave_the_state_alone():
    """dt = 0 in front of a sequence (left padding): the state and the
    outputs after it are those of the sequence alone, whatever x, B, C hold
    there."""
    rng = np.random.default_rng(13)
    x, dt, A, B, C, state = _draw(rng, 1, 20)
    dt[:, :9] = 0.0
    args = lambda lo: (jnp.asarray(x[:, lo:]), jnp.asarray(dt[:, lo:]),
                       jnp.asarray(A), jnp.asarray(B[:, lo:]),
                       jnp.asarray(C[:, lo:]), jnp.asarray(state))
    y_pad, s_pad = ssm.ssm_chunked(*args(0), chunk=8)
    y, s = ssm.ssm_chunked(*args(9), chunk=8)
    np.testing.assert_allclose(y_pad[:, 9:], y, atol=TOL)
    np.testing.assert_allclose(s_pad, s, atol=TOL)


def test_the_convolution_carries_its_last_inputs_and_its_bias():
    """A sequence convolved in two pieces, the second from the first's
    history, is the sequence convolved whole; the bias is in the result."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 11, 6)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    zeros = jnp.zeros((2, 3, 6), jnp.float32)
    whole, last = ssm.conv_bias_silu(x, zeros, taps, bias)
    want = jax.nn.silu(tiny.reference.causal_conv(x, taps, bias))
    np.testing.assert_allclose(whole, want, atol=1e-6)
    first, history = ssm.conv_bias_silu(x[:, :7], zeros, taps, bias)
    second, final = ssm.conv_bias_silu(x[:, 7:], history, taps, bias)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(final), np.asarray(last))
    np.testing.assert_array_equal(np.asarray(last), np.asarray(x[:, -3:]))
    unbiased, _ = ssm.conv_bias_silu(x, zeros, taps, jnp.zeros_like(bias))
    assert np.abs(np.asarray(unbiased - whole)).max() > 0.1


# -- the expert layer ----------------------------------------------------------

def _uncut_experts(seed=5, tokens=24):
    """An uncut expert layer (all 16 experts) and a batch of inputs."""
    model = {**tiny.MODEL, "n_routed_experts": 16, "expert_offset": 0}
    layer = tiny.weights.make_layer(seed, 1, model, jnp.float32)
    assert "router" in layer
    x = jnp.asarray(np.random.default_rng(seed).normal(
        size=(2, tokens // 2, model["hidden_size"])), jnp.float32)
    return model, layer, x


def _share(layer, lo, n):
    return {**layer, "up": layer["up"][lo:lo + n],
            "down": layer["down"][lo:lo + n]}


def _reference_experts(layer, x, model, **kw):
    dm = tiny.reference.dims(model)
    hidden = tiny.reference.rms_norm(x, layer["post_norm"], dm["eps"])
    return tiny.reference.latent_moe(layer, hidden, dm, "float32", **kw)


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """THE SHARE TEST (guide §4). Four chips of four experts each
    (`expert_offset` 0, 4, 8, 12 of the router's 16), the shared expert
    counted once: the sum of what they compute is the uncut reference's
    layer. The up-projection out of the latent width is linear, so the
    shares' latent sums may each go up on their own chip."""
    model, layer, x = _uncut_experts()
    want = _reference_experts(layer, x, model)
    valid = jnp.ones(x.shape[:2], bool)
    total = jnp.zeros_like(x)
    here = 0
    for lo in range(0, 16, 4):
        cfg = tiny.config({**model, "n_routed_experts": 4, "router_experts": 16,
                           "expert_offset": lo})
        out, counters = ssm.latent_moe_block(_share(layer, lo, 4), x, valid,
                                             cfg, shared=lo == 0)
        total = total + (out - x)
        here += int(counters[1])
        assert int(counters[0]) == x.shape[0] * x.shape[1] * 4
        # and a share alone is the reference's share
        got = _reference_experts(
            _share(layer, lo, 4), x,
            {**model, "n_routed_experts": 4, "expert_offset": lo},
            shared=lo == 0)
        np.testing.assert_allclose(out - x, got, atol=TOL)
    assert here == x.shape[0] * x.shape[1] * 4   # every assignment, once
    np.testing.assert_allclose(total, want, atol=TOL)


def test_every_token_on_one_held_expert_loses_none():
    model, layer, x = _uncut_experts()
    model = {**model, "n_routed_experts": 4, "expert_offset": 4}
    bias = np.zeros(16, np.float32)
    bias[[5, 0, 1, 2]] = 10.0
    layer = {**_share(layer, 4, 4), "router_bias": jnp.asarray(bias)}
    want = _reference_experts(layer, x, model)
    out, counters = ssm.latent_moe_block(
        layer, x, jnp.ones(x.shape[:2], bool), tiny.config(model))
    tokens = x.shape[0] * x.shape[1]
    # one expert's run in one row tile: the product visits one pair
    assert counters.tolist() == [tokens * 4, tokens, 1, tokens, 4, 1]
    np.testing.assert_allclose(out - x, want, atol=TOL)


def test_positions_that_are_not_valid_are_routed_nowhere():
    model, layer, x = _uncut_experts()
    valid = jnp.ones(x.shape[:2], bool).at[:, :3].set(False)
    _, counters = ssm.latent_moe_block(layer, x, valid, tiny.config(model))
    live = int(valid.sum())
    assert counters[0] == counters[1] == live * 4


def test_what_the_product_never_wrote_reaches_no_token(monkeypatch):
    """The rows past the last group are never written by the grouped product
    and relu^2 of what lies there may be anything: NaN written into them
    after each product does not move the layer's output."""
    from llama_pipeline_parallel_tpu.ops import grouped_matmul as gm

    model, layer, x = _uncut_experts()
    model = {**model, "n_routed_experts": 4, "expert_offset": 4}
    cfg, layer = tiny.config(model), _share(layer, 4, 4)
    valid = jnp.ones(x.shape[:2], bool)
    want, _ = ssm.latent_moe_block(layer, x, valid, cfg)
    real = gm.grouped_matmul

    def poisoned(lhs, rhs, meta):
        out = real(lhs, rhs, meta)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < meta.offsets[-1], out, jnp.nan)

    monkeypatch.setattr(ssm, "grouped_matmul", poisoned)
    got, _ = ssm.latent_moe_block(layer, x, valid, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_experts_stored_in_another_dtype_than_the_programs_are_refused():
    model, layer, x = _uncut_experts()
    stored = {**layer, "up": layer["up"].astype(jnp.bfloat16)}
    with pytest.raises(ValueError, match="stored bfloat16"):
        ssm.latent_moe_block(stored, x, jnp.ones(x.shape[:2], bool),
                             tiny.config(model))


# -- the configuration ------------------------------------------------------------

def test_the_configuration_reads_the_published_keys():
    cfg = tiny.config()
    assert cfg.family == "ssm_moe" and cfg.pattern == "MEM*EME"
    assert (cfg.recurrent_layers, cfg.kv_cache_layers, cfg.expert_layers) == (
        3, 1, 3)
    assert [cfg.kind_index(i) for i in range(7)] == [0, 0, 1, 0, 1, 2, 2]
    assert cfg.ssm_inner == 64 and cfg.ssm_conv_width == 64 + 2 * 2 * 8
    assert cfg.held == 8 and cfg.router_experts == 16
    assert cfg.shared_intermediate_size == 48 and cfg.rms_norm_eps == 1e-5


@pytest.mark.parametrize("change,named", [
    ({"hybrid_override_pattern": "MEM*EM"}, "letters"),
    ({"hybrid_override_pattern": "MEM*EMX"}, "one of"),
    ({"mamba_num_heads": 6}, "expand"),
    ({"mlp_hidden_act": "silu"}, "relu"),
    ({"n_group": 2}, "one group"),
    ({"mlp_bias": True}, "bias"),
    ({"expert_offset": 12}, "outside the router"),
    ({"num_key_value_heads": 3}, "multiple"),
])
def test_a_configuration_of_another_shape_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        tiny.config({**tiny.MODEL, **change})


def test_init_params_draws_the_tree_the_benchmark_weights_have():
    """The program's own draw (`init_params`, for checkpoints and tests) and
    the benchmark's seeded weights are one tree: same leaves, shapes and
    dtypes, the float32 ones among them."""
    cfg = tiny.config()
    own = jax.eval_shape(lambda: ssm.init_params(jax.random.PRNGKey(0), cfg))
    theirs = jax.eval_shape(lambda: tiny.weights.make_program_weights(
        tiny.SEED, tiny.MODEL, jnp.float32))
    assert jax.tree.structure(own) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    bf16 = ssm.init_params(jax.random.PRNGKey(0), SsmMoEConfig.tiny(
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    mamba, experts = bf16["layers"][0], bf16["layers"][1]
    for name in ("A_log", "D", "dt_bias"):
        assert mamba[name].dtype == jnp.float32, name
    assert experts["router"].dtype == experts["router_bias"].dtype == jnp.float32
    assert mamba["in_proj"].dtype == experts["up"].dtype == jnp.bfloat16


# -- the whole block ------------------------------------------------------------

def _prefill(params, cfg, prompt, bucket):
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    return ssm_decode.prefill_prompt(params, jnp.asarray(ids),
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
    out = _prefill(params, tiny.config(), prompt, bucket)
    np.testing.assert_allclose(out["logits"][0], want, atol=TOL)
    assert int(out["next_pos"][0]) == len(prompt)
    # 3 expert layers x 4 experts a token and 3 state-space layers, of the
    # prompt's tokens alone
    assert int(out["counters"][0]) == len(prompt) * 4 * 3
    assert int(out["counters"][6]) == len(prompt) * 3


def test_left_padding_leaves_the_stores_of_the_unpadded_prompt(sound):
    params, prompt, _ = sound
    cfg = tiny.config()
    bare = _prefill(params, cfg, prompt, len(prompt))["cache"]
    padded = _prefill(params, cfg, prompt, 32)["cache"]
    np.testing.assert_allclose(padded["state"], bare["state"], atol=TOL)
    np.testing.assert_allclose(padded["conv"], bare["conv"], atol=TOL)
    np.testing.assert_allclose(padded["k"][:, :, 32 - len(prompt):],
                               bare["k"], atol=TOL)


def _altered(params, what):
    """The program's parameters with one term of the mathematics changed."""
    change = {
        "decay": ("in_proj", lambda l: {**l, "A_log": l["A_log"] + 1.5}),
        "dt bias": ("in_proj", lambda l: {**l, "dt_bias": l["dt_bias"] + 2.0}),
        "skip": ("in_proj", lambda l: {**l, "D": 0 * l["D"]}),
        "conv": ("in_proj", lambda l: {**l, "conv_w": l["conv_w"][::-1]}),
        "conv bias": ("in_proj", lambda l: {**l, "conv_b": 0 * l["conv_b"]}),
        "gate norm": ("in_proj",
                      lambda l: {**l, "gate_norm": 2 * l["gate_norm"]}),
        "latent": ("router",
                   lambda l: {**l, "latent_out": -l["latent_out"]}),
        "shared": ("router", lambda l: {**l, "shared_up": -l["shared_up"]}),
        "softmax": ("wq", lambda l: {**l, "wv": -l["wv"]}),
    }
    mark, alter = change[what]
    return {**params, "layers": [alter(layer) if mark in layer else layer
                                 for layer in params["layers"]]}


@pytest.mark.parametrize("what", [
    "decay", "dt bias", "skip", "conv", "conv bias", "gate norm", "latent",
    "shared", "softmax", "held range", "pattern"])
def test_every_term_matters_under_the_seeded_draw(sound, what):
    """Each of the decay, the step's bias, the skip, the convolution and its
    bias, the grouped norm, the routed and the shared experts, the softmax
    layer, the held range and the order of the layers moves the logits far
    outside the tolerance the sound comparison keeps: none could be left out
    unnoticed."""
    params, prompt, want = sound
    cfg = tiny.config()
    if what == "held range":
        cfg = tiny.config({**tiny.MODEL, "expert_offset": 5})
    elif what == "pattern":
        # the same leaves, the first two state-space layers changing places
        layers = list(params["layers"])
        layers[0], layers[2] = layers[2], layers[0]
        params = {**params, "layers": layers}
    else:
        params = _altered(params, what)
    got = _prefill(params, cfg, prompt, 16)["logits"][0]
    assert np.abs(np.asarray(got) - want).max() > 100 * TOL
