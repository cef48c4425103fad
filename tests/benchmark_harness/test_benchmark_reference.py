"""The plain reference against the program at a tiny size in float32, and the
control: the same mathematics in float8 must not pass for it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import registry, weights
from benchmark.reference import dense_decoder
from benchmark_tiny import TINY_MODEL

MODEL = {**TINY_MODEL, "num_hidden_layers": 2}


def _program_config():
    from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig

    return LlamaConfig(**{k: MODEL[k] for k in registry.LLAMA_CONFIG_KEYS},
                       dtype=jnp.float32)


@pytest.mark.parametrize("seed", [0, 42, 32748])
def test_seeded_weights_are_the_ones_the_trainer_starts_from(seed):
    from llama_pipeline_parallel_tpu.models.llama import model as program

    ours = weights.make_weights(seed, MODEL)
    cfg = _program_config()
    # jitted, as the trainer makes them (train_step.init_params_sharded)
    theirs = jax.jit(lambda key: program.init_params(key, cfg))(
        jax.random.PRNGKey(seed))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_weights_refuse_a_seed_the_key_cannot_hold():
    with pytest.raises(ValueError, match="outside"):
        weights.make_weights(2 ** 32, MODEL)


def test_reference_logits_match_the_programs_forward():
    from llama_pipeline_parallel_tpu.models.llama import model as program

    params = weights.make_weights(3, MODEL)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    ref = dense_decoder.logits_fn(params, ids, MODEL)
    prog = program.forward(params, ids, cfg=_program_config())
    # both float32; the program's attention and norm order differ in rounding
    np.testing.assert_allclose(np.asarray(ref), np.asarray(prog),
                               rtol=2e-4, atol=2e-5)


def test_reference_loss_matches_the_programs_loss():
    from llama_pipeline_parallel_tpu.models.llama import model as program

    params = weights.make_weights(5, MODEL)
    ids = jax.random.randint(jax.random.PRNGKey(2), (3, 16), 0, 256)
    ref = dense_decoder.mean_next_token_loss(params, ids, MODEL)
    prog = program.loss_fn(program.forward(params, ids, cfg=_program_config()),
                           ids)
    assert float(ref) == pytest.approx(float(prog), abs=2e-6)


HP = {"learning_rate": 1e-3, "weight_decay": 0.001, "adam_beta1": 0.9,
      "adam_beta2": 0.99, "adam_eps": 1e-8, "max_grad_norm": 5.0,
      "total_steps": 1e6}


def test_reference_adamw_matches_optax_chain():
    import optax

    rows = [jax.random.randint(jax.random.PRNGKey(i), (2, 16), 0, 256)
            for i in range(3)]
    got = dense_decoder.follow_training(
        weights.make_weights(9, MODEL), rows, MODEL, HP, groups=2)
    params = weights.make_weights(9, MODEL)
    tx = optax.chain(optax.clip_by_global_norm(5.0), optax.adamw(
        optax.linear_schedule(1e-3, 0.0, 1_000_000), b1=0.9, b2=0.99,
        eps=1e-8, weight_decay=0.001))
    state = tx.init(params)
    for ids, step in zip(rows, got):
        loss, grads = jax.value_and_grad(dense_decoder.mean_next_token_loss)(
            params, ids, MODEL)
        updates, state = tx.update(grads, state, params)
        assert step["loss"] == pytest.approx(float(loss), rel=1e-5)
        assert step["grad_norm"] == pytest.approx(
            float(optax.global_norm(grads)), rel=1e-4)
        per_layer = [float(jnp.sqrt(sum(
            jnp.sum(jnp.square(leaf[i]))
            for leaf in jax.tree.leaves(updates["layers"])))) for i in (0, 1)]
        assert step["update_norm_per_stage"] == pytest.approx(per_layer,
                                                              rel=1e-4)
        params = optax.apply_updates(params, updates)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_float8_training_does_not_pass_for_the_reference(seed):
    """The control: the reference itself in float8 fails the comparison that
    the float32 program passes (limits as in the tiny cell's file)."""
    import benchmark_tiny

    job = registry.load_job(benchmark_tiny.REPO, "train")
    rows = [jax.random.randint(jax.random.PRNGKey(10 * seed + i), (4, 32), 0,
                               256) for i in range(3)]
    sound = dense_decoder.follow_training(
        weights.make_weights(seed, MODEL), rows, MODEL, HP, groups=1)
    control = dense_decoder.follow_training(
        weights.make_weights(seed, MODEL), rows, MODEL, HP, groups=1,
        precision="fp8")
    checks = job.compare(control, sound, benchmark_tiny.LOOSE_CHECKS)
    assert not all(c.ok for c in checks)
    assert all(c.ok for c in job.compare(sound, sound,
                                         benchmark_tiny.LOOSE_CHECKS))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_float8_serving_reads_a_gap_the_reference_does_not(seed):
    params = weights.make_weights(seed, MODEL)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, 256, 12).tolist()
    # greedy continuation by the reference itself: gaps are exactly 0
    served = []
    for _ in range(8):
        ids = jnp.asarray([prompt + served], jnp.int32)
        served.append(int(jnp.argmax(
            dense_decoder.logits_fn(params, ids, MODEL)[0, -1])))
    sound = dense_decoder.served_token_gaps(params, prompt, served, MODEL, 24)
    control = dense_decoder.served_token_gaps(params, prompt, served, MODEL,
                                              24, precision="fp8")
    assert max(sound) == 0.0 and len(sound) == 8
    assert all(g >= 0.0 for g in control)


def test_served_token_gap_sees_an_altered_token():
    params = weights.make_weights(4, MODEL)
    prompt, served = list(range(10)), [3, 200, 17]
    gaps = dense_decoder.served_token_gaps(params, prompt, served, MODEL, 16)
    logits = dense_decoder.logits_fn(
        params, jnp.asarray([prompt + served], jnp.int32), MODEL)[0]
    for i, tok in enumerate(served):
        row = logits[len(prompt) - 1 + i]
        assert gaps[i] == pytest.approx(float(row.max() - row[tok]), abs=1e-5)
    with pytest.raises(ValueError, match="exceed"):
        dense_decoder.served_token_gaps(params, prompt, served, MODEL, 12)
