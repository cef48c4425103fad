"""ops/ssm_state_step.py: the decode tick's step of the Mamba-2 recurrence on
a store's rows in place, interpreted on the CPU, against the plain formula it
took the place of (what `models/ssm_moe/model.ssm_step` was until PR 46).

Tolerances: both sides are float32 and form the same products; the kernel
sums over N on the matrix unit (another order of the same float32 sum) and
XLA:CPU may contract a multiply and an add. 1e-5 on values of order 10 is a
few ulps. What the kernel must NOT touch is compared bit for bit: a row with
dt = 0 and every other layer of the store.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.ops import ssm_state_step as kernel

TOL = dict(rtol=1e-5, atol=1e-5)
LAYERS, SLOTS, P, N = 3, 4, 8, 16
INACTIVE = (1, 3)


def _formula(x, dt, A, B, C, state):
    """One position of every row. x: [b, H, P]; dt: [b, H]; A: [H]; B, C:
    [b, G, N]; state: [b, H, P, N]. A head reads its group's B and C by
    shape."""
    b, H, _ = x.shape
    G = B.shape[1]
    grouped = lambda a: a.reshape(b, G, H // G, *a.shape[2:])
    decay = grouped(jnp.exp(dt * A))[..., None, None]
    xdt = grouped(x * dt[..., None])[..., None]
    state = decay * grouped(state) + xdt * B[:, :, None, None, :]
    y = jnp.sum(state * C[:, :, None, None, :], axis=-1)
    return y.reshape(x.shape), state.reshape(b, H, *state.shape[3:])


def _draw(seed, heads, groups):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = rng.uniform(0.001, 0.5, (SLOTS, heads)).astype(np.float32)
    dt[list(INACTIVE)] = 0.0                 # rows that are not decoding
    return (normal(LAYERS, SLOTS, heads, P, N),
            (normal(SLOTS, heads, P), jnp.asarray(dt),
             -jnp.asarray(rng.uniform(1.0, 16.0, heads), jnp.float32),
             normal(SLOTS, groups, N), normal(SLOTS, groups, N)))


step = jax.jit(kernel.ssm_state_step, static_argnums=(1, 7),
               donate_argnums=0)


@pytest.mark.parametrize("index", [0, 2])
@pytest.mark.parametrize("block_groups", [1, 2])
@pytest.mark.parametrize("per_group", [1, 16])
def test_a_step_in_place_is_the_formula(per_group, block_groups, index):
    """Heads a group 1 and 16, blocks of one and of two groups, two layers of
    a three-layer store, two rows of four not decoding."""
    groups = 2
    heads = per_group * groups
    store, args = _draw(per_group * 100 + block_groups * 10 + index, heads,
                        groups)
    before = np.asarray(store)
    want_y, want_s = _formula(*args, store[index])
    y, after = step(jnp.array(store), index, *args, per_group * block_groups)
    np.testing.assert_allclose(y, want_y, **TOL)
    np.testing.assert_allclose(after[index], want_s, **TOL)
    after = np.asarray(after)
    active = [s for s in range(SLOTS) if s not in INACTIVE]
    assert (after[index, active] != before[index, active]).any()
    # bit for bit: the rows with dt = 0 and every other layer
    np.testing.assert_array_equal(after[index, list(INACTIVE)],
                                  before[index, list(INACTIVE)])
    others = [i for i in range(LAYERS) if i != index]
    np.testing.assert_array_equal(after[others], before[others])


@pytest.mark.parametrize("block_groups", [1, 2])
def test_a_second_step_starts_from_the_first_steps_store(block_groups):
    """The store the kernel returns IS the store: two steps through it equal
    two steps of the formula, and a step of another layer between them
    changes nothing of this one."""
    store, first = _draw(7, 8, 2)
    _, second = _draw(8, 8, 2)
    y1, s1 = _formula(*first, store[1])
    y2, s2 = _formula(*second, s1)
    got1, store = step(store, 1, *first, 4 * block_groups)
    _, store = step(store, 0, *second, 4 * block_groups)
    got2, store = step(store, 1, *second, 4 * block_groups)
    np.testing.assert_allclose(got1, y1, **TOL)
    np.testing.assert_allclose(got2, y2, **TOL)
    np.testing.assert_allclose(store[1], s2, **TOL)


def test_the_block_is_whole_groups_under_the_budget():
    """The served shape (128 heads in 8 groups, [64, 128] a head: 32 KB)
    takes the most groups that fit; a head too large for the budget still
    gets one group; a pinned block that is not whole groups is refused."""
    per = kernel._BLOCK_BYTES // (64 * 128 * 4)
    assert kernel.head_block(128, 8, 64, 128) == min(128, per)
    assert kernel.head_block(128, 8, 64, 128) % 16 == 0
    assert kernel.head_block(6, 3, 64, 128) == 6     # 4 heads: not a divisor
    assert kernel.head_block(32, 2, 1024, 1024) == 16
    store, args = _draw(3, 8, 2)
    with pytest.raises(ValueError, match="whole groups"):
        kernel.ssm_state_step(store, 0, *args, block_heads=6)
    with pytest.raises(ValueError, match="do not belong together"):
        kernel.ssm_state_step(store.astype(jnp.bfloat16), 0, *args)
