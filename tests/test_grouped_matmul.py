"""ops/grouped_matmul.py: the expert layer's grouped product, against what it
replaced (`jax.lax.ragged_dot`) on the same operands.

Tolerances, and where they come from. Both keep float32 accumulation and
round once to the operands' dtype. In float32 they differ by the order of
the sums alone (contraction blocks of `contraction_tile` against XLA's own
order): 1e-5 relative on results of order `sqrt(k) * 0.1`. In bfloat16 each
side rounds its float32 sum once, half an ulp (2^-9 relative), and the sums
differ by their order before that: one ulp of the result, rtol 2^-7, and
2^-7 absolute for results near zero.

Rows past the last group are never compared: the kernel does not write them
(they hold whatever the buffer held) and `moe_block` discards them, which
the last test holds against NaN there. Mosaic compiles the kernel for a
described v5e at the three expert cells' shapes in
tests/test_paged_attention.py, the one file that loads the TPU's library.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_tiny as tiny
from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.ops import grouped_matmul as gm

TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2 ** -7, atol=2 ** -7)}

# name: (m, k, n, group sizes, visits of one product)
CASES = {
    "one_group": (32, 64, 48, [32], 1),
    "every_row_in_one_expert": (32, 64, 48, [0, 32, 0, 0], 1),
    "an_expert_without_a_row": (32, 64, 48, [5, 0, 7, 3], 3),
    "fewer_rows_than_m": (64, 64, 48, [3, 2, 0, 4], 3),
    "no_group_has_a_row": (32, 64, 48, [0, 0, 0], 0),
    # 256 rows are two tiles of 128: the second group lies in both
    "a_group_straddling_a_row_tile": (256, 128, 128, [100, 60, 0, 50], 4),
    # every tile of a 384-row product has rows of the one long group
    "a_group_over_three_row_tiles": (384, 128, 128, [2, 380], 4),
    # widths no power-of-two tile divides: blocks of 640 and 768 rows
    "widths_1280_by_1536": (32, 1280, 1536, [9, 0, 20], 2),
    "widths_1536_by_1280": (32, 1536, 1280, [0, 17, 3], 2),
    # contractions that are no power of two, 3 x 128 and 21 x 128, beside
    # outputs 9 and 21 lanes of 128 wide (the latent experts' two products
    # are 1024 x 2688 and 2688 x 1024)
    "contraction_384_by_1152": (32, 384, 1152, [4, 0, 25], 2),
    "contraction_2688_by_1152": (32, 2688, 1152, [0, 30, 1], 2),
    "widths_1024_by_2688": (32, 1024, 2688, [9, 0, 20], 2),
    "widths_2688_by_1024": (32, 2688, 1024, [0, 17, 3], 2),
}


def _operands(m, k, n, groups, dtype, seed=0):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    rhs = jnp.asarray(rng.normal(size=(groups, k, n)) * 0.1, dtype)
    return lhs, rhs


@jax.jit
def _product(lhs, rhs, sizes):
    meta = gm.group_metadata(sizes, lhs.shape[0])
    return gm.grouped_matmul(lhs, rhs, meta), meta.visits


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_ragged_dot_on_the_rows_that_have_a_group(case, dtype):
    m, k, n, sizes, visits = CASES[case]
    lhs, rhs = _operands(m, k, n, len(sizes), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    got, visited = _product(lhs, rhs, sizes)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    real = int(sizes.sum())
    assert got.shape == (m, n) and got.dtype == dtype
    assert int(visited) == visits
    np.testing.assert_allclose(np.asarray(got[:real], np.float32),
                               np.asarray(want[:real], np.float32),
                               **TOL[dtype])


def test_a_blocked_contraction_is_the_whole_one(monkeypatch):
    """The contraction tile follows the shapes and moves no result beyond the
    order of its sums: 1280 in one block, in two of 640, in ten of 128."""
    m, k, n, sizes = 32, 1280, 256, jnp.asarray([11, 0, 14], jnp.int32)
    lhs, rhs = _operands(m, k, n, 3, jnp.float32)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    for block_bytes, tk in [(4 << 20, 1280), (640 * n * 4, 640), (1, 128)]:
        monkeypatch.setattr(gm, "_BLOCK_BYTES", block_bytes)
        assert gm.contraction_tile(k, n, 4) == tk
        meta = gm.group_metadata(sizes, m)
        got = gm.grouped_matmul(lhs, rhs, meta)
        np.testing.assert_allclose(got[:25], want[:25], rtol=1e-5, atol=1e-5)


def test_tiles_at_the_three_expert_cells_shapes():
    """One rule for the three cells: a 128-row tile whatever `T * k` is (a
    tick's 512 / 256 / 256 rows, a 2048-token unit's 16,384), the output
    never tiled, and contraction blocks of 2 to 4 MB that divide the width:
    (d, f) = 4096 x 1280, 5120 x 1536, 7168 x 2048 and the `down` product's
    (f, d)."""
    assert [gm.row_tile(m) for m in (512, 256, 16384, 12, 96)] == [
        128, 128, 128, 12, 32]
    bf16 = 2
    assert gm.contraction_tile(4096, 1280, bf16) == 1024
    assert gm.contraction_tile(1280, 4096, bf16) == 256
    assert gm.contraction_tile(5120, 1536, bf16) == 1280
    assert gm.contraction_tile(1536, 5120, bf16) == 384
    assert gm.contraction_tile(7168, 2048, bf16) == 1024
    assert gm.contraction_tile(2048, 7168, bf16) == 256
    # the tiny test widths: one block
    assert gm.contraction_tile(32, 16, 4) == 32


def test_tiles_at_the_latent_experts_shapes():
    """The same rule where the contraction is no power of two: 2,688 = 21 x
    128 has the divisors 128, 384, 896 and 2,688 that are multiples of 128,
    and a block of 896 x 1024 bf16 is 1.8 MB (2,688 rows would be 5.5 MB);
    1,024 x 2,688 takes blocks of 512 (2.75 MB). A tick's 64 x 22 = 1,408
    sorted rows and a 1,024-token unit's 22,528 are whole tiles of 128."""
    bf16 = 2
    assert [t for t in range(128, 2689, 128) if 2688 % t == 0] == [
        128, 384, 896, 2688]
    assert gm.contraction_tile(2688, 1024, bf16) == 896
    assert gm.contraction_tile(1024, 2688, bf16) == 512
    assert gm.contraction_tile(384, 1152, bf16) == 384
    assert gm.contraction_tile(2688, 1152, bf16) == 896
    # float32 (the CPU tests' dtype) halves what fits a block
    assert gm.contraction_tile(2688, 1024, 4) == 896
    assert gm.contraction_tile(1024, 2688, 4) == 256
    assert [gm.row_tile(m) for m in (1408, 22528, 128 * 22)] == [128] * 3


@pytest.mark.parametrize("tk", [384, 128])
def test_a_contraction_of_three_lanes_blocked_is_the_whole_one(monkeypatch, tk):
    """3 x 128 in one block and in three: the last block is a whole one (the
    tile divides the width), so no step reads past the matrix."""
    m, k, n, sizes = 32, 384, 1152, jnp.asarray([11, 0, 14], jnp.int32)
    lhs, rhs = _operands(m, k, n, 3, jnp.float32)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    monkeypatch.setattr(gm, "_BLOCK_BYTES", tk * n * 4)
    assert gm.contraction_tile(k, n, 4) == tk
    got = gm.grouped_matmul(lhs, rhs, gm.group_metadata(sizes, m))
    np.testing.assert_allclose(got[:25], want[:25], rtol=1e-5, atol=1e-5)


def test_the_metadata_walks_every_pair_that_shares_a_row_and_no_other():
    """Against a plain count on the host: group g is visited once for every
    row tile its run of rows reaches into, in order, a tile's visits side by
    side; nothing else is visited."""
    rng = np.random.default_rng(3)
    for m in (128, 512, 1024):
        for _ in range(20):
            groups = int(rng.integers(1, 12))
            sizes = rng.integers(0, 2 * m // groups, groups)
            sizes[rng.random(groups) < 0.3] = 0
            while sizes.sum() > m:
                sizes[np.argmax(sizes)] //= 2
            meta = gm.group_metadata(jnp.asarray(sizes, jnp.int32), m)
            tm = gm.row_tile(m)
            starts = np.concatenate([[0], np.cumsum(sizes)])
            want = [(g, t) for g in range(groups) if sizes[g]
                    for t in range(starts[g] // tm,
                                   (starts[g + 1] - 1) // tm + 1)]
            visits = int(meta.visits)
            assert visits == len(want)
            got = list(zip(meta.group_of[:visits].tolist(),
                           meta.tile_of[:visits].tolist()))
            assert got == want
            assert meta.offsets.tolist() == starts.tolist()
            assert meta.group_of.shape == (m // tm + groups - 1,)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_stack_of_three_under_one_jit_is_each_period_alone(dtype):
    """`place` traced: the sizes are zero outside the period's own experts,
    the stack is one operand, and each place reads its own experts."""
    m, k, n, held = 64, 128, 128, 4
    lhs, rhs = _operands(m, k, n, 3 * held, dtype)
    sizes = jnp.asarray([7, 0, 30, 12], jnp.int32)

    @jax.jit
    def at(place):
        stack_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((3 * held,), jnp.int32), sizes, (place * held,))
        meta = gm.group_metadata(stack_sizes, m)
        return gm.grouped_matmul(lhs, rhs, meta), meta.visits

    for place in range(3):
        got, visits = at(jnp.int32(place))
        want = jax.lax.ragged_dot(
            lhs, rhs[place * held:(place + 1) * held], sizes)
        assert int(visits) == 3
        np.testing.assert_allclose(np.asarray(got[:49], np.float32),
                                   np.asarray(want[:49], np.float32),
                                   **TOL[dtype])
    assert at._cache_size() == 1


def test_operands_that_do_not_belong_together_are_refused():
    lhs, rhs = _operands(32, 64, 48, 4, jnp.float32)
    meta = gm.group_metadata(jnp.asarray([1, 2, 3], jnp.int32), 32)
    with pytest.raises(ValueError, match="do not belong together"):
        gm.grouped_matmul(lhs, rhs, meta)
    with pytest.raises(ValueError, match="do not belong together"):
        gm.grouped_matmul(lhs[:, :32], rhs, gm.group_metadata(
            jnp.asarray([1, 2, 3, 4], jnp.int32), 32))


@pytest.mark.parametrize("case", ["seeded", "idle", "one"])
def test_nan_in_the_rows_no_group_owns_does_not_leak_through_moe_block(
        monkeypatch, case):
    """The kernel never writes the rows past the last group: on the chip
    they are uninitialised memory. With NaN put there after every product
    (so `act`'s tail is NaN going into `down`, too), the layer's output is
    what it was: the combine's `where` drops those rows before any sum."""
    cfg = tiny.config()
    layer = tiny.weights.make_layer(tiny.SEED, 1, tiny.MODEL, jnp.float32)
    moe = tiny.biased({"post_norm": layer["post_norm"], **layer["moe"]}, case)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, 32)),
                    jnp.float32)
    valid = jnp.ones(x.shape[:2], bool).at[1, :2].set(False)
    want, counted = tiny.moe_block_alone(moe, x, valid, cfg)
    assert 0 < int(counted[1]) < x.shape[0] * x.shape[1] * 4   # a tail exists

    def poisoned(lhs, rhs, meta):
        out = gm.grouped_matmul(lhs, rhs, meta)
        tail = jnp.arange(out.shape[0])[:, None] >= meta.offsets[-1]
        return jnp.where(tail, jnp.nan, out)

    monkeypatch.setattr(hybrid, "grouped_matmul", poisoned)
    got, counters = tiny.moe_block_alone(moe, x, valid, cfg)
    assert counters.tolist() == counted.tolist()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
