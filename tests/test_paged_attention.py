"""ops/paged_attention.py: the decode tick's one-query attention over the
page pool, against what it replaced (`decode._gather_pages` + `attention`)
on the same pool.

Tolerances, and where they come from. Both paths keep float32 scores, softmax
statistics and accumulation. In float32 they differ by the order of the sums
alone (page by page against the whole row): 1e-5 on outputs of order 1. In
bfloat16 both round the softmax weights to bfloat16 for the value product,
the gather path after normalizing them and the kernel before (relative 2^-9
either way, at different rounding points), and both round the result to
bfloat16 once (half an ulp, 2^-9 relative): two ulps of the result, rtol
2^-6, and 2^-7 absolute for results near zero, where the weights' rounding is
an absolute error of 2^-9 x sum |p v|.

The last tests compile for a DESCRIBED v5e (no chip: section 2 of the
on-chip-measurement guide): Mosaic at both serving cells' shapes, and the
whole fp tick's memory, which the CPU interpreter cannot show (it copies
every operand of the kernel, the pool among them). They are kept in this
one file: one process at a time may load the TPU's library.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.models.hybrid_moe import decode as hybrid_decode
from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.hybrid_moe.config import HybridMoEConfig
from llama_pipeline_parallel_tpu.models.llama import decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.ops import (
    eva_prefill_attention,
    gqa_prefill_attention,
    grouped_matmul,
    latent_prefill_attention,
    paged_attention,
    paged_latent_attention,
    sparse_latent_attention,
    ssm_state_step,
)
from llama_pipeline_parallel_tpu.ops.attention import attention

L, PAGES, PAGE, KV_H, HD, PMAX = 2, 9, 8, 2, 128, 4
GARBAGE = PAGES            # the pool's extra page: what dead logical pages name
LAYER = 1
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2 ** -6, atol=2 ** -7)}


def _upto(n):
    """A mask row valid on [0, n)."""
    return (np.arange(PMAX * PAGE) < n).astype(np.int32)


def _scenario(name):
    """(page_table [S, PMAX], live_pages [S], kv_mask [S, PMAX * PAGE]) of
    two slot rows. Dead logical pages name the garbage page, as
    serve/pages.py leaves them."""
    table = np.full((2, PMAX), GARBAGE, np.int32)
    mask = np.zeros((2, PMAX * PAGE), np.int32)
    if name == "mask_with_holes":
        # left pads inside a prompt bucket and stray holes, two pages live
        table[:, :2] = [[3, 1], [0, 6]]
        live = [2, 2]
        mask[0] = _upto(13)
        mask[0, [0, 1, 2, 7, 9]] = 0
        mask[1] = _upto(16)
        mask[1, :5] = 0
    elif name == "wholly_masked_page_inside_live_range":
        table[:, :3] = [[3, 1, 2], [4, 5, 6]]
        live = [3, 3]
        mask[0] = _upto(20)
        mask[0, PAGE:2 * PAGE] = 0          # the middle page
        mask[1] = _upto(17)
        mask[1, :PAGE] = 0                  # the first page: a long left pad
    elif name == "one_live_page":
        table[:, 0] = [7, 2]
        live = [1, 1]
        mask[0] = _upto(1)                  # the token attends to itself only
        mask[1] = _upto(PAGE)
    elif name == "every_page_live":
        table[:] = [[0, 1, 2, 3], [4, 5, 6, 7]]
        live = [PMAX, PMAX]
        mask[0] = _upto(PMAX * PAGE)
        mask[1] = _upto(PMAX * PAGE - 3)
        mask[1, 2] = 0
    elif name == "inactive_row":
        # row 0 is not decoding: mid-prefill, it owns pages and mask spans
        table[:, :2] = [[3, 1], [0, 6]]
        live = [0, 2]
        mask[0] = _upto(11)
        mask[1] = _upto(9)
    elif name == "dead_pages_name_the_garbage_page":
        # three of four logical pages dead, a fresh page whose tail is stale
        table[:, 0] = [5, 8]
        live = [1, 1]
        mask[0] = _upto(3)
        mask[1] = _upto(6)
    elif name == "a_physical_page_shared_across_slots":
        # a forked prefix: both rows read pages 2 and 4, then their own
        table[:, :3] = [[2, 4, 1], [2, 4, 7]]
        live = [3, 3]
        mask[0] = _upto(21)
        mask[1] = _upto(18)
    else:
        raise AssertionError(name)
    return table, np.asarray(live, np.int32), mask


SCENARIOS = ["mask_with_holes", "wholly_masked_page_inside_live_range",
             "one_live_page", "every_page_live", "inactive_row",
             "dead_pages_name_the_garbage_page",
             "a_physical_page_shared_across_slots"]


def _pool(dtype, seed):
    rng = np.random.default_rng(seed)
    shape = (L, PAGES + 1, PAGE, KV_H, HD)
    k, v = rng.normal(size=shape), rng.normal(size=shape)
    # what must never reach an output: it would dwarf every real value
    k[:, GARBAGE], v[:, GARBAGE] = 3e4, -3e4
    return jnp.asarray(k, dtype), jnp.asarray(v, dtype)


def _both_paths(q, k, v, table, live, mask):
    out = paged_attention.paged_decode_attention(
        q, k, v, jnp.int32(LAYER), jnp.asarray(table), jnp.asarray(live),
        jnp.asarray(mask))
    gk, gv = decode._gather_pages({"k": k, "v": v}, LAYER, jnp.asarray(table),
                                  q.dtype)
    ref = attention(q[:, None], gk, gv, jnp.asarray(mask), causal=False)[:, 0]
    assert out.shape == ref.shape and out.dtype == ref.dtype == q.dtype
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("g", [1, 4, 8, 16])
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_the_kernel_is_the_gather_and_the_product(scenario, g, dtype):
    table, live, mask = _scenario(scenario)
    k, v = _pool(dtype, seed=g)
    q = jnp.asarray(np.random.default_rng(7).normal(size=(2, KV_H * g, HD)),
                    dtype)
    out, ref = _both_paths(q, k, v, table, live, mask)
    assert np.isfinite(out).all()
    for row in range(2):
        if live[row] == 0:
            # its token is discarded by the scheduler, but it rides the
            # static shape into the layers after: zeros, never NaN
            assert not out[row].any()
        else:
            np.testing.assert_allclose(out[row], ref[row], **TOL[dtype])
            assert np.abs(out[row]).max() < 10.0     # no garbage page in it


# rows of seven logical pages walked n = 2 or 3 pages a step: Pmax is a
# multiple of neither, so the last step holds one page and padding
WIDE = 7


def _stepped(name):
    """(page_table [3, WIDE], live_pages [3], kv_mask [3, WIDE * PAGE]) of
    three slot rows whose live pages end at chosen places of the steps."""
    upto = lambda n: (np.arange(WIDE * PAGE) < n).astype(np.int32)
    table = np.full((3, WIDE), GARBAGE, np.int32)
    mask = np.zeros((3, WIDE * PAGE), np.int32)
    if name == "rows_end_inside_a_step":
        # five pages: the last live page lies INSIDE a step (n = 2: the
        # third, n = 3: the second) and a later step is dead, with a wholly
        # masked page inside the live range; two pages: one step only;
        # none: the row is not decoding, yet owns pages and mask spans
        live = [5, 2, 0]
        table[0, :5], table[1, :2], table[2, :2] = [3, 1, 8, 0, 6], [4, 7], [2, 5]
        mask[0] = upto(5 * PAGE - 3)
        mask[0, [0, 1, 11]] = 0
        mask[0, 3 * PAGE:4 * PAGE] = 0
        mask[1] = upto(PAGE + 2)
        # a stray span past the live pages counts for nothing: at n = 3 its
        # block shares the last live page's step and holds that page again
        mask[1, 2 * PAGE:2 * PAGE + 4] = 1
        mask[2] = upto(2 * PAGE)
    elif name == "rows_end_with_a_step":
        # every page live (the last step's spare blocks hold the last page
        # again and count for nothing), six pages (n = 2 and n = 3: the end
        # of a step), and a row whose FIRST pages are wholly masked
        live = [WIDE, 6, 4]
        table[0], table[1, :6], table[2, :4] = (
            [0, 1, 2, 3, 4, 5, 6], [7, 8, 0, 2, 4, 6], [1, 3, 5, 7])
        mask[0] = upto(WIDE * PAGE)
        mask[0, 5] = 0
        mask[1] = upto(6 * PAGE - 1)
        mask[2] = upto(4 * PAGE - 5)
        mask[2, :2 * PAGE + 1] = 0
    else:
        raise AssertionError(name)
    return table, np.asarray(live, np.int32), mask


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["plain", "widened"])
@pytest.mark.parametrize("n", [2, 3])
@pytest.mark.parametrize("scenario", ["rows_end_inside_a_step",
                                      "rows_end_with_a_step"])
def test_one_update_a_step_over_its_pages_is_the_gather_and_the_product(
        monkeypatch, scenario, n, kind, dtype):
    """n > 1 pages under ONE running-softmax update, and only the steps that
    hold a live page visited. `widened`: what `tests/test_window_moe.py`
    holds for one step, over several: keys of 96 stored 128 wide with the
    scale of 96, values of 64, a learned sink a head sharing the softmax."""
    dk, dv = (96, 64) if kind == "widened" else (HD, HD)
    g = 4
    rng = np.random.default_rng(n)
    k = rng.normal(size=(L, PAGES + 1, PAGE, KV_H, HD))
    v = rng.normal(size=(L, PAGES + 1, PAGE, KV_H, dv))
    q = rng.normal(size=(3, KV_H * g, HD))
    k[..., dk:], q[..., dk:] = 0.0, 0.0
    k[:, GARBAGE, ..., :dk], v[:, GARBAGE] = 3e4, -3e4
    k, v, q = (jnp.asarray(a, dtype) for a in (k, v, q))
    sink = (jnp.asarray(rng.normal(size=KV_H * g), jnp.float32)
            if kind == "widened" else None)
    table, live, mask = _stepped(scenario)
    monkeypatch.setattr(paged_attention, "_STEP_BYTES",
                        n * PAGE * KV_H * (HD + dv) * k.dtype.itemsize)
    assert paged_attention.pages_per_step(k, v, WIDE) == n
    out = paged_attention.paged_decode_attention(
        q, k, v, jnp.int32(LAYER), jnp.asarray(table), jnp.asarray(live),
        jnp.asarray(mask), sink, dk ** -0.5 if kind == "widened" else None)
    gk, gv = (pool[LAYER, table].reshape(3, WIDE * PAGE, KV_H, -1)
              for pool in (k, v))
    # the gathered rows see what lies in the live pages
    mask = mask * (np.arange(WIDE * PAGE)[None, :] < live[:, None] * PAGE)
    ref = np.asarray(attention(q[:, None, :, :dk], gk[..., :dk], gv,
                               jnp.asarray(mask), causal=False)[:, 0],
                     np.float32)
    if sink is not None:
        scores = np.einsum(
            "bhd,bshd->bhs", np.asarray(q, np.float32),
            np.repeat(np.asarray(gk, np.float32), g, axis=2)) * dk ** -0.5
        scores = np.where(mask[:, None, :] > 0, scores, -np.inf)
        top = np.maximum(scores.max(-1), np.asarray(sink))
        total = np.exp(scores - top[..., None]).sum(-1)
        ref = ref * (total / (total + np.exp(np.asarray(sink) - top)))[..., None]
    assert out.shape == ref.shape and out.dtype == dtype
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    for row in range(3):
        if live[row] == 0:
            assert not out[row].any()
        else:
            np.testing.assert_allclose(out[row], ref[row], **TOL[dtype])
            assert np.abs(out[row]).max() < 10.0     # no garbage page in it


def test_the_grid_visits_the_steps_that_hold_a_live_page_and_no_other():
    """A slot's `cdiv(live, n)` steps in order, one for a slot with none;
    the entries past the visits name a slot and a step that exist."""
    slot_of, step_of, visits = paged_attention._visits(
        jnp.asarray([5, 0, 2, 7], jnp.int32), n=3, steps=3)
    assert int(visits) == 2 + 1 + 1 + 3
    assert list(np.asarray(slot_of)[:7]) == [0, 0, 1, 2, 3, 3, 3]
    assert list(np.asarray(step_of)[:7]) == [0, 1, 0, 0, 0, 1, 2]
    assert slot_of.shape == step_of.shape == (4 * 3,)
    assert (np.asarray(slot_of)[7:] == 3).all()
    assert ((0 <= np.asarray(step_of)) & (np.asarray(step_of) < 3)).all()


@pytest.mark.parametrize("step_bytes,n", [
    (0, 1), (3 * 2 * PAGE * KV_H * HD * 4, 3), (1 << 30, PMAX)])
def test_pages_per_step_follow_the_shapes_and_do_not_move_the_result(
        monkeypatch, step_bytes, n):
    """One page a step, a number that does not divide the row's pages (the
    last step's spare blocks clamp and are skipped), the whole row a step."""
    monkeypatch.setattr(paged_attention, "_STEP_BYTES", step_bytes)
    assert paged_attention._pages_per_step(PMAX, PAGE * KV_H * HD * 4) == n
    table, live, mask = _scenario("every_page_live")
    live[1] = 3
    mask[1] = _upto(2 * PAGE + 5)
    k, v = _pool(jnp.float32, seed=3)
    q = jnp.asarray(np.random.default_rng(8).normal(size=(2, KV_H * 4, HD)),
                    jnp.float32)
    out, ref = _both_paths(q, k, v, table, live, mask)
    np.testing.assert_allclose(out, ref, **TOL[jnp.float32])


def test_pages_per_step_at_the_serving_cells_shapes():
    """bf16 pages of 64 tokens: the dense cell's 32 KV heads make a page
    0.5 MB of keys (1 a step), the hybrid's 8 make it 0.125 MB (4)."""
    assert paged_attention._pages_per_step(40, 64 * 32 * 128 * 2) == 1
    assert paged_attention._pages_per_step(40, 64 * 8 * 128 * 2) == 4
    # the state-space cell's 2 KV heads: a page is 32 KB of keys, 16 a step
    assert paged_attention._pages_per_step(36, 64 * 2 * 128 * 2) == 16


# -- compiled for a described v5e ---------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The kernel as the chip runs it: `interpret_mode()` asks the backend,
    which is the CPU here whatever the program is compiled for."""
    monkeypatch.setattr(paged_attention, "interpret_mode", lambda: False)
    for module in (sparse_latent_attention, paged_latent_attention,
                   latent_prefill_attention, eva_prefill_attention,
                   gqa_prefill_attention, grouped_matmul, ssm_state_step):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _described(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("cell,slots,h,kv_h,layers,pages", [
    ("serve-closed-16.deepseek", 16, 32, 32, 4, 640),
    ("serve-closed-64.solar-open2", 64, 64, 8, 1, 2560),
    # 16 query heads a KV head over a KV-head axis of 2: the page is read as
    # the [128, 128] matrix it is in memory, so no second-minor size of 2
    # reaches Mosaic
    ("serve-reason-64.nemotron3-super", 64, 32, 2, 1, 2304),
])
def test_mosaic_compiles_the_kernel_at_a_serving_cells_shapes(
        one_chip, mosaic, cell, slots, h, kv_h, layers, pages):
    """Page 64, rows of 40 logical pages, bf16: Mosaic takes the block
    shapes (the hybrid's 8-head page block too), and XLA:TPU reads the pool
    through a bitcast: nothing pool-sized is made in front of the kernel."""
    pmax, page, hd = 40, 64, 128
    pool = jax.ShapeDtypeStruct((layers, pages + 1, page, kv_h, hd),
                                jnp.bfloat16)
    args = _described(
        (jax.ShapeDtypeStruct((slots, h, hd), jnp.bfloat16), pool, pool,
         jax.ShapeDtypeStruct((), jnp.int32),
         jax.ShapeDtypeStruct((slots, pmax), jnp.int32),
         jax.ShapeDtypeStruct((slots,), jnp.int32),
         jax.ShapeDtypeStruct((slots, pmax * page), jnp.int32)), one_chip)
    compiled = jax.jit(paged_attention.paged_decode_attention).lower(
        *args).compile()
    text = compiled.as_text()
    assert "paged_decode_attn" in text and "tpu_custom_call" in text
    pool_bytes = layers * (pages + 1) * page * kv_h * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 16


@pytest.mark.parametrize("cell,slots,vocab,counters", [
    ("serve-closed-16.deepseek", 16, 102400, 0),
    ("serve-closed-64.solar-open2", 64, 24576, 6),
    ("serve-reason-64.nemotron3-super", 64, 32768, 7),
])
def test_the_first_tokens_program_compiles_at_a_whole_bucket_cells_shapes(
        one_chip, mosaic, cell, slots, vocab, counters):
    """`tick_io.first_token` (a prefilled row's first token drawn where the
    logits lie, PR 47) at the shapes of the three cells that prefill whole
    buckets: one read of 3 + counters words, `prev` back in its own shape,
    and temporaries of a few copies of one row of logits, nothing more."""
    from llama_pipeline_parallel_tpu.models import family, tick_io

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    args = _described(
        (jax.ShapeDtypeStruct((1, vocab), jnp.float32),
         i32(tick_io.FIRST_COLUMNS), i32(3 * slots + counters),
         i32(counters) if counters else None), one_chip)
    compiled = tick_io.first_token(family.sample_rowwise, slots).lower(
        *args).compile()
    read, fed = compiled.out_info
    assert (read.shape, read.dtype) == ((3 + counters,), jnp.int32)
    assert (fed.shape, fed.dtype) == ((3 * slots + counters,), jnp.int32)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * vocab * 4


def _dense_tick(slots, pmax, page, pages):
    cfg = LlamaConfig(vocab_size=256, hidden_size=2048, intermediate_size=256,
                      num_hidden_layers=2, num_attention_heads=16,
                      num_key_value_heads=16, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: decode.init_page_pool(cfg, pages, page))
    return decode.paged_decode_step, cfg, params, pool


def _hybrid_tick(slots, pmax, page, pages):
    cfg = HybridMoEConfig(
        vocab_size=256, hidden_size=256, num_hidden_layers=4,
        num_attention_heads=64, num_key_value_heads=8, kda_heads=2,
        kda_rank=16, router_experts=16, experts_held=8,
        num_experts_per_tok=4, moe_intermediate_size=64,
        shared_intermediate_size=64)
    params = jax.eval_shape(
        lambda: hybrid.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: {
        **hybrid_decode.init_page_pool(cfg, pages, page),
        **hybrid_decode.init_recurrent_store(cfg, slots)})
    return hybrid_decode.paged_decode_step, cfg, params, pool


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_a_tick_compiled_for_the_chip_keeps_the_pool_in_place(
        one_chip, mosaic, family):
    """What the compiled checks of tests/test_pool_walk.py and
    tests/test_hybrid_serving.py held before the kernel (XLA:CPU's
    interpreter copies the kernel's operands): compiled for the chip with a
    pool many times its weights, the tick's temporaries stay under a
    sixteenth of one pool array and the outputs are the donated stores'
    buffers. Widths at which the pool's `[page * kv_h, hd]` view is a
    bitcast for XLA:TPU, as at both cells': bf16 heads of 128, 16 KV heads
    (dense) and the hybrid's own 8 under 64 query heads."""
    slots, pmax, page = 4, 8, 64
    tick, cfg, params, pool = (_dense_tick if family == "dense"
                               else _hybrid_tick)(slots, pmax, page, 2048)
    z = jax.ShapeDtypeStruct((slots,), jnp.int32)
    f = jax.ShapeDtypeStruct((slots,), jnp.float32)
    args = _described(
        (params, z, pool, jax.ShapeDtypeStruct((slots, pmax), jnp.int32), z,
         z, jax.ShapeDtypeStruct((slots, pmax * page), jnp.int32), z,
         jax.ShapeDtypeStruct((slots, 2), jnp.uint32), f, z, f), one_chip)
    compiled = tick.lower(*args, cfg).compile()
    analysis = compiled.memory_analysis()

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    assert nbytes(pool["k"]) > 5 * nbytes(params)
    assert analysis.temp_size_in_bytes < nbytes(pool["k"]) // 16, analysis
    assert analysis.alias_size_in_bytes >= nbytes(pool)
    assert "paged_decode_attn" in compiled.as_text()


# -- the latent family, compiled for the same chip -----------------------------

@pytest.mark.parametrize("queries", [128, 32], ids=["chunk-block", "tick"])
def test_mosaic_compiles_the_sparse_read_at_the_long_cells_shapes(
        one_chip, mosaic, queries):
    """128 heads, 2048 chosen entries of 640 (576 stored in whole tiles),
    bf16: a block of a chunk's queries and a tick's 32 rows."""
    args = _described(
        (jax.ShapeDtypeStruct((queries, 128, 640), jnp.bfloat16),
         jax.ShapeDtypeStruct((queries, 2048, 640), jnp.bfloat16),
         jax.ShapeDtypeStruct((queries, 2048), jnp.bool_)), one_chip)
    compiled = jax.jit(
        lambda q, e, ok: sparse_latent_attention.sparse_latent_attention(
            q, e, ok, 192 ** -0.5)).lower(*args).compile()
    text = compiled.as_text()
    assert "sparse_latent_attn" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def _latent_programs(slots, pmax, page, pages):
    """The latent family at the published entry widths (576 and 1088, stored
    as 640 and 1152) and small everything else."""
    from llama_pipeline_parallel_tpu.models.latent_moe import decode as latent_decode
    from llama_pipeline_parallel_tpu.models.latent_moe import model as latent
    from llama_pipeline_parallel_tpu.models.latent_moe.config import (
        LatentMoEConfig,
    )

    cfg = LatentMoEConfig(
        vocab_size=256, hidden_size=256, num_hidden_layers=5,
        intermediate_size=256, num_attention_heads=8, q_lora_rank=128,
        index_n_heads=4, index_topk=128, swa_num_attention_heads=4,
        swa_q_lora_rank=128, router_experts=16, experts_held=8,
        num_experts_per_tok=4, moe_intermediate_size=64,
        shared_intermediate_size=64)
    params = jax.eval_shape(
        lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: {
        **latent_decode.init_page_pool(cfg, pages, page),
        **latent_decode.init_recurrent_store(cfg, slots)})
    return latent_decode, cfg, params, pool


@pytest.mark.parametrize("program", ["tick", "chunk", "first_draft"])
def test_a_drafting_latent_program_compiled_for_the_chip_keeps_its_stores_in_place(
        one_chip, mosaic, program):
    """A latent model that drafts, at the published entry widths: the verify
    tick (two queries a row, the module's layer behind the trunk's), a chunk
    that keeps the module's entries, and the first draft, compiled for the
    chip with pages many times the weights: the outputs are the donated
    stores' buffers, nothing as large as the latent pages is made beside
    them, and the sparse read is the kernel."""
    from llama_pipeline_parallel_tpu.models import tick_io
    from llama_pipeline_parallel_tpu.models.family import family_of
    from llama_pipeline_parallel_tpu.models.latent_moe import decode as latent_decode
    from llama_pipeline_parallel_tpu.models.latent_moe import draft
    from llama_pipeline_parallel_tpu.models.latent_moe import model as latent
    from llama_pipeline_parallel_tpu.models.latent_moe.config import (
        LatentMoEConfig,
    )

    slots, pmax, page = 4, 16, 64
    cfg = LatentMoEConfig(
        vocab_size=256, hidden_size=256, num_hidden_layers=3, period=("full",),
        intermediate_size=256, num_attention_heads=8, q_lora_rank=128,
        v_head_dim=256, index_n_heads=4, index_topk=128, attention_gate=False,
        lora_rescale=False, router_experts=16, experts_held=8,
        num_experts_per_tok=4, moe_intermediate_size=64,
        shared_intermediate_size=64, num_nextn_predict_layers=1)
    fam = family_of(cfg)
    params = jax.eval_shape(
        lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: {
        **latent_decode.init_page_pool(cfg, 4096, page),
        **latent_decode.init_recurrent_store(cfg, slots)})
    assert pool["latent"].shape[0] == 4 and pool["latent"].shape[-1] == 640
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    mask, prev = i32(slots, pmax * page), i32(
        fam.fetch_rows * slots + len(fam.counters))
    if program == "tick":
        args = _described((params, i32(slots, tick_io.COLUMNS + pmax), prev,
                           pool, mask), one_chip)
        compiled = fam.decode_tick.lower(*args, cfg).compile()
    elif program == "chunk":
        ids = i32(1, 256)
        args = _described((params, ids, ids, ids, pool, i32(pmax), i32(),
                           mask, i32()), one_chip)
        compiled = latent_decode.paged_prefill_chunk.lower(
            *args, cfg, next_id=_described(i32(1), one_chip)).compile()
    else:
        args = _described(
            (params, jax.ShapeDtypeStruct((1, 256), jnp.bfloat16), prev, pool,
             i32(pmax), i32(), mask, i32(), i32()), one_chip)
        compiled = draft.first_draft.lower(*args, cfg).compile()
    analysis = compiled.memory_analysis()

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    assert nbytes(pool["latent"]) > 5 * nbytes(params)
    assert analysis.alias_size_in_bytes >= nbytes(pool)
    assert analysis.temp_size_in_bytes < nbytes(pool["latent"]) // 4, analysis
    assert "sparse_latent_attn" in compiled.as_text()


@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_a_latent_program_compiled_for_the_chip_keeps_its_stores_in_place(
        one_chip, mosaic, program):
    """The three stores of the latent family at their published entry
    widths: compiled for the chip with pages many times the weights, the
    outputs are the donated stores' buffers and nothing as large as the
    latent pages is made beside them. Stored 576 wide instead of 640, the
    same programs copy the latent pages whole on the way in and out (the
    chip hands a store whose rows are not whole tiles over in another
    layout: PERF.md, PR 30); `store_multiple` is what this test holds."""
    slots, pmax, page = 4, 16, 64
    latent_decode, cfg, params, pool = _latent_programs(slots, pmax, page, 4096)
    assert pool["latent"].shape[-1] == 640 and pool["ring"].shape[-1] == 1152
    z = jax.ShapeDtypeStruct((slots,), jnp.int32)
    f = jax.ShapeDtypeStruct((slots,), jnp.float32)
    mask = jax.ShapeDtypeStruct((slots, pmax * page), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    if program == "tick":
        args = _described(
            (params, z, pool, jax.ShapeDtypeStruct((slots, pmax), jnp.int32),
             z, z, mask, z, jax.ShapeDtypeStruct((slots, 2), jnp.uint32), f,
             z, f), one_chip)
        compiled = latent_decode.paged_decode_step.lower(*args, cfg).compile()
    else:
        ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
        args = _described(
            (params, ids, ids, ids, pool,
             jax.ShapeDtypeStruct((pmax,), jnp.int32), scalar, mask, scalar),
            one_chip)
        compiled = latent_decode.paged_prefill_chunk.lower(*args, cfg).compile()
    analysis = compiled.memory_analysis()

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    assert nbytes(pool["latent"]) > 5 * nbytes(params)
    assert analysis.alias_size_in_bytes >= nbytes(pool)
    assert analysis.temp_size_in_bytes < nbytes(pool["latent"]) // 4, analysis
    assert "sparse_latent_attn" in compiled.as_text()


# -- the engine's own tick: one staged buffer in, one fetched vector out --------

@pytest.mark.parametrize("family", ["dense", "hybrid", "latent"])
def test_the_engines_tick_compiled_for_the_chip_keeps_the_stores_in_place(
        one_chip, mosaic, family):
    """`tick_io.packed` of each family's step (what `ServeEngine` runs),
    compiled for the chip: the slices and bitcasts that take the staged
    buffer apart cost no store-sized temporary, the outputs are the donated
    stores' buffers, the kernel is still the tick's attention, and what the
    host fetches is one int32 vector of 3 a slot and the counters."""
    from llama_pipeline_parallel_tpu.models import tick_io

    slots, pmax, page = 4, 16, 64
    if family == "latent":
        latent_decode, cfg, params, pool = _latent_programs(
            slots, pmax, page, 4096)
        tick, store = latent_decode.paged_decode_step, "latent"
        kernel, counters = "sparse_latent_attn", len(latent_decode.counters(cfg))
    else:
        tick, cfg, params, pool = (
            _dense_tick if family == "dense" else _hybrid_tick)(
                slots, pmax, page, 2048)
        store, kernel = "k", "paged_decode_attn"
        counters = 0 if family == "dense" else len(hybrid_decode.COUNTERS)
    args = _described(
        (params,
         jax.ShapeDtypeStruct((slots, tick_io.COLUMNS + pmax), jnp.int32),
         # the tick before's fetched vector, fed back on the device
         jax.ShapeDtypeStruct((3 * slots + counters,), jnp.int32),
         pool, jax.ShapeDtypeStruct((slots, pmax * page), jnp.int32)),
        one_chip)
    lowered = tick_io.packed(tick).lower(*args, cfg)
    assert "module @jit_paged_decode_step" in lowered.as_text()
    assert lowered.out_info["fetch"].shape == (3 * slots + counters,)
    assert lowered.out_info["fetch"].dtype == jnp.int32
    compiled = lowered.compile()
    analysis = compiled.memory_analysis()

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    assert analysis.alias_size_in_bytes >= nbytes(pool)
    assert analysis.temp_size_in_bytes < nbytes(pool[store]) // 4, analysis
    assert kernel in compiled.as_text()


# -- the state-space family: its tick and its expert layer for the same chip ------

def test_a_state_space_tick_compiled_for_the_chip_keeps_its_stores_in_place(
        one_chip, mosaic):
    """The fifth family's tick as `ServeEngine` runs it, its layers unrolled
    in the pattern's order: the recurrent store's rows are stepped where
    they lie by `ops/ssm_state_step.py`, the donated leaf aliased through
    both Mamba-2 layers (no copy of a layer's state in front of the step and
    no `dynamic-update-slice` of one behind it, outputs aliased), the
    attention is the paged kernel at 16 query heads a KV head, and the host
    fetches 3 a slot and the family's counters. Mamba-2 and attention shapes
    as the cell's, a small width."""
    from llama_pipeline_parallel_tpu.models import tick_io
    from llama_pipeline_parallel_tpu.models.ssm_moe import decode as ssm_decode
    from llama_pipeline_parallel_tpu.models.ssm_moe import model as ssm
    from llama_pipeline_parallel_tpu.models.ssm_moe.config import SsmMoEConfig

    slots, pmax, page = 8, 16, 64
    cfg = SsmMoEConfig(
        vocab_size=256, hidden_size=256, pattern="MEM*E", ssm_heads=64,
        router_experts=16, experts_held=8, num_experts_per_tok=4,
        moe_latent_size=128, moe_intermediate_size=384,
        shared_intermediate_size=256)
    params = jax.eval_shape(lambda: ssm.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: {
        **ssm_decode.init_page_pool(cfg, 2048, page),
        **ssm_decode.init_recurrent_store(cfg, slots)})
    counters = len(ssm_decode.COUNTERS)
    args = _described(
        (params,
         jax.ShapeDtypeStruct((slots, tick_io.COLUMNS + pmax), jnp.int32),
         jax.ShapeDtypeStruct((3 * slots + counters,), jnp.int32),
         pool, jax.ShapeDtypeStruct((slots, pmax * page), jnp.int32)),
        one_chip)
    lowered = tick_io.packed(ssm_decode.paged_decode_step).lower(*args, cfg)
    assert lowered.out_info["fetch"].shape == (3 * slots + counters,)
    compiled = lowered.compile()
    analysis = compiled.memory_analysis()

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    assert nbytes(pool) > 5 * nbytes(params)
    assert analysis.alias_size_in_bytes >= nbytes(pool)
    # under half of ONE layer's state of the slots: no copy of it was made
    assert analysis.temp_size_in_bytes < nbytes(pool["state"]) // (
        2 * cfg.recurrent_layers), analysis
    text = compiled.as_text()
    assert "paged_decode_attn" in text and "grouped_matmul" in text
    # M E M * E: two state steps, one paged attention, two products an E
    assert text.count('custom_call_target="tpu_custom_call"') == 2 + 1 + 4
    assert "ssm_state_step" in text
    layer_state = "f32[{},{},{},{}]".format(*pool["state"].shape[1:])
    assert not [line for line in text.splitlines()
                if layer_state in line and ("dynamic-update-slice(" in line
                                            or " copy(" in line)]


def _dense_block(slots: int):
    """The dense state-space block at `serve-rag-48.granite4-h-micro`'s
    Mamba-2 and attention shapes (one group of 64 heads of 64, state 128,
    chunk 256; 32 query / 8 KV heads of 64, two of them a page row, scale
    1/64; the four multipliers, the head tied), a small width and three
    layers; its pool of 2048 pages and `slots` rows."""
    from llama_pipeline_parallel_tpu.models.ssm_moe import decode as ssm_decode
    from llama_pipeline_parallel_tpu.models.ssm_moe import model as ssm
    from llama_pipeline_parallel_tpu.models.ssm_moe.config import SsmMoEConfig

    cfg = SsmMoEConfig(
        vocab_size=512, hidden_size=256, pattern="M-*-M-",
        num_attention_heads=32, num_key_value_heads=8, head_dim=64,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        ssm_chunk=256, dense_intermediate_size=512, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=1 / 64,
        logits_scaling=8.0, tie_word_embeddings=True)
    params = jax.eval_shape(lambda: ssm.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: {
        **ssm_decode.init_page_pool(cfg, 2048, 64),
        **ssm_decode.init_recurrent_store(cfg, slots)})
    return cfg, params, pool


def _dims(a) -> str:
    return ",".join(str(n) for n in a.shape)


@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_a_dense_state_space_program_compiled_for_the_chip_keeps_its_stores_in_place(
        one_chip, mosaic, program):
    """KV heads of 64, the first below a lane tile in any served
    configuration: two lie side by side in a 128-lane row and a page is the
    matrix `[64 x 4, 128]`, so the tick's kernel (through a view) and the
    chunk's scatter and gather read and write the pool where it lies: no
    instruction but the in-place writes makes an array of a pool's shape. The
    state step runs at one group of 64 heads. A chunk reads and writes ONE
    slot's row of the recurrent store: no copy of the `conv` store in the
    convolution's own layout (three places padded to 128 lanes) stands in
    front of it or behind it (`decode._own_layout`)."""
    from llama_pipeline_parallel_tpu.models import tick_io
    from llama_pipeline_parallel_tpu.models.ssm_moe import decode as ssm_decode

    slots, pmax, page, C = 48, 64, 64, 2048
    cfg, params, pool = _dense_block(slots)
    assert pool["k"].shape == (1, 2049, page * 4, 128)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    kv_mask = i32(slots, pmax * page)
    if program == "tick":
        args = _described(
            (params, i32(slots, tick_io.COLUMNS + pmax),
             i32(3 * slots + len(ssm_decode.COUNTERS)), pool, kv_mask),
            one_chip)
        compiled = tick_io.packed(ssm_decode.paged_decode_step).lower(
            *args, cfg).compile()
        kernels = ("paged_decode_attn", "ssm_state_step")
    else:
        args = _described(
            (params, i32(1, C), i32(1, C), i32(1, C), pool, i32(pmax), i32(),
             kv_mask, i32()), one_chip)
        compiled = ssm_decode.paged_prefill_chunk.lower(*args, cfg).compile()
        kernels = ("full_chunk_attn",)
    analysis = compiled.memory_analysis()
    nbytes = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize
    assert analysis.alias_size_in_bytes >= sum(nbytes(a) for a in pool.values())
    if program == "tick":
        # a copy of the keys' pool is larger than everything the tick holds
        assert analysis.temp_size_in_bytes < nbytes(pool["k"]) // 2, analysis
    text = compiled.as_text()
    assert all(name in text for name in kernels)
    made = lambda a, ops: [
        line for line in text.splitlines()
        if f"[{_dims(a)}]" in line.split(" = ")[-1].split("(")[0]
        and any(f" {op}(" in line for op in ops)]
    assert not made(pool["k"], ("copy", "transpose"))
    assert not made(pool["conv"], ("copy", "transpose"))
    assert not made(pool["state"], ("copy", "transpose"))
    # nor does a chunk slice a whole LAYER of either store out (every slot's
    # row: 100 MB a layer at the cell's 48 slots, where it needs one row)
    for name in ("state", "conv") if program == "chunk" else ():
        layer = "[1," + _dims(pool[name]).split(",", 1)[1] + "]"
        assert layer not in text, (name, layer)


def test_mosaic_compiles_the_state_step_at_one_group_of_64_heads(
        one_chip, mosaic):
    """The dense block's store at four layers, float32 [4, 48, 64, 64, 128]:
    one group of 64 heads is one block of 2 MB, walked in four runs of 16
    unrolled heads; the store is aliased to the result and the program holds
    nothing else of any size."""
    layers, slots, H, P, G, N = 4, 48, 64, 64, 1, 128
    shapes = _described(tuple(
        jax.ShapeDtypeStruct(s, jnp.float32)
        for s in ((layers, slots, H, P, N), (slots, H, P), (slots, H), (H,),
                  (slots, G, N), (slots, G, N))), one_chip)
    assert ssm_state_step.head_block(H, G, P, N) == H
    compiled = jax.jit(
        lambda store, *a: ssm_state_step.ssm_state_step(store, 2, *a),
        donate_argnums=0).lower(*shapes).compile()
    analysis = compiled.memory_analysis()
    assert analysis.alias_size_in_bytes >= layers * slots * H * P * N * 4
    assert analysis.temp_size_in_bytes < 8 << 20, analysis
    assert "ssm_state_step" in compiled.as_text()


def test_mosaic_compiles_the_chunks_attention_at_heads_of_64(one_chip, mosaic):
    """A 2048-token chunk at the end of the cell's longest row: 32 query / 8
    KV heads of 64 over 17,920 places (4 query heads stacked a program, the
    output written 64 lanes a head)."""
    T, S, H, G, d = 2048, 17920, 32, 8, 64
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    shapes = _described(
        (bf16(1, T, H, d), bf16(1, S, G, d), bf16(1, S, G, d),
         jax.ShapeDtypeStruct((1, S), jnp.int32),
         jax.ShapeDtypeStruct((), jnp.int32)), one_chip)
    compiled = jax.jit(gqa_prefill_attention.full_prefill_attention).lower(
        *shapes).compile()
    assert "full_chunk_attn" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("bucket", [128, 1024])
def test_mosaic_compiles_a_whole_buckets_attention_at_the_reasoning_cells_shapes(
        one_chip, mosaic, bucket):
    """`serve-reason-64.nemotron3-super` prefills whole buckets of 128 to
    1024 through the chunk's kernel too (32 query heads over 2 KV heads of
    128, 16 stacked a program): no bucket's scores are formed."""
    H, G, d = 32, 2, 128
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    shapes = _described(
        (bf16(1, bucket, H, d), bf16(1, bucket, G, d), bf16(1, bucket, G, d),
         jax.ShapeDtypeStruct((1, bucket), jnp.int32),
         jax.ShapeDtypeStruct((), jnp.int32)), one_chip)
    compiled = jax.jit(gqa_prefill_attention.full_prefill_attention).lower(
        *shapes).compile()
    assert "full_chunk_attn" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_mosaic_compiles_the_state_step_at_the_cells_shape_in_place(
        one_chip, mosaic):
    """`serve-reason-64.nemotron3-super`'s store, float32 [5, 64, 128, 64,
    128] (1.34 GB): Mosaic takes the block `head_block` chooses and every
    smaller one of whole groups, the store is aliased to the result and the
    program holds nothing else of any size."""
    layers, slots, H, P, G, N = 5, 64, 128, 64, 8, 128
    shapes = _described(tuple(
        jax.ShapeDtypeStruct(s, jnp.float32)
        for s in ((layers, slots, H, P, N), (slots, H, P), (slots, H), (H,),
                  (slots, G, N), (slots, G, N))), one_chip)
    chosen = ssm_state_step.head_block(H, G, P, N)
    assert chosen % (H // G) == 0 and H % chosen == 0
    for hb in sorted({H // G, chosen}):
        compiled = jax.jit(
            lambda store, *a: ssm_state_step.ssm_state_step(
                store, 3, *a, block_heads=hb),
            donate_argnums=0).lower(*shapes).compile()
        analysis = compiled.memory_analysis()
        assert analysis.alias_size_in_bytes >= layers * slots * H * P * N * 4
        assert analysis.temp_size_in_bytes < 8 << 20, analysis
        assert "ssm_state_step" in compiled.as_text()


def test_a_latent_expert_layer_compiled_for_the_chip_reads_its_experts_as_stored(
        one_chip, mosaic):
    """`latent_moe_block` at a tick's 64 rows and the cell's real widths (128
    experts of 2 x 1024 x 2688 held of 512, top-22): two grouped kernels,
    whose right operands are the arguments themselves, and temporaries far
    under ONE expert's matrices (5.5 MB each; a copy of the layer's experts
    would be 1.4 GB)."""
    from llama_pipeline_parallel_tpu.models.ssm_moe import model as ssm
    from llama_pipeline_parallel_tpu.models.ssm_moe.config import SsmMoEConfig

    cfg = SsmMoEConfig(vocab_size=256, pattern="E", experts_held=128)
    layer = jax.eval_shape(lambda: ssm.init_params(
        jax.random.PRNGKey(0), cfg))["layers"][0]
    assert layer["up"].shape == (128, 1024, 2688)
    args = _described(
        (layer, jax.ShapeDtypeStruct((64, 1, 4096), jnp.bfloat16),
         jax.ShapeDtypeStruct((64, 1), jnp.bool_)), one_chip)
    compiled = jax.jit(
        lambda *a: ssm.latent_moe_block(*a, cfg)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 and "grouped_matmul" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# -- plain MLA that reads the whole cache, compiled for the same chip ------------

def test_mosaic_compiles_the_dense_latent_tick_kernel_at_the_longdoc_cells_shapes(
        one_chip, mosaic):
    """32 rows of 64 heads against pages of 64 entries of 640 (576 stored in
    whole tiles), 288 logical pages a row, the pool of five layers whole:
    Mosaic takes the blocks, twelve pages a step, and nothing pool-sized is
    made in front of the kernel."""
    slots, pmax, page, pages, layers = 32, 288, 64, 9216, 5
    assert paged_latent_attention._pages_per_step(pmax, page * 640 * 2) == 12
    args = _described(
        (jax.ShapeDtypeStruct((slots, 64, 640), jnp.bfloat16),
         jax.ShapeDtypeStruct((layers, pages + 1, page, 640), jnp.bfloat16),
         jax.ShapeDtypeStruct((), jnp.int32),
         jax.ShapeDtypeStruct((slots, pmax), jnp.int32),
         jax.ShapeDtypeStruct((slots,), jnp.int32),
         jax.ShapeDtypeStruct((slots, pmax * page), jnp.int32)), one_chip)
    compiled = jax.jit(
        lambda q, pool, layer, table, live, mask:
        paged_latent_attention.paged_latent_decode_attention(
            q, pool, layer, table, live, mask, 0.13, 512)).lower(*args).compile()
    text = compiled.as_text()
    assert "paged_latent_decode_attn" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("keys", [2048, 18432], ids=["bucket", "row-end"])
def test_mosaic_compiles_the_dense_latent_prefill_kernel_at_the_longdoc_cells_shapes(
        one_chip, mosaic, keys):
    """A 2048-token unit of 64 heads (128 + 64 for scores, 128 for values)
    against its own bucket and against a whole 18,432-place row: the scores
    stay in the kernel (nothing of [64, 2048, keys] float32 exists)."""
    b, H, T = 1, 64, 2048
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    args = _described(
        (shape(b, H, T, 128), shape(b, H, T, 64), shape(b, H, keys, 128),
         shape(b, keys, 64), shape(b, H, keys, 128),
         jax.ShapeDtypeStruct((b, keys), jnp.int32),
         jax.ShapeDtypeStruct((), jnp.int32)), one_chip)
    compiled = jax.jit(
        lambda qn, qr, kn, kr, v, valid, start:
        latent_prefill_attention.latent_prefill_attention(
            qn, qr, kn, kr, v, valid, start, 0.13)).lower(*args).compile()
    text = compiled.as_text()
    assert "latent_prefill_attn" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_a_program_of_one_kind_of_layer_compiled_for_the_chip_keeps_its_pages_in_place(
        one_chip, mosaic, program):
    """A.X-K1's shape at the published entry width (576 stored as 640) and
    small everything else, pages many times the weights: the outputs are the
    donated pool's buffers, nothing as large as the latent pages is made
    beside them (no gathered rows in the tick), and both kernels are in the
    programs."""
    from llama_pipeline_parallel_tpu.models.latent_moe import decode as latent_decode
    from llama_pipeline_parallel_tpu.models.latent_moe import model as latent
    from llama_pipeline_parallel_tpu.models.latent_moe.config import (
        LatentMoEConfig,
    )

    slots, pmax, page = 4, 16, 64
    cfg = LatentMoEConfig(
        vocab_size=256, hidden_size=256, num_hidden_layers=5,
        period=("full",), intermediate_size=256, num_attention_heads=8,
        q_lora_rank=128, index_topk=0, attention_gate=False,
        lora_rescale=False, rope_theta=1e4, rope_scaling=tuple(sorted({
            "beta_fast": 32.0, "beta_slow": 1.0, "factor": 32.0,
            "mscale": 1.0, "mscale_all_dim": 1.0,
            "original_max_position_embeddings": 4096.0}.items())),
        router_experts=16, experts_held=8, num_experts_per_tok=4,
        moe_intermediate_size=64, shared_intermediate_size=64)
    params = jax.eval_shape(
        lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(
        lambda: latent_decode.init_page_pool(cfg, 4096, page))
    assert set(pool) == {"latent"} and pool["latent"].shape[-1] == 640
    z = jax.ShapeDtypeStruct((slots,), jnp.int32)
    f = jax.ShapeDtypeStruct((slots,), jnp.float32)
    mask = jax.ShapeDtypeStruct((slots, pmax * page), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    if program == "tick":
        args = _described(
            (params, z, pool, jax.ShapeDtypeStruct((slots, pmax), jnp.int32),
             z, z, mask, z, jax.ShapeDtypeStruct((slots, 2), jnp.uint32), f,
             z, f), one_chip)
        compiled = latent_decode.paged_decode_step.lower(*args, cfg).compile()
        kernel = "paged_latent_decode_attn"
    else:
        ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
        args = _described(
            (params, ids, ids, ids, pool,
             jax.ShapeDtypeStruct((pmax,), jnp.int32), scalar, mask, scalar),
            one_chip)
        compiled = latent_decode.paged_prefill_chunk.lower(*args, cfg).compile()
        kernel = "latent_prefill_attn"
    analysis = compiled.memory_analysis()

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    assert nbytes(pool) > 5 * nbytes(params)
    assert analysis.alias_size_in_bytes >= nbytes(pool)
    assert analysis.temp_size_in_bytes < nbytes(pool) // 4, analysis
    assert kernel in compiled.as_text()


# -- the compressed-window family, compiled for the same chip ---------------------

def test_mosaic_compiles_the_two_kinds_prefill_kernel_at_the_bytes_cells_shapes(
        one_chip, mosaic):
    """A 2048-byte unit of 32 heads of 128 against the slot's 25 summary
    pages (1,600 entries, padded to whole blocks inside), the ring as it
    stood and its own keys: the scores stay in the kernel (nothing of [32,
    2048, 5696] float32 exists)."""
    T, width, n_sum, window = 2048, 4096, 1600, 2048
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    tag = lambda n: jax.ShapeDtypeStruct((1, n), jnp.int32)
    args = _described(
        (shape(1, T, width), shape(1, n_sum, width), shape(1, n_sum, width),
         tag(n_sum), shape(1, window + T, width), shape(1, window + T, width),
         tag(window + T), tag(T), tag(T)), one_chip)
    compiled = jax.jit(
        lambda q, ks, vs, ts, ke, ve, te, lo, hi:
        eva_prefill_attention.eva_prefill_attention(
            q, ks, vs, ts, ke, ve, te, lo, hi, 32, 128 ** -0.5)
    ).lower(*args).compile()
    text = compiled.as_text()
    assert "eva_prefill_attn" in text and "tpu_custom_call" in text
    # the summaries' padding to whole blocks: 448 rows of keys and of values
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20


@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_a_compressed_window_program_compiled_for_the_chip_keeps_its_pool_in_place(
        one_chip, mosaic, program):
    """EvaByte's widths (32 heads of 128, window 2048, chunk 16, pages of
    64) at two layers and a small feed-forward, the pool many times the
    weights: the outputs are the donated pool's buffers (through the tick's
    `lax.cond` on whether a window finished too), nothing as large as a
    quarter of the pool is made beside them, and the kernels are in the
    programs: the dense family's paged kernel in the tick, the two-kinds
    kernel in a unit."""
    from llama_pipeline_parallel_tpu.models import tick_io
    from llama_pipeline_parallel_tpu.models.eva import decode as eva_decode
    from llama_pipeline_parallel_tpu.models.eva import model as eva
    from llama_pipeline_parallel_tpu.models.eva.config import EvaConfig

    slots, max_len, page, pages = 16, 25600, 64, 912
    cfg = EvaConfig(num_hidden_layers=2, intermediate_size=1024)
    width = eva_decode.table_width(cfg, max_len, page)
    assert width == 57
    params = jax.eval_shape(lambda: eva.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: eva_decode.init_page_pool(cfg, pages, page))
    mask = jax.ShapeDtypeStruct((slots, max_len), jnp.int32)
    if program == "tick":
        args = _described(
            (params, jax.ShapeDtypeStruct(
                (slots, tick_io.COLUMNS + width), jnp.int32),
             jax.ShapeDtypeStruct(
                 (3 * slots + len(eva_decode.COUNTERS),), jnp.int32),
             pool, mask),
            one_chip)
        lowered = tick_io.packed(eva_decode.paged_decode_step).lower(*args, cfg)
        assert lowered.out_info["fetch"].shape == (
            3 * slots + len(eva_decode.COUNTERS),)
        compiled = lowered.compile()
        kernel = "paged_decode_attn"
    else:
        ids = jax.ShapeDtypeStruct((1, 2048), jnp.int32)
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        args = _described(
            (params, ids, ids, ids, pool,
             jax.ShapeDtypeStruct((width,), jnp.int32), scalar, mask, scalar),
            one_chip)
        compiled = eva_decode.paged_prefill_chunk.lower(*args, cfg).compile()
        kernel = "eva_prefill_attn"
    analysis = compiled.memory_analysis()

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    assert nbytes(pool) > 5 * nbytes(params)
    assert analysis.alias_size_in_bytes >= nbytes(pool)
    assert analysis.temp_size_in_bytes < nbytes(pool) // 4, analysis
    assert kernel in compiled.as_text()


# -- the expert layer's grouped product, compiled for the same chip ----------------

# cell: (periods, experts held, d, f, rows a tick, top-k)
EXPERT_CELLS = {
    "serve-closed-64.solar-open2": (1, 40, 4096, 1280, 64, 8),
    "serve-long-32.dots3": (1, 32, 5120, 1536, 32, 8),
    "serve-longdoc-32.a.x-k1": (4, 12, 7168, 2048, 32, 8),
}


# the latent experts' two products, 1024 x 2688 and 2688 x 1024: the first
# contraction that is no power of two (blocks of 512 and 896) and an output
# 21 lanes of 128 wide
GROUPED_CELLS = {**EXPERT_CELLS,
                 "serve-reason-64.nemotron3-super": (1, 128, 1024, 2688, 64, 22)}


@pytest.mark.parametrize("tokens", ["tick", 2048])
@pytest.mark.parametrize("cell", sorted(GROUPED_CELLS))
def test_mosaic_compiles_the_grouped_product_at_the_expert_cells_shapes(
        one_chip, mosaic, cell, tokens):
    """bf16, the stack of every period as stored, a tick's `T * k` rows and
    a 2048-token unit's 16,384, the `gate` / `up` orientation and `down`'s:
    Mosaic takes the blocks (whole-width weight blocks of 2 to 4 MB, a
    float32 accumulator beside them), and XLA:TPU hands the stack to the
    kernel as it lies: no temporary as large as ONE expert's matrix."""
    periods, held, d, f, tick_rows, k = GROUPED_CELLS[cell]
    m = (tick_rows if tokens == "tick" else tokens) * k
    stack = periods * held
    sizes = jax.ShapeDtypeStruct((stack,), jnp.int32)

    def product(lhs, rhs, sizes):
        return grouped_matmul.grouped_matmul(
            lhs, rhs, grouped_matmul.group_metadata(sizes, m))

    for kk, nn in ((d, f), (f, d)):
        args = _described(
            (jax.ShapeDtypeStruct((m, kk), jnp.bfloat16),
             jax.ShapeDtypeStruct((stack, kk, nn), jnp.bfloat16), sizes),
            one_chip)
        compiled = jax.jit(product).lower(*args).compile()
        text = compiled.as_text()
        assert "grouped_matmul" in text and "tpu_custom_call" in text
        assert compiled.memory_analysis().temp_size_in_bytes < kk * nn * 2


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_an_expert_layer_compiled_for_the_chip_makes_no_copy_of_the_stack(
        one_chip, mosaic, cell):
    """`moe_block` at a tick's rows and the cell's real expert widths, the
    stack of every period an argument and `place` traced: three grouped
    kernels, and temporaries in the class PR 33 left the A.X-K1 tick in
    (8.7 MB for the whole tick; a copy of ONE layer's experts in front of
    one product would be 210 to 352 MB)."""
    periods, held, d, f, rows, k = EXPERT_CELLS[cell]
    cfg = HybridMoEConfig(
        vocab_size=256, hidden_size=d, num_hidden_layers=4 * periods,
        num_attention_heads=64, num_key_value_heads=8, kda_heads=2,
        kda_rank=16, router_experts=8 * held, experts_held=held,
        num_experts_per_tok=k, moe_intermediate_size=f,
        shared_intermediate_size=f, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    moe = {"post_norm": bf16(d),
           "router": jax.ShapeDtypeStruct((d, 8 * held), jnp.float32),
           "router_bias": jax.ShapeDtypeStruct((8 * held,), jnp.float32),
           "shared_gate": bf16(d, f), "shared_up": bf16(d, f),
           "shared_down": bf16(f, d)}
    experts = {"gate": bf16(periods, held, d, f), "up": bf16(periods, held, d, f),
               "down": bf16(periods, held, f, d)}
    args = _described(
        (moe, experts, jax.ShapeDtypeStruct((), jnp.int32), bf16(rows, 1, d),
         jax.ShapeDtypeStruct((rows, 1), jnp.bool_)), one_chip)
    compiled = jax.jit(
        lambda *a: hybrid.moe_block(*a, cfg)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3 and "grouped_matmul" in text
    assert "ragged" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# -- the window / full softmax family, compiled for the same chip ------------------

@pytest.mark.parametrize("kind,keys", [("window", 2048 + 128),
                                       ("full", 2048), ("full", 34304)],
                         ids=["band", "bucket", "row-end"])
def test_mosaic_compiles_the_grouped_prefill_kernels_at_the_mixed_cells_shapes(
        one_chip, mosaic, kind, keys):
    """A 2048-token unit of 64 heads of 192 over 8 (window) or 4 (full) KV
    heads with values of 128: the band against its own context, the causal
    kernel against its own bucket and against a whole 34,304-place row. The
    scores stay in the kernel: what is made beside it is the operands laid
    out a KV head (nothing of [64, 2048, keys] float32 exists)."""
    G = 8 if kind == "window" else 4
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    q, k, v = shape(1, 2048, 64, 192), shape(1, keys, G, 192), shape(
        1, keys, G, 128)
    valid = jax.ShapeDtypeStruct((1, keys), jnp.int32)
    if kind == "window":
        args = _described((q, k, v, valid,
                           jax.ShapeDtypeStruct((64,), jnp.float32)), one_chip)
        fn = lambda q, k, v, valid, sink: (
            gqa_prefill_attention.window_prefill_attention(
                q, k, v, valid, sink, 128))
        name = "window_prefill_attn"
    else:
        args = _described((q, k, v, valid,
                           jax.ShapeDtypeStruct((), jnp.int32)), one_chip)
        fn = gqa_prefill_attention.full_prefill_attention
        name = "full_chunk_attn"
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert name in text and "tpu_custom_call" in text
    operands = 2 * (2048 * 64 * 192 + keys * G * (192 + 128))
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * operands


@pytest.mark.parametrize("store", ["pages", "rings"])
def test_mosaic_compiles_the_widened_kernel_at_the_mixed_cells_shapes(
        one_chip, mosaic, store):
    """64 rows of 64 query heads against keys stored 256 wide beside values
    of 128: the full layers' pages (4 KV heads, 536 logical pages a row, a
    page kept as its matrix and seen through a reshape) and the window
    layers' rings as a pool of one page a slot (8 KV heads, 128 places, a
    sink a head). XLA:TPU reads either through a bitcast: nothing
    pool-sized is made in front of the kernel."""
    slots, h = 64, 64
    if store == "pages":
        layers, pages, page, kv_h, pmax = 2, 2048, 64, 4, 536
        k = jax.ShapeDtypeStruct((layers, pages + 1, page * kv_h, 256),
                                 jnp.bfloat16)
        v = jax.ShapeDtypeStruct((layers, pages + 1, page * kv_h, 128),
                                 jnp.bfloat16)
        sink = None
    else:
        layers, pages, page, kv_h, pmax = 5, slots - 1, 128, 8, 1
        k = jax.ShapeDtypeStruct((layers, slots, page, kv_h, 256), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((layers, slots, page, kv_h, 128), jnp.bfloat16)
        sink = jax.ShapeDtypeStruct((h,), jnp.float32)
    by_head = lambda a: a.reshape(layers, pages + 1, page, kv_h, a.shape[-1])
    args = _described(
        (jax.ShapeDtypeStruct((slots, h, 256), jnp.bfloat16), k, v,
         jax.ShapeDtypeStruct((), jnp.int32),
         jax.ShapeDtypeStruct((slots, pmax), jnp.int32),
         jax.ShapeDtypeStruct((slots,), jnp.int32),
         jax.ShapeDtypeStruct((slots, pmax * page), jnp.int32))
        + (() if sink is None else (sink,)), one_chip)
    compiled = jax.jit(
        lambda q, k, v, layer, table, live, mask, *sink:
        paged_attention.paged_decode_attention(
            q, by_head(k), by_head(v), layer, table, live, mask,
            sink[0] if sink else None, 192 ** -0.5)).lower(*args).compile()
    text = compiled.as_text()
    assert "paged_decode_attn" in text and "tpu_custom_call" in text
    pool_bytes = layers * (pages + 1) * page * kv_h * 256 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_a_window_program_compiled_for_the_chip_keeps_its_two_stores_in_place(
        one_chip, mosaic, program):
    """The family at its published head shapes (64 heads, keys of 192 stored
    256 wide, values of 128, 4 and 8 KV heads, a window of 128) and small
    everything else, pages many times the weights: the outputs are the
    donated stores' buffers, nothing as large as the pages is made beside
    them (no gathered rows in the tick, no page pool turned to another
    layout by a chunk's scatter), and the kernels are in the programs."""
    from llama_pipeline_parallel_tpu.models.window_moe import decode as window_decode
    from llama_pipeline_parallel_tpu.models.window_moe import model as window
    from llama_pipeline_parallel_tpu.models.window_moe.config import (
        WindowMoEConfig,
    )

    slots, pmax, page = 4, 32, 64
    cfg = WindowMoEConfig(
        vocab_size=256, hidden_size=256, pattern=(0, 1, 1, 0),
        moe_layers=(0, 1, 1, 1), intermediate_size=256, router_experts=16,
        experts_held=8, num_experts_per_tok=4, moe_intermediate_size=128)
    params = jax.eval_shape(
        lambda: window.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: {
        **window_decode.init_page_pool(cfg, 2048, page),
        **window_decode.init_recurrent_store(cfg, slots)})
    assert pool["k"].shape == (2, 2049, page * 4, 256)
    assert pool["ring_k"].shape == (2, slots, 128, 8, 256)
    z = jax.ShapeDtypeStruct((slots,), jnp.int32)
    f = jax.ShapeDtypeStruct((slots,), jnp.float32)
    mask = jax.ShapeDtypeStruct((slots, pmax * page), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    if program == "tick":
        args = _described(
            (params, z, pool, jax.ShapeDtypeStruct((slots, pmax), jnp.int32),
             z, z, mask, z, jax.ShapeDtypeStruct((slots, 2), jnp.uint32), f,
             z, f), one_chip)
        compiled = window_decode.paged_decode_step.lower(*args, cfg).compile()
        kernels = ("paged_decode_attn",)
    else:
        ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
        args = _described(
            (params, ids, ids, ids, pool,
             jax.ShapeDtypeStruct((pmax,), jnp.int32), scalar, mask, scalar),
            one_chip)
        compiled = window_decode.paged_prefill_chunk.lower(*args, cfg).compile()
        kernels = ("window_prefill_attn", "full_chunk_attn")
    analysis = compiled.memory_analysis()

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    assert nbytes(pool) > 5 * nbytes(params)
    assert analysis.alias_size_in_bytes >= nbytes(pool)
    assert analysis.temp_size_in_bytes < nbytes(pool) // 8, analysis
    for kernel in kernels:
        assert kernel in compiled.as_text()
