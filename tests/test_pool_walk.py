"""How the paged programs walk the page pool (models/llama/decode.py
`_walk_pool`): the pool is the layer loop's CARRY, written through full-pool
scatters and read through full-pool gathers or, by the fp decode tick, by a
kernel given the pool whole (ops/paged_attention.py), never the loop's `xs`
/ `ys`.

These are structural tests of the traced programs, not of their results (the
parity tests in test_paged_serving.py / test_prefix_cache.py are the
correctness gate). A scan's `ys` is a fresh stacked array that a donated
argument cannot alias: with the pool as `xs` / `ys` every layer's pages were
sliced out whole, stored back whole and the pool copied once a tick, 38% of
the serving tick's busy time on the v5e (PERF.md, PR 25). Nothing but a
device trace would notice those copies coming back, so the jaxpr is held to
the shape of the walk here, and the compiled program to its memory: XLA:CPU's
`memory_analysis()` does show the aliasing (2.5 pools of temporaries with
the pool as `ys`, under a hundredth of one pool as a carry).
"""

import jax
import jax.numpy as jnp
import pytest

from llama_pipeline_parallel_tpu.models.llama import decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig

# sizes chosen so that no other array of the programs has the shape of a
# layer's pages [P + 1, PAGE, kv_h, hd] or of a layer's scales [P + 1, kv_h]
SLOTS, PAGES_PER_SLOT, PAGE, NUM_PAGES = 2, 4, 4, 11


def _args(program: str, quant: str, num_pages: int = NUM_PAGES):
    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    pool = decode.init_page_pool(cfg, num_pages, PAGE, quant)
    kv_mask = jnp.zeros((SLOTS, PAGES_PER_SLOT * PAGE), jnp.int32)
    if program == "paged_decode_step":
        z = jnp.zeros((SLOTS,), jnp.int32)
        args = (params, z, pool,
                jnp.zeros((SLOTS, PAGES_PER_SLOT), jnp.int32), z, z, kv_mask,
                z, jnp.zeros((SLOTS, 2), jnp.uint32),
                jnp.zeros((SLOTS,), jnp.float32), z,
                jnp.ones((SLOTS,), jnp.float32))
    else:
        # a whole number of pages for the chunk, neither start nor length a
        # page multiple for the span
        n, start = (8, 0) if program == "paged_prefill_chunk" else (5, 2)
        ids = jnp.ones((1, n), jnp.int32)
        args = (params, ids, ids, jnp.broadcast_to(jnp.arange(n), (1, n)),
                pool, jnp.arange(PAGES_PER_SLOT, dtype=jnp.int32),
                jnp.int32(0), kv_mask, jnp.int32(start))
    return cfg, pool, args


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(v, "jaxpr", v)       # ClosedJaxpr or Jaxpr
            if hasattr(inner, "eqns"):
                yield inner


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _sub_jaxprs(eqn):
            yield from _equations(inner)


def _layer_loop(jaxpr, n_layers: int):
    loops = [e for e in _equations(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == n_layers]
    assert len(loops) == 1, [e.primitive.name for e in loops]
    return loops[0]


def _without_leading_ones(shape):
    shape = tuple(shape)
    while shape and shape[0] == 1:
        shape = shape[1:]
    return shape


@pytest.mark.parametrize("quant", ["fp", "int8"])
@pytest.mark.parametrize("program", ["paged_decode_step",
                                     "paged_prefill_chunk",
                                     "paged_prefill_span"])
def test_the_pool_is_carried_through_the_layer_loop(program, quant):
    cfg, pool, args = _args(program, quant)
    fn = getattr(decode, program)
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, cfg))(*args).jaxpr
    loop = _layer_loop(jaxpr, cfg.num_hidden_layers)

    n_consts, n_carry = loop.params["num_consts"], loop.params["num_carry"]
    carried = [(v.aval.shape, v.aval.dtype)
               for v in loop.invars[n_consts:n_consts + n_carry]]
    scanned = [v.aval.shape for v in loop.invars[n_consts + n_carry:]]
    stacked = [v.aval.shape for v in loop.outvars[n_carry:]]

    # the pool (and an int8 pool's scales) rides in the carry, whole ...
    for name, leaf in pool.items():
        assert carried.count((leaf.shape, leaf.dtype)) >= 2, (name, carried)
    # ... and is neither sliced a layer at a time on the way in nor stacked
    # into a fresh array on the way out
    pool_shapes = {leaf.shape for leaf in pool.values()}
    assert not pool_shapes & set(scanned), scanned
    assert not pool_shapes & set(stacked), stacked

    # inside the body nothing has the size of a whole layer's pages (or
    # scales): the write scatters into the full pool, the read gathers the
    # page table's pages from it
    layer_shapes = {leaf.shape[1:] for leaf in pool.values()}
    body = loop.params["jaxpr"].jaxpr
    for eqn in _equations(body):
        for out in eqn.outvars:
            shape = _without_leading_ones(getattr(out.aval, "shape", ()))
            assert shape not in layer_shapes, (
                f"{eqn.primitive.name} yields a whole layer of the pool: "
                f"{out.aval}")


def _gathers_of_logical_rows(jaxpr, pool, table_shape):
    """Gathers whose result is `page_table`'s logical rows of one layer:
    [*table_shape, page, kv_h, hd]."""
    rows = tuple(table_shape) + pool["k"].shape[2:]
    return [e for e in _equations(jaxpr) if e.primitive.name == "gather"
            and tuple(e.outvars[0].aval.shape) == rows]


def _kernels(jaxpr):
    return [e for e in _equations(jaxpr) if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("quant", ["fp", "int8"])
@pytest.mark.parametrize("program", ["paged_decode_step",
                                     "paged_prefill_chunk",
                                     "paged_prefill_span"])
def test_only_the_fp_tick_reads_its_pages_where_they_lie(program, quant):
    """One query a row over fp pages: the kernel walks the page table
    (ops/paged_attention.py) and nothing gathers a slot's logical rows. The
    choice is made by what the program can see: an int8 pool dequantizes on
    read and the prefills' queries are longer than one token; those three
    gather as they did, keys and values (and an int8 pool's scales)."""
    cfg, pool, args = _args(program, quant)
    fn = getattr(decode, program)
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, cfg))(*args).jaxpr
    table_shape = ((SLOTS, PAGES_PER_SLOT) if program == "paged_decode_step"
                   else (1, PAGES_PER_SLOT))
    gathers = _gathers_of_logical_rows(jaxpr, pool, table_shape)
    kernels = _kernels(jaxpr)
    if program == "paged_decode_step" and quant == "fp":
        assert [e.params["name"] for e in kernels] == ["paged_decode_attn"]
        assert not gathers
        # grouped queries share a KV head's rows by shape: no `repeat_kv`
        # broadcast of [b, s, kv_h, n_rep, hd] either
        assert not [e for e in _equations(jaxpr)
                    if e.primitive.name == "broadcast_in_dim"
                    and len(e.outvars[0].aval.shape) == 5]
    else:
        assert not kernels
        assert len(gathers) == 2


@pytest.mark.parametrize("quant", ["fp", "int8"])
def test_decode_tick_temporaries_are_smaller_than_one_pool(quant):
    """Compiled with a pool several times its weights, the tick's temporaries
    stay under one pool array: the donated pool is updated in place.

    The fp tick's attention is a Pallas kernel, which XLA:CPU runs through
    Pallas's interpreter: a loop over the grid that carries EVERY operand of
    the kernel, the pool's two arrays among them (once a page block), and so
    copies them. This backend's compiled memory says nothing about that
    tick any more: its compiled check is made for the chip itself, in
    tests/test_paged_attention.py
    `test_the_fp_tick_compiled_for_the_chip_keeps_the_pool_in_place`. What
    is held here for fp is the traced program: inside the layer loop
    nothing as large as a pool array is made but the in-place writes and
    the kernel's views of their results, which the kernel reads."""
    cfg, pool, args = _args("paged_decode_step", quant, num_pages=16384)
    weights = sum(a.nbytes for a in jax.tree.leaves(args[0]))
    one_pool_array = pool["k"].nbytes
    assert one_pool_array > 5 * weights
    if quant == "fp":
        jaxpr = jax.make_jaxpr(
            lambda *a: decode.paged_decode_step(*a, cfg))(*args).jaxpr
        body = _layer_loop(jaxpr, cfg.num_hidden_layers).params["jaxpr"].jaxpr
        large = [e for e in _equations(body) for out in e.outvars
                 if getattr(out.aval, "size", 0) >= pool["k"].size]
        assert sorted(e.primitive.name for e in large) == [
            "reshape", "reshape", "scatter", "scatter"], large
        views = {e.outvars[0] for e in large if e.primitive.name == "reshape"}
        written = {e.outvars[0] for e in large
                   if e.primitive.name == "scatter"}
        assert {e.invars[0] for e in large
                if e.primitive.name == "reshape"} == written
        kernel, = _kernels(body)
        assert views <= set(kernel.invars)
        assert all(out.aval.size < pool["k"].size // 4
                   for out in kernel.outvars)
        return
    compiled = decode.paged_decode_step.lower(*args, cfg).compile()
    analysis = compiled.memory_analysis()
    if analysis is None:
        pytest.skip("this backend reports no memory analysis")
    assert analysis.temp_size_in_bytes < one_pool_array // 4, analysis
    # and the pool's buffers are the outputs' buffers
    assert analysis.alias_size_in_bytes >= sum(
        leaf.nbytes for leaf in pool.values())
