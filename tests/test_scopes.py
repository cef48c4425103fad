"""The names on device work (utils/trace.py vocabulary): every matrix product
and kernel call of the hot programs carries a leaf scope, the compiled modules
and the kernels keep their names, the `profile_window` schedule counter
equals counts made by hand, and the serving tick's four phases add up."""

import inspect
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.models.llama import decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
from llama_pipeline_parallel_tpu.ops import (
    eva_prefill_attention,
    flash_attention,
    gqa_prefill_attention,
    grouped_matmul,
    latent_prefill_attention,
    paged_attention,
    paged_latent_attention,
    pallas_ce,
    pallas_prologue,
    sparse_latent_attention,
    ssm_state_step,
)
from llama_pipeline_parallel_tpu.optim import OptimizerConfig, make_optimizer
from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.parallel import train_step as ts
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh
from llama_pipeline_parallel_tpu.utils import trace

# -- (a) coverage ------------------------------------------------------------


def _lower_train_step(pp, schedule, microbatches):
    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16)   # fp32 masters, as the cells
    mesh = make_mesh(MeshConfig(pp=pp, dp=1))
    stacked = pl.stack_stages(llama.init_params(jax.random.PRNGKey(0), cfg),
                              StageManifest.for_config(cfg, pp))
    pcfg = pl.PipelineConfig(num_stages=pp, num_microbatches=microbatches,
                             schedule=schedule)
    tx, sched = make_optimizer(OptimizerConfig(
        learning_rate=1e-3, total_steps=50, warmup_steps=5))
    state = ts.init_train_state(stacked, tx, mesh)
    step = ts.make_train_step(mesh, cfg, pcfg, tx, sched, stacked)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        3, cfg.vocab_size, size=(microbatches, 16)), jnp.int32)
    batch = {"input_ids": ids, "labels": ids,
             "attention_mask": jnp.ones_like(ids),
             "position_ids": jnp.broadcast_to(
                 jnp.arange(16, dtype=jnp.int32), ids.shape)}
    return step.lower(state, batch)


def _lower_paged_decode(quant="fp"):
    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16)   # fp32 masters, as the cells
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    slots, pages_per_slot, page = 2, 4, 4
    pool = decode.init_page_pool(cfg, slots * pages_per_slot, page, quant)
    z = jnp.zeros((slots,), jnp.int32)
    return decode.paged_decode_step.lower(
        params, z, pool, jnp.zeros((slots, pages_per_slot), jnp.int32), z, z,
        jnp.zeros((slots, pages_per_slot * page), jnp.int32), z,
        jnp.zeros((slots, 2), jnp.uint32), jnp.zeros((slots,), jnp.float32),
        z, jnp.ones((slots,), jnp.float32), cfg)


def _lower_prefill():
    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16)   # fp32 masters, as the cells
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    ids = jnp.ones((1, 8), jnp.int32)
    return decode.prefill_prompt.lower(params, ids, ids, cfg, 16)


def _lower_prefill_chunk():
    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16)   # fp32 masters, as the cells
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    page, pages = 4, 4
    pool = decode.init_page_pool(cfg, 8, page)
    ids = jnp.ones((1, 8), jnp.int32)
    return decode.paged_prefill_chunk.lower(
        params, ids, ids, jnp.broadcast_to(jnp.arange(8), (1, 8)), pool,
        jnp.arange(pages, dtype=jnp.int32), jnp.int32(0),
        jnp.zeros((2, page * pages), jnp.int32), jnp.int32(0), cfg)


def _lower_prefill_span():
    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16)   # fp32 masters, as the cells
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    page, pages = 4, 4
    pool = decode.init_page_pool(cfg, 8, page)
    ids = jnp.ones((1, 5), jnp.int32)            # not a page multiple
    return decode.paged_prefill_span.lower(
        params, ids, ids, jnp.broadcast_to(jnp.arange(5), (1, 5)), pool,
        jnp.arange(pages, dtype=jnp.int32), jnp.int32(0),
        jnp.zeros((2, page * pages), jnp.int32), jnp.int32(2), cfg)


PROGRAMS = {
    # name: (lowering, module name, scopes that must appear)
    "train_step_pp1": (
        lambda: _lower_train_step(1, "1f1b", 2), "jit_train_step",
        {"embed", "attn_qkv", "attn_core", "attn_out", "mlp", "final_norm",
         "lm_head_loss", "optimizer", "grad_clip", "pp_fwd", "pp_bwd",
         "cast_weights"}),
    "train_step_pp4_1f1b": (
        lambda: _lower_train_step(4, "1f1b", 4), "jit_train_step",
        {"pp_fwd", "pp_recompute", "pp_bwd", "pp_handoff", "grad_reduce",
         "lm_head_loss", "optimizer"}),
    "train_step_pp2_zb1": (
        lambda: _lower_train_step(2, "zb1", 4), "jit_train_step",
        {"pp_fwd", "pp_recompute", "pp_bwd", "pp_w", "pp_handoff"}),
    # the fp tick gathers nothing: its attention is the `paged_decode_attn`
    # kernel under `decode_attn` (a custom_call, so held to a scope below);
    # an int8 pool's rows are still gathered and dequantized
    "paged_decode_step": (
        _lower_paged_decode, "jit_paged_decode_step",
        {"embed", "attn_qkv", "kv_write", "decode_attn", "attn_out",
         "decode_mlp", "final_norm", "lm_head", "sample", "cast_weights"}),
    "paged_decode_step_int8": (
        lambda: _lower_paged_decode("int8"), "jit_paged_decode_step",
        {"embed", "attn_qkv", "kv_write", "kv_gather", "decode_attn",
         "attn_out", "decode_mlp", "final_norm", "lm_head", "sample",
         "cast_weights"}),
    "prefill_prompt": (
        _lower_prefill, "jit_prefill_prompt",
        {"embed", "attn_qkv", "kv_write", "decode_attn", "attn_out",
         "decode_mlp", "final_norm", "lm_head"}),
    "paged_prefill_chunk": (
        _lower_prefill_chunk, "jit_paged_prefill_chunk",
        {"attn_qkv", "kv_write", "kv_gather", "decode_attn", "decode_mlp",
         "lm_head"}),
    "paged_prefill_span": (
        _lower_prefill_span, "jit_paged_prefill_span",
        {"attn_qkv", "kv_write", "kv_gather", "decode_attn", "decode_mlp",
         "lm_head"}),
}

HEAVY = r"stablehlo\.(dot_general|convolution|custom_call)\b"
# partitioning markers the compiler removes, not device work
NOT_WORK = ("@Sharding", "@SPMDFullToShardShape", "@SPMDShardToFullShape",
            "@xla.sdy.")


def _leaf(path):
    return next((p for p in reversed(path.split("/")) if p in trace.SCOPES),
                None)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_product_and_kernel_call_carries_a_leaf_scope(program, devices):
    lower, module, expected = PROGRAMS[program]
    lowered = lower()

    # as traced: every heavy operation's own name stack, before any
    # compiler pass can drop it (XLA:CPU rewrites the attention products
    # into batch dots without metadata; the TPU compiler keeps it)
    text = lowered.as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    heavy = [line for line in text.splitlines() if re.search(HEAVY, line)
             and not any(m in line for m in NOT_WORK)]
    assert heavy
    seen = set()
    for line in heavy:
        path = names.get(re.search(r"loc\((#loc\d+)\)", line).group(1), "")
        assert _leaf(path), f"no scope on {line.strip()[:120]} ({path!r})"
        seen.update(p for p in path.split("/") if p in trace.SCOPES)

    # as compiled: the module's name, and whatever metadata survived names
    # a scope; together the two texts show every scope the program uses
    compiled = lowered.compile().as_text()
    assert re.search(r"HloModule (\w+)", compiled).group(1) == module
    for line in compiled.splitlines():
        if re.search(r" (dot|convolution|custom-call)\(", line):
            path = re.search(r'op_name="([^"]*)"', line)
            if path:
                assert _leaf(path.group(1)), line.strip()[:200]
    for path in re.findall(r'op_name="([^"]*)"', compiled):
        seen.update(p for p in path.split("/") if p in trace.SCOPES)
    assert expected <= seen, sorted(expected - seen)


@pytest.mark.parametrize("module,kernels", [
    (flash_attention, ("KERNEL_FLASH_FWD", "KERNEL_FLASH_BWD_DQ",
                       "KERNEL_FLASH_BWD_DKV")),
    (pallas_ce, ("KERNEL_CE_FWD", "KERNEL_CE_BWD_DH", "KERNEL_CE_BWD_DW")),
    (pallas_prologue, ("KERNEL_PROLOGUE_FWD", "KERNEL_PROLOGUE_BWD_DX",
                       "KERNEL_PROLOGUE_BWD_DW")),
    (paged_attention, ("KERNEL_PAGED_DECODE_ATTN",)),
    (sparse_latent_attention, ("KERNEL_SPARSE_LATENT_ATTN",)),
    (paged_latent_attention, ("KERNEL_PAGED_LATENT_DECODE_ATTN",)),
    (latent_prefill_attention, ("KERNEL_LATENT_PREFILL_ATTN",)),
    (eva_prefill_attention, ("KERNEL_EVA_PREFILL_ATTN",)),
    (grouped_matmul, ("KERNEL_GROUPED_MATMUL",)),
    (ssm_state_step, ("KERNEL_SSM_STATE_STEP",)),
    (gqa_prefill_attention, ("KERNEL_FULL_CHUNK_ATTN",
                             "KERNEL_WINDOW_PREFILL_ATTN")),
])
def test_every_pallas_call_passes_its_name(module, kernels):
    source = inspect.getsource(module)
    calls = source.count("pl.pallas_call(")
    assert calls == len(kernels)
    for constant in kernels:
        assert source.count(f"name=trace.{constant},") == 1
        assert getattr(trace, constant) in trace.KERNELS
    assert len(trace.KERNELS) == 18 == len(set(trace.KERNELS))


def test_flash_kernel_name_reaches_the_lowered_program():
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    text = jax.jit(lambda q: flash_attention.flash_attention(
        q, q, q, None, causal=True)).lower(q).as_text(debug_info=True)
    assert "flash_fwd" in text


# -- (c) the schedule counter ------------------------------------------------

def test_slot_counts_of_1f1b_pp4_m16_are_the_tables():
    """22 ticks: 3 F-only, 16 F+B, 3 B-only (PR 38: the halves every stage
    masked are in no scan). F is scanned in the first 19 ticks, where stage
    s idles s slots before its first unit and 3 - s after its last, and the
    mirror for B in the last 19: 3 of 19 masked each, on every stage (6 of
    22 when every tick ran both halves)."""
    counts = pl.schedule_slot_counts(
        pl.PipelineConfig(num_stages=4, num_microbatches=16))
    assert counts == [{"stage": s, "f": 19, "f_masked": 3, "b": 19,
                       "b_masked": 3, "w": 0, "w_masked": 0}
                      for s in range(4)]


def test_slot_counts_of_zb1_count_w_slots_and_skipped_halves():
    """zb1, 2 stages, 4 micro-batches: an F segment of 1 tick, F+B of 4, B
    of 1, W of 4. F is scanned in 5 ticks (4 live a stage), B in 5, W in 4
    (all live): the halves a segment does not scan are not slots."""
    counts = pl.schedule_slot_counts(pl.PipelineConfig(
        num_stages=2, num_microbatches=4, schedule="zb1"))
    assert counts == [{"stage": s, "f": 5, "f_masked": 1, "b": 5,
                       "b_masked": 1, "w": 4, "w_masked": 0}
                      for s in range(2)]


def test_slot_counts_scale_with_flushes_and_gpipe_has_none():
    chunked = pl.schedule_slot_counts(pl.PipelineConfig(
        num_stages=4, num_microbatches=16, accum_chunks=2))
    # two flushes of 8 micro-batches: 11 F and 11 B slots of 14 ticks each,
    # 3 of each masked, on every stage
    assert [(c["f"], c["f_masked"], c["b"], c["b_masked"])
            for c in chunked] == [(22, 6, 22, 6)] * 4
    assert pl.schedule_slot_counts(pl.PipelineConfig(
        num_stages=4, num_microbatches=16, schedule="gpipe")) is None


# -- the recorder's annotate --------------------------------------------------

def test_annotate_writes_no_line_and_tells_no_listener(tmp_path):
    rec = trace.SpanRecorder(str(tmp_path / "spans.jsonl"))
    heard = []
    rec.add_listener(heard.append)
    with rec.annotate(trace.TICK_WAIT):
        time.sleep(0)
    with rec.span("serve_prefill"):
        pass
    rec.close()
    assert [r["name"] for r in heard] == ["serve_prefill"]
    assert (tmp_path / "spans.jsonl").read_text().count("\n") == 1


# -- (d) the serving tick's phases -------------------------------------------

def test_tick_phases_add_up_and_dur_keeps_its_meaning():
    """Four sums on every `serve_decode_step` span, with the recorder
    unconfigured: together they are the wall time of the engine's calls that
    dispatch a tick and collect the one before, and `dur` is still dispatch +
    wait (what `decode_tick_ms.serve` divides by `ticks`)."""
    from llama_pipeline_parallel_tpu.serve import (
        ServeConfig,
        ServeEngine,
        ServeRequest,
    )

    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=2, max_len=24, prompt_buckets=(16,), page_size=8,
        max_queue=8, decode_span_every=3))
    spans, ticks = [], []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    real_tick = engine._decode_tick

    def timed_tick():
        t0 = time.perf_counter()
        real_tick()
        ticks.append(time.perf_counter() - t0)

    engine._decode_tick = timed_tick
    try:
        handles = [engine.submit(ServeRequest(
            input_ids=[5, 6, 7], seed=i,
            gen=decode.GenerationConfig(max_new_tokens=6))) for i in range(2)]
        engine.drain(timeout_s=120)
        engine.shutdown()
    finally:
        trace.recorder().remove_listener(listener)
    assert all(len(h.result(timeout=1)) == 6 for h in handles)

    decode_spans = [s for s in spans if s["name"] == "serve_decode_step"]
    # five ticks in six calls: the first dispatches alone, the last collects
    assert sum(s["ticks"] for s in decode_spans) == len(ticks) - 1 == 5
    assert max(s["ticks"] for s in decode_spans) == 3      # decode_span_every
    assert "serve_ttft" not in {s["name"] for s in spans}
    phases = ("stage_s", "dispatch_s", "wait_s", "emit_s")
    for s in decode_spans:
        assert all(s[k] >= 0.0 for k in phases)
        assert s["dur"] == pytest.approx(s["dispatch_s"] + s["wait_s"])
    total = sum(s[k] for s in decode_spans for k in phases)
    # the phases tile the tick but for the clock reads between them
    assert total <= sum(ticks)
    assert total == pytest.approx(sum(ticks), rel=0.05, abs=2e-3)


def test_the_tick_counts_the_pages_it_reads_and_the_pages_its_rows_have():
    """`kv_pages_live` and `kv_pages_table` on every `serve_decode_step`
    span, summed over its ticks like `tokens`: a decoding row's live pages
    are its write position's page and those before it (what
    ops/paged_attention.py fetches), its table row has max_len / page_size.
    Bucket 16, page 8, rows of 5 pages, 6 tokens: 5 ticks of 2 rows write
    at 16..20, the third page."""
    from llama_pipeline_parallel_tpu.serve import (
        ServeConfig,
        ServeEngine,
        ServeRequest,
    )

    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=2, max_len=40, prompt_buckets=(16,), page_size=8,
        max_queue=8, decode_span_every=2))
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        for i in range(2):
            engine.submit(ServeRequest(
                input_ids=[5, 6, 7], seed=i,
                gen=decode.GenerationConfig(max_new_tokens=6)))
        engine.drain(timeout_s=120)
        engine.shutdown()
    finally:
        trace.recorder().remove_listener(listener)
    decode_spans = [s for s in spans if s["name"] == "serve_decode_step"]
    assert [s["ticks"] for s in decode_spans] == [2, 2, 1]
    assert [s["tokens"] for s in decode_spans] == [4, 4, 2]
    assert [s["kv_pages_live"] for s in decode_spans] == [12, 12, 6]
    assert [s["kv_pages_table"] for s in decode_spans] == [20, 20, 10]


def test_the_tick_counts_the_grid_steps_its_attention_walks(monkeypatch):
    """`kv_steps_visited` beside them: over the rows that decoded,
    `cdiv(live pages, n)`, with n the pages the kernel takes under one
    softmax update for THIS pool's shapes (asked of
    `ops/paged_attention.pages_per_step`, three here). Bucket 16, page 8,
    11 tokens: ten ticks of 2 rows write at 16..25, eight in the third page
    (one step) and two in the fourth (two steps)."""
    from llama_pipeline_parallel_tpu.ops import paged_attention
    from llama_pipeline_parallel_tpu.serve import (
        ServeConfig,
        ServeEngine,
        ServeRequest,
    )

    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    page = 8
    kv_bytes = 2 * page * cfg.num_key_value_heads * cfg.head_dim * 4
    monkeypatch.setattr(paged_attention, "_STEP_BYTES", 3 * kv_bytes)
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=2, max_len=40, prompt_buckets=(16,), page_size=page,
        max_queue=8, decode_span_every=4))
    pool = engine.slots.pool
    n = paged_attention.pages_per_step(pool["k"], pool["v"], 40 // page)
    assert n == 3
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        for i in range(2):
            engine.submit(ServeRequest(
                input_ids=[5, 6, 7], seed=i,
                gen=decode.GenerationConfig(max_new_tokens=11)))
        engine.drain(timeout_s=120)
        engine.shutdown()
    finally:
        trace.recorder().remove_listener(listener)
    decode_spans = [s for s in spans if s["name"] == "serve_decode_step"]
    assert all("kv_steps_visited" in s for s in decode_spans)
    assert sum(s["ticks"] for s in decode_spans) == 10
    # the host's own sum over the decoding rows' write positions
    steps = 2 * sum(-(-(write_pos // page + 1) // n)
                    for write_pos in range(16, 26))
    assert steps == 2 * (8 * 1 + 2 * 2)
    assert sum(s["kv_steps_visited"] for s in decode_spans) == steps
    assert sum(s["kv_pages_live"] for s in decode_spans) == 2 * (8 * 3 + 2 * 4)
    assert [s["kv_steps_visited"] for s in decode_spans] == [8, 8, 8]
