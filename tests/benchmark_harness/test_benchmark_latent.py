"""The latent-attention family in the benchmark: its job end to end at a tiny
size on the CPU (sound: correct; a served token altered where it is emitted:
not; a program that selects the most recent positions instead of the
largest scores: not; the float8 control: not), its six per-layer readers and
the four expert-layer readers of the hybrid cell on synthetic observations
of its names, and the entries' agreement with their files."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny
import synthetic_xplane as sx
from conftest import REPO

from benchmark import (
    harness,
    latent_moe_weights,
    latent_work,
    registry,
    scopes,
    xplane,
)
from benchmark.reference import latent_moe_decoder

CELL = "serve-tiny.latent"
OWN = ["latent_attn_share.serve", "index_share.serve",
       "latent_cache_share.serve", "index_kept_share.serve",
       "prefill_chunk_ms.serve", "sparse_decode_attn_roofline.serve",
       "sparse_latent_attn_roofline.serve"]
JOINED = ["moe_share.serve", "moe_experts_roofline.serve",
          "expert_load_max_over_mean.serve", "tick_ms_per_row.serve"]
_PERIOD = ["full_attention"] + ["sliding_attention"] * 3
TINY_LATENT = {
    "hidden_size": 32, "num_hidden_layers": 5, "vocab_size": 256,
    "intermediate_size": 48, "rms_norm_eps": 1e-5,
    "layer_types": ["full_attention"] + _PERIOD * 2,
    "first_k_dense_replace": 1, "apply_mla_qkv_lora_rescale": True,
    "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rope_theta": 80000000, "rope_scaling": None,
    "index_n_heads": 2, "index_head_dim": 8, "index_topk": 8,
    "swa_num_attention_heads": 2, "swa_q_lora_rank": 16,
    "swa_kv_lora_rank": 12, "swa_qk_nope_head_dim": 12,
    "swa_qk_rope_head_dim": 4, "swa_v_head_dim": 8, "swa_rope_theta": 50000,
    "sliding_window_size": 5,
    "moe_intermediate_size": 16, "n_routed_experts": 4, "router_experts": 16,
    "expert_offset": 8, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 4,
    "scoring_func": "sigmoid", "init_std": 0.15,
}


def make_root(tmp: str) -> str:
    """`benchmark_tiny`'s root with a latent configuration, a mix of prompts
    on both sides of the chunk, and a cell, added by files and entries
    alone, as a PR adds them."""
    root = benchmark_tiny.make_root(tmp)
    bdir = os.path.join(root, "benchmark")
    benchmark_tiny._dump(os.path.join(bdir, "configs", "latent.json"), {
        "name": "latent", "source": "tests", "why": "tiny", **TINY_LATENT,
        "compute_dtype": "float32", "weights_dtype": "float32",
        "reduced": {}, "assumed": {}, "layout": "cpu"})
    benchmark_tiny._dump(os.path.join(bdir, "traffic", "serve-long-tiny.json"), {
        "kind": "closed_loop", "why": "tiny", "clients": 4, "block": 4,
        "prompt_classes": [[8, 0.25], [16, 0.5], [32, 0.25]],
        "output_classes": [[4, 0.5], [8, 0.5]],
        "ramp_completions": 2, "temperature": 0.0})
    benchmark_tiny._dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "name": CELL, "config": "latent", "traffic": "serve-long-tiny",
        "chips": 1, "job": "serve_closed_latent", "why": "tiny",
        "engine": {"page_size": 4, "max_slots": 4, "max_len": 40,
                   "prompt_buckets": [8, 16, 32], "num_pages": 40,
                   "kv_quant": "fp", "prefix_cache": False,
                   "prefill_chunk_tokens": 8, "max_queue": 64,
                   "decode_span_every": 4},
        "check_requests": 3, "trace_seconds": 1.0,
        "checks": {"served_logit_gap_mean": 1e-4,
                   "selection_missed_share": 0.0}})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "latent", "source": "tests",
                             "file": "benchmark/configs/latent.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": CELL, "config": "latent",
                               "traffic": "serve-long-tiny", "chips": 1,
                               "why": "tiny"})
    listed = {"serve_tpot_ms_p90", "decode_tick_ms.serve"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in listed:
            m["workloads"].append(CELL)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in OWN:
        bench["per_layer"].append(dict(real[name], workloads=[CELL]))
    benchmark_tiny._dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("latent")))


def _run(root, seed=11, trace=False, seconds=2.0):
    return harness.run_cell(root, CELL, seed=seed, seconds=seconds,
                            trace=trace, devices=jax.devices()[:1],
                            t_start=time.time())


def test_the_latent_cell_is_correct_and_counts_its_selection(root, capsys):
    res = _run(root, seed=2 ** 31 + 9)
    out = capsys.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 6
    assert set(res["metrics"]) == {"serve_tpot_ms_p90", "setup_s"}
    for exact in ("routed_total_off_tokens_x_topk_x_layers",
                  "index_selected_off_host_count", "selection_missed_share"):
        assert f"check {exact}: value=0.0" in out
    assert "% kept" in out and "replayed 3 sampled requests" in out


def test_the_latent_cell_traced_reads_spans_and_counters(root, capsys):
    res = _run(root, trace=True)
    out = capsys.readouterr().out
    assert res["correct"] is True
    # no TPU plane in a CPU trace: the device readers find nothing to read,
    # the spans' and the counters' readers do
    assert set(res["metrics"]) == {
        "decode_tick_ms.serve", "index_kept_share.serve",
        "prefill_chunk_ms.serve"}
    kept = res["metrics"]["index_kept_share.serve"]["value"]
    assert 20.0 < kept < 100.0          # rows pass `index_topk` 8
    assert "serve_prefill spans saw" in out and "chunks," in out


def test_a_served_token_altered_where_it_is_emitted_is_not_correct(
        root, monkeypatch):
    from llama_pipeline_parallel_tpu.serve.engine import RequestHandle

    real = RequestHandle._push

    def altered(self, token):
        real(self, (token + 1) % 256 if len(self.tokens_out) % 3 == 2
             else token)

    monkeypatch.setattr(RequestHandle, "_push", altered)
    assert _run(root, seed=8, seconds=1.0)["correct"] is False


def test_a_program_that_selects_the_most_recent_positions_is_not_correct(
        root, monkeypatch, capsys):
    """"The largest scores" replaced by "the most recent positions" in the
    PROGRAM: its served tokens fall below the reference's best and its
    selection misses the reference's, while both exact counts still hold
    (it selects as many)."""
    from llama_pipeline_parallel_tpu.models.latent_moe import model as latent

    real = latent.select

    def most_recent(scores, before, own, topk):
        places = jnp.arange(scores.shape[-1], dtype=jnp.float32)
        return real(jnp.broadcast_to(places, scores.shape), before, own, topk)

    jax.clear_caches()                  # the programs are traced anew
    monkeypatch.setattr(latent, "select", most_recent)
    try:
        res = _run(root, seed=5)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    out = capsys.readouterr().out
    assert res["correct"] is False
    assert "check index_selected_off_host_count: value=0.0" in out
    for failed in ("served_logit_gap_mean", "selection_missed_share"):
        line = next(l for l in out.splitlines()
                    if l.startswith(f"check {failed}:"))
        assert line.endswith("NOT OK"), line


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_reads_a_gap_the_reference_does_not(seed):
    """The reference's own greedy continuation has gap 0 exactly; the same
    mathematics with float8 products puts other tokens first, and the widest
    gap is above the limit the tiny cell keeps (1e-3)."""
    top = latent_moe_weights.make_top(seed, TINY_LATENT)
    layer_fn = latent_moe_weights.layer_fn(seed, TINY_LATENT, jnp.float32)
    prompt = np.random.default_rng(seed).integers(0, 256, 14).tolist()
    served = []
    for _ in range(8):
        ids = jnp.asarray([prompt + served], jnp.int32)
        served.append(int(jnp.argmax(latent_moe_decoder.logits_fn(
            top, layer_fn, ids, TINY_LATENT)[0, -1])))
    args = (top, layer_fn, [prompt], [served], TINY_LATENT, 24)
    sound = latent_moe_decoder.served_token_gaps(*args)[0]
    control = latent_moe_decoder.served_token_gaps(*args, precision="fp8")[0]
    assert max(sound) == 0.0 and len(sound) == 8
    assert max(control) > 1e-3


def test_the_references_selection_is_asked_for_by_query():
    """`rows` returns the visibility of just those queries in every full
    layer: the query's own position, and `index_topk` places once the row is
    that long."""
    top = latent_moe_weights.make_top(4, TINY_LATENT)
    layer_fn = latent_moe_weights.layer_fn(4, TINY_LATENT, jnp.float32)
    prompt = np.random.default_rng(4).integers(0, 256, 20).tolist()
    gaps, masks = latent_moe_decoder.served_token_gaps(
        top, layer_fn, [prompt], [[3, 4]], TINY_LATENT, 24, rows=[[4, 19]])
    assert len(gaps[0]) == 2 and masks.shape == (2, 1, 2, 24)
    masks = np.asarray(masks)
    assert masks[:, 0, 0].sum(axis=-1).tolist() == [5, 5]       # 0..4, all
    assert masks[:, 0, 1].sum(axis=-1).tolist() == [8, 8]
    assert masks[:, 0, 0, 4].all() and masks[:, 0, 1, 19].all()
    assert not masks[:, 0, 1, 20:].any()


def test_the_programs_weights_are_the_references_layers():
    model = dict(TINY_LATENT, num_hidden_layers=9,
                 layer_types=["full_attention"] + _PERIOD * 2)
    tree = latent_moe_weights.make_program_weights(5, model, jnp.bfloat16)
    same = lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32))
    first = latent_moe_weights.make_layer(5, 0, model, jnp.bfloat16)
    for name, leaf in first["mixer"].items():
        same(tree["first"]["attn"][name], leaf)
    for name, leaf in first["mlp"].items():
        same(tree["first"]["mlp"][name], leaf)
    for i in (1, 2, 4, 5, 8):
        layer = latent_moe_weights.make_layer(5, i, model, jnp.bfloat16)
        p, j = divmod(i - 1, 4)
        mixer = (tree["periods"]["full"] if j == 0
                 else tree["periods"]["win"][j - 1])
        for name, leaf in layer["mixer"].items():
            same(mixer[name][p], leaf)
        for name, leaf in layer["moe"].items():
            same(tree["periods"]["moe"][j][name][p], leaf)
    counts = latent_moe_weights.param_count(model)
    assert counts["total"] == sum(x.size for x in jax.tree.leaves(tree))


# -- the readers on synthetic observations ------------------------------------------

TICK = "jit(paged_decode_step)/while/body/"
FILL = "jit(paged_prefill_chunk)/while/body/"
MODEL = {"hidden_size": 5120, "moe_intermediate_size": 1536,
         "num_hidden_layers": 5, "n_routed_experts": 32,
         "num_attention_heads": 128, "kv_lora_rank": 512,
         "qk_rope_head_dim": 64, "index_n_heads": 64, "index_head_dim": 128,
         "index_topk": 2048}


def _op(name, path, start, dur):
    return (sx.instruction(name), path, start, dur)


@pytest.fixture
def runs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path))
    return tmp_path


def _observe(runs, planes, spans, name="serve-cell.latent"):
    cell = types.SimpleNamespace(name=name, model=MODEL,
                                 params={"engine": {"page_size": 64}})
    run = runs / f"{cell.name}.42.1"
    run.mkdir()
    path = sx.write(run / "t.xplane.pb", planes)
    return {"kind": "serve", "cell": cell, "spans": list(spans),
            "window": (0.0, 2.0), "xplane": xplane.read(path),
            "devices": [types.SimpleNamespace(device_kind="TPU v5 lite")]}


@pytest.fixture
def latent_obs(runs):
    # two ticks and one chunk in [0, 1000) ns, busy 900 (idle [400, 500)):
    # tick 1: mla_proj 20, index_score 30, index_topk 40, latent_gather 50,
    #   sparse_attn 60, window_attn 10, ring_write 10, moe_experts 100 of
    #   which the grouped product's own kernel 60, attn_out 20, sample 60
    # tick 2: index_score 30, latent_gather 70, sparse_attn 50, moe_router
    #   10, lm_head 140
    # chunk: index_topk 80, latent_gather 40, sparse_attn 60, latent_write 20
    ops = [
        _op("fusion.1", "jit(paged_decode_step)/mla_proj/dot_general", 0, 20),
        _op("fusion.2", TICK + "index_score/dot_general", 20, 30),
        _op("sort.3", TICK + "index_topk/top_k", 50, 40),
        _op("fusion.4", TICK + "latent_gather/gather", 90, 50),
        (sx.instruction("sparse_latent_attn.5", "bf16[32,128,640]"),
         TICK + "sparse_attn/sparse_latent_attn", 140, 60),
        _op("fusion.6", TICK + "window_attn/dot_general", 200, 10),
        _op("fusion.7", TICK + "ring_write/scatter", 210, 10),
        _op("fusion.8", TICK + "moe_experts/mul", 220, 40),
        _op("ragged-dot-none.9", "ragged-dot-none", 260, 60),
        _op("fusion.10", TICK + "attn_out/dot_general", 320, 20),
        _op("sort.11", "jit(paged_decode_step)/sample/sort", 340, 60),
        _op("fusion.2", TICK + "index_score/dot_general", 500, 30),
        _op("fusion.4", TICK + "latent_gather/gather", 530, 70),
        (sx.instruction("sparse_latent_attn.5", "bf16[32,128,640]"),
         TICK + "sparse_attn/sparse_latent_attn", 600, 50),
        _op("fusion.12", TICK + "moe_router/dot_general", 650, 10),
        _op("fusion.13", "jit(paged_decode_step)/lm_head/dot_general", 660, 140),
        _op("sort.14", FILL + "branch_3_fun/index_topk/top_k", 800, 80),
        _op("fusion.15", FILL + "branch_3_fun/latent_gather/gather", 880, 40),
        (sx.instruction("sparse_latent_attn.16", "bf16[128,128,640]"),
         FILL + "branch_3_fun/sparse_attn/sparse_latent_attn", 920, 60),
        _op("fusion.17", FILL + "latent_write/scatter", 980, 20)]
    host = {"python": [("serve_tick_wait", None, 0, 400),
                       ("serve_tick_wait", None, 500, 300)]}
    counters = {"routed_total": 7680, "routed_here": 960, "experts_hit": 640,
                "expert_load_max": 160, "experts_held": 1280}
    spans = [
        {"name": "serve_decode_step", "ts": 0.2, "dur": 0.4, "ticks": 10,
         "tokens": 240, **counters, "index_visible": 2_400_000,
         "index_selected": 900_000},
        {"name": "serve_prefill", "ts": 0.7, "dur": 0.2, "bucket": 8192,
         "chunk": 2048, "offset": 4096, **counters,
         "index_visible": 20_000_000, "index_selected": 8_000_000},
        {"name": "serve_prefill", "ts": 1.0, "dur": 0.3, "bucket": 8192,
         "chunk": 2048, "offset": 6144, **counters,
         "index_visible": 28_000_000, "index_selected": 8_300_000},
        {"name": "serve_prefill", "ts": 1.4, "dur": 0.1, "bucket": 2048,
         "chunk": 2048, "offset": 0, **counters,
         "index_visible": 4_000_000, "index_selected": 4_000_000}]
    return _observe(runs, {"/device:TPU:0": {"XLA Ops": ops},
                           "/host:CPU": host}, spans)


def _roofline(flops, hbm, seconds):
    return 100.0 * max(flops / 197e12, hbm / 819e9) / seconds


SEEN, KEPT = 240_000, 90_000            # a tick's mean of the decode spans


@pytest.mark.parametrize("name,expected", [
    ("latent_attn_share.serve", 100.0 * (20 + 60 + 10 + 20 + 50 + 60) / 900),
    ("index_share.serve", 100.0 * (30 + 40 + 30 + 80) / 900),
    ("latent_cache_share.serve", 100.0 * (50 + 10 + 70 + 40 + 20) / 900),
    ("index_kept_share.serve", 100.0 * 21.2 / 54.4),
    ("prefill_chunk_ms.serve", 250.0),
    # (30 + 40 + 50 + 60 + 30 + 70 + 50) ns over two ticks
    ("sparse_decode_attn_roofline.serve", _roofline(
        SEEN * 64 * 128 * 2 + KEPT * 128 * (576 + 512) * 2,
        (SEEN * 128 + KEPT * 576) * 2, 165e-9)),
    # three calls of the kernel: 32, 32 and 128 queries of 2048 places x 640
    ("sparse_latent_attn_roofline.serve", 100.0 * sum(
        max(q * 128 * 2048 * 640 * 4 / 197e12,
            q * ((2048 * 640 + 2 * 128 * 640) * 2 + 2048 * 4) / 819e9)
        for q in (32, 32, 128)) / 170e-9),
    # the hybrid cell's four expert-layer readers, on this family's names
    ("moe_share.serve", 100.0 * (100 + 10) / 900),
    ("moe_experts_roofline.serve", _roofline(
        96 * 6 * 5120 * 1536, (64 * 3 * 5120 * 1536 + 96 * 10240) * 2, 50e-9)),
    ("expert_load_max_over_mean.serve", (160 / 40) / (960 / 1280)),
    ("tick_ms_per_row.serve", 1e3 * 0.4 / 240),
])
def test_reader_on_a_synthetic_observation_of_the_familys_names(
        latent_obs, name, expected):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read(latent_obs) == pytest.approx(expected)


@pytest.mark.parametrize("name", OWN)
def test_latent_reader_is_none_without_its_input(name, latent_obs, runs):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read({"kind": "none"}) is None
    assert reader.read(dict(latent_obs, kind="train")) is None
    # what a program without the family gives in a serving cell: spans
    # without the counters or the chunks, a trace of other names
    bare = dict(latent_obs, xplane=None, spans=[
        {k: v for k, v in s.items() if k in ("name", "ts", "dur", "ticks")}
        for s in latent_obs["spans"]])
    assert reader.read(bare) is None
    other = _observe(runs, {
        "/device:TPU:0": {"XLA Ops": [
            _op("fusion.1", TICK + "kv_gather/gather", 0, 30),
            _op("fusion.2", TICK + "moe_experts/mul", 30, 10)]},
        "/host:CPU": {"python": [("serve_tick_wait", None, 0, 40)]}},
        bare["spans"], name="serve-cell.other")
    assert reader.read(other) is None


def test_the_sparse_reads_share_cannot_pass_the_roofline_by_its_count():
    """The counts charge what the mathematics needs: one read of every
    visible index key and of every selected entry."""
    flops, hbm = latent_work.sparse_tick_work(1000, 400, MODEL)
    assert hbm == 1000 * 256 + 400 * 1152
    assert flops == 1000 * 64 * 128 * 2 + 400 * 128 * (576 + 512) * 2
    flops, hbm = latent_work.sparse_read_kernel_work(128, 128, 2048, 640)
    assert flops == 128 * 128 * 2048 * 640 * 4
    assert hbm == 128 * ((2048 * 640 + 2 * 128 * 640) * 2 + 2048 * 4)


def test_every_latent_reader_agrees_with_its_benchmark_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cell = "serve-long-32.dots3"
    for name in OWN:
        reader, entry = registry.load_layer_metric(REPO, name), entries[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
        assert entry["workloads"] == [cell]
    for name in ("decode_tick_ms.serve", "tick_host_share.serve",
                 "host_idle_ms_per_tick.serve"):
        assert entries[name]["workloads"][-1] == cell, name
    # the four expert-layer readers would read this cell as they stand (the
    # synthetic observation above), but test_benchmark_hybrid.py holds their
    # lists to the hybrid cell alone: a `benchmark` PR's to widen (PERF.md §7)
    for name in JOINED + ["kv_pool_share.serve", "kda_share.serve",
                          "state_cache_share.serve",
                          "device_idle_share.serve"]:
        assert cell not in entries[name]["workloads"], name
    loaded = registry.load_cell(REPO, cell)
    assert loaded.job == "serve_closed_latent" and loaded.chips == 1
    assert set(OWN) <= set(loaded.per_layer)
    # tokens/s swings with how many long prompts a 30 s window holds (4.4%
    # over nine seeds); the gap between tokens is a chunk + a tick and holds
    # to 0.9% (PERF.md PR 30)
    assert loaded.end_to_end == ["serve_tpot_ms_p90", "setup_s"]
    assert loaded.mix["clients"] == 32 and loaded.mix["block"] == 20
    engine = loaded.params["engine"]
    assert engine["prefill_chunk_tokens"] == 2048 and not engine["prefix_cache"]
    assert engine["num_pages"] * engine["page_size"] == \
        engine["max_slots"] * engine["max_len"]           # nothing refused


def test_the_configuration_file_states_its_cut_and_keeps_every_width():
    with open(os.path.join(
            REPO, "benchmark", "configs", "dots3-note-prev.ep8-d5.json")) as f:
        cfg = json.load(f)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 46,
                                "n_routed_experts": 256, "vocab_size": 152064}
    # the floors of a model_config cut: the dense layer and a whole period,
    # >= 8 experts a layer, >= an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 5 and len(cfg["layer_types"]) == 46
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["router_experts"] == 256 and cfg["num_experts_per_tok"] == 8
    dm = latent_moe_decoder.dims(cfg)
    assert (dm["d"], dm["f"], dm["ffn"], dm["f_heads"], dm["f_rq"],
            dm["f_rkv"], dm["f_nope"], dm["f_rope"], dm["f_v"]) == (
        5120, 1536, 13824, 128, 1024, 512, 128, 64, 128)
    assert (dm["s_heads"], dm["s_rq"], dm["s_rkv"], dm["s_nope"],
            dm["s_rope"], dm["s_v"], dm["window"]) == (
        64, 1024, 1024, 192, 64, 128, 513)
    assert (dm["i_heads"], dm["i_hd"], dm["topk"]) == (64, 128, 2048)
    counts = latent_moe_weights.param_count(cfg)
    assert 4.08e9 < counts["total"] < 4.10e9        # 8.2 GB in bfloat16
    for key in ("stands_for", "assumed", "layout", "why"):
        assert cfg[key]
    for item in ("apply_mla_qkv_lora_rescale", "attention_gate_type",
                 "sliding_window_size", "rope", "indexer", "own position",
                 "router", "left out", "init"):
        assert cfg["assumed"][item], item
    # every number of the catalog row's `config` that is not cut is here
    # under its own key, unchanged
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["swa_kv_lora_rank"],
            cfg["index_topk"], cfg["sliding_window_size"],
            cfg["moe_intermediate_size"], cfg["rope_theta"],
            cfg["swa_rope_theta"]) == (1024, 512, 1024, 2048, 513, 1536,
                                       80000000, 50000)
