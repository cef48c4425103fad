"""A.X-K1's block in the benchmark: its job end to end at a tiny size on the
CPU (sound: correct; a served token altered where it is emitted: not; the
float8 control: not; the softmax scale without YaRN's factor: not), its four
per-layer readers on a synthetic trace and spans of its names, the
entries' agreement with their files, the cell's file against the mix and the
engine, and the configuration file against the catalog row."""

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

from benchmark import harness, mla_moe_weights, mla_work, registry, scopes, xplane
from benchmark.reference import mla_moe_decoder

CELL = "serve-tiny.mla"
REAL_CELL = "serve-longdoc-32.a.x-k1"
OWN = ["dense_latent_read_share.serve", "latent_decode_attn_roofline.serve",
       "latent_prefill_attn_roofline.serve", "latent_visible_per_row.serve"]
# accepted readers this cell prints as notes: the tick readers' lists are
# held to end in the other latent cell (test_benchmark_latent.py), the
# engine's three move tokens/s, which this cell does not report
NOTED = ["decode_tick_ms.serve", "tick_host_share.serve",
         "host_idle_ms_per_tick.serve", "device_idle_share.serve",
         "queue_wait_ms_p90.serve", "ttft_ms_p90.serve"]
TINY_MLA = {
    "hidden_size": 32, "num_hidden_layers": 5, "vocab_size": 256,
    "intermediate_size": 48, "rms_norm_eps": 1e-6,
    "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "rope_theta": 100,
    "rope_scaling": {"beta_fast": 2, "beta_slow": 0.5, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 0.5,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "moe_intermediate_size": 16, "n_routed_experts": 4, "router_experts": 16,
    "expert_offset": 8, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "num_experts_per_tok": 4,
    "scoring_func": "sigmoid", "topk_method": "none", "init_std": 0.15,
}


def make_root(tmp: str) -> str:
    """`benchmark_tiny`'s root with the configuration, a mix of prompts on
    both sides of the chunk, and a cell, added by files and entries alone,
    as a PR adds them."""
    root = benchmark_tiny.make_root(tmp)
    bdir = os.path.join(root, "benchmark")
    benchmark_tiny._dump(os.path.join(bdir, "configs", "mla.json"), {
        "name": "mla", "source": "tests", "why": "tiny", **TINY_MLA,
        "compute_dtype": "float32", "weights_dtype": "float32",
        "reduced": {}, "assumed": {}, "layout": "cpu"})
    benchmark_tiny._dump(os.path.join(bdir, "traffic", "serve-long-tiny.json"), {
        "kind": "closed_loop", "why": "tiny", "clients": 4, "block": 4,
        "prompt_classes": [[8, 0.25], [16, 0.5], [32, 0.25]],
        "output_classes": [[4, 0.5], [8, 0.5]],
        "ramp_completions": 2, "temperature": 0.0})
    benchmark_tiny._dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "name": CELL, "config": "mla", "traffic": "serve-long-tiny",
        "chips": 1, "job": "serve_closed_mla", "why": "tiny",
        "engine": {"page_size": 4, "max_slots": 4, "max_len": 40,
                   "prompt_buckets": [8, 16, 32], "num_pages": 40,
                   "kv_quant": "fp", "prefix_cache": False,
                   "prefill_chunk_tokens": 8, "max_queue": 64,
                   "decode_span_every": 4},
        "check_requests": 3, "trace_seconds": 1.0,
        "notes_from": ["queue_wait_ms_p90.serve", "tick_ms_per_row.serve"],
        "checks": {"served_logit_gap_mean": 1e-4, "served_logit_gap": 1e-3}})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "mla", "source": "tests",
                             "file": "benchmark/configs/mla.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": CELL, "config": "mla",
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
    return make_root(str(tmp_path_factory.mktemp("mla")))


def _run(root, seed=11, trace=False, seconds=2.0):
    return harness.run_cell(root, CELL, seed=seed, seconds=seconds,
                            trace=trace, devices=jax.devices()[:1],
                            t_start=time.time())


def test_the_cell_is_correct_and_counts_what_its_rows_read(root, capsys):
    res = _run(root, seed=2 ** 31 + 9)
    out = capsys.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 6
    assert set(res["metrics"]) == {"serve_tpot_ms_p90", "setup_s"}
    for exact in ("routed_total_off_tokens_x_topk_x_layers",
                  "latent_visible_off_host_count"):
        assert f"check {exact}: value=0.0" in out
    for compared in ("served_logit_gap_mean", "served_logit_gap"):
        assert f"check {compared}: value=" in out
    assert "tokens/s; gap between tokens over" in out    # both are printed
    # what is resident: no index pages, no ring
    assert "'index_pages_bytes': 0" in out and "'ring_store_bytes': 0" in out
    assert "dense read: the ticks saw" in out and "tokens/s" in out


def test_the_cell_traced_reads_spans_and_counters(root, capsys):
    res = _run(root, trace=True)
    out = capsys.readouterr().out
    assert res["correct"] is True
    # no TPU plane in a CPU trace: the device readers find nothing to read,
    # the spans' and the counter's readers do
    assert set(res["metrics"]) == {"decode_tick_ms.serve",
                                   "latent_visible_per_row.serve"}
    seen = res["metrics"]["latent_visible_per_row.serve"]["value"]
    assert 8.0 < seen < 40.0            # contexts of 9 to 39 positions
    # the accepted readers of this family's names, printed and not reported
    assert "serve: note queue_wait_ms_p90.serve = " in out
    assert "serve: note tick_ms_per_row.serve = " in out


def test_a_served_token_altered_where_it_is_emitted_is_not_correct(
        root, monkeypatch):
    from llama_pipeline_parallel_tpu.serve.engine import RequestHandle

    real = RequestHandle._push

    def altered(self, token):
        real(self, (token + 1) % 256 if len(self.tokens_out) % 3 == 2
             else token)

    monkeypatch.setattr(RequestHandle, "_push", altered)
    assert _run(root, seed=8, seconds=1.0)["correct"] is False


def _not_ok(out: str) -> set:
    return {line.split()[1].rstrip(":") for line in out.splitlines()
            if line.startswith("check ") and line.endswith("NOT OK")}


@pytest.mark.parametrize("seed", [8, 2 ** 31 + 5])
def test_the_committed_float8_control_is_not_correct_by_the_gap_alone(
        root, monkeypatch, capsys, seed):
    """`SERVE_CLOSED_MLA_CONTROL=fp8` puts the float8 reference in the
    program's place: the harness reports `correct: false`, by the limits on
    the gap and by no other check (the counts and the traffic are the sound
    run's)."""
    job = registry.load_job(root, "serve_closed_mla")
    monkeypatch.setenv(job.CONTROL_ENV, "fp8")
    res = _run(root, seed=seed, seconds=1.0)
    out = capsys.readouterr().out
    assert res["correct"] is False and res["failed"] == 0
    assert "serve: CONTROL (SERVE_CLOSED_MLA_CONTROL=fp8)" in out
    failing = _not_ok(out)
    assert failing and failing <= {"served_logit_gap_mean", "served_logit_gap"}


def test_a_control_precision_the_reference_does_not_know_is_refused(
        root, monkeypatch):
    job = registry.load_job(root, "serve_closed_mla")
    monkeypatch.setenv(job.CONTROL_ENV, "float4")
    with pytest.raises(ValueError, match="unknown precision"):
        _run(root, seed=8, seconds=1.0)


def _greedy(seed, n_prompt=14, n_new=8, alter=()):
    top = mla_moe_weights.make_top(seed, TINY_MLA)
    layer_fn = mla_moe_weights.layer_fn(seed, TINY_MLA, jnp.float32)
    prompt = np.random.default_rng(seed).integers(0, 256, n_prompt).tolist()
    served = []
    for _ in range(n_new):
        ids = jnp.asarray([prompt + served], jnp.int32)
        served.append(int(jnp.argmax(mla_moe_decoder.logits_fn(
            top, layer_fn, ids, TINY_MLA, alter=alter)[0, -1])))
    return (top, layer_fn, [prompt], [served], TINY_MLA, 24)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_reads_a_gap_the_reference_does_not(seed):
    """The reference's own greedy continuation has gap 0 exactly; the same
    mathematics with float8 products puts other tokens first, and the widest
    gap is above the limit the tiny cell keeps (1e-3)."""
    args = _greedy(seed)
    sound = mla_moe_decoder.served_token_gaps(*args)[0]
    control = mla_moe_decoder.served_token_gaps(*args, precision="fp8")[0]
    assert max(sound) == 0.0 and len(sound) == 8
    assert max(control) > 1e-3


def test_a_model_served_without_yarns_softmax_factor_is_another_model():
    """Tokens chosen under the plain 1 / sqrt(head) scale lie below the
    reference's best under YaRN's."""
    args = _greedy(2, alter=("plain_scale",))
    assert max(mla_moe_decoder.served_token_gaps(*args)[0]) > 1e-3


def test_the_programs_weights_are_the_references_layers():
    tree = mla_moe_weights.make_program_weights(5, TINY_MLA, jnp.bfloat16)
    same = lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32))
    first = mla_moe_weights.make_layer(5, 0, TINY_MLA, jnp.bfloat16)
    for name, leaf in first["mixer"].items():
        same(tree["first"]["attn"][name], leaf)
    for name, leaf in first["mlp"].items():
        same(tree["first"]["mlp"][name], leaf)
    assert tree["periods"]["win"] == [] and len(tree["periods"]["moe"]) == 1
    for i in (1, 2, 4):
        layer = mla_moe_weights.make_layer(5, i, TINY_MLA, jnp.bfloat16)
        for name, leaf in layer["mixer"].items():
            same(tree["periods"]["full"][name][i - 1], leaf)
        for name, leaf in layer["moe"].items():
            same(tree["periods"]["moe"][0][name][i - 1], leaf)
    counts = mla_moe_weights.param_count(TINY_MLA)
    assert counts["total"] == sum(x.size for x in jax.tree.leaves(tree))


def test_the_hosts_count_of_what_the_ticks_saw():
    """A request of n prompt tokens and m received tokens ran m - 1 ticks
    at contexts n + 1 .. n + m - 1; each warm-up bucket one tick at b + 1."""
    job = registry.load_job(REPO, "serve_closed_mla")
    records = [{"request": {"prompt": [0] * 10}, "tokens": [1, 2, 3, 4]},
               {"request": {"prompt": [0] * 7}, "tokens": [1]},
               {"request": {"prompt": [0] * 5}, "tokens": []}]
    assert job.host_latent_visible(records, [8, 16], 5) == 5 * (
        9 + 17 + 11 + 12 + 13)


# -- the readers on synthetic observations ------------------------------------------

TICK = "jit(paged_decode_step)/while/body/closed_call/"
FILL = "jit(paged_prefill_chunk)/while/body/closed_call/branch_2_fun/"
MODEL = {"hidden_size": 7168, "num_hidden_layers": 5,
         "num_attention_heads": 64, "kv_lora_rank": 512,
         "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128}


def _op(name, path, start, dur):
    return (sx.instruction(name), path, start, dur)


@pytest.fixture
def runs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path))
    return tmp_path


def _observe(runs, planes, spans, name="serve-cell.mla"):
    cell = types.SimpleNamespace(name=name, model=MODEL,
                                 params={"engine": {"page_size": 64}})
    run = runs / f"{cell.name}.42.1"
    run.mkdir()
    path = sx.write(run / "t.xplane.pb", planes)
    return {"kind": "serve", "cell": cell, "spans": list(spans),
            "window": (0.0, 2.0), "xplane": xplane.read(path),
            "devices": [types.SimpleNamespace(device_kind="TPU v5 lite")]}


@pytest.fixture
def mla_obs(runs):
    # two ticks (5 kernel calls each) and one chunk (5 calls) in [0, 1000)
    # ns, busy 900 (idle [400, 500)):
    # tick 1: mla_proj 50, five kernels of 30, moe_experts 100, lm_head 100
    # tick 2: five kernels of 20, latent_write 20, moe_router 80, sample 100
    # chunk: mla_proj 40 (the expansion), five kernels of 20, attn_out 60
    kernel = lambda n, path, start, dur: (
        sx.instruction(n, "bf16[32,64,512]"), path, start, dur)
    ops = [_op("fusion.1", TICK + "mla_proj/dot_general", 0, 50)]
    ops += [kernel("paged_latent_decode_attn.4",
                   TICK + "latent_read/paged_latent_decode_attn",
                   50 + 30 * i, 30) for i in range(5)]
    ops += [_op("fusion.2", TICK + "moe_experts/mul", 200, 100),
            _op("fusion.3", "jit(paged_decode_step)/lm_head/dot_general",
                300, 100)]
    ops += [kernel("paged_latent_decode_attn.4",
                   TICK + "latent_read/paged_latent_decode_attn",
                   500 + 20 * i, 20) for i in range(5)]
    ops += [_op("fusion.5", TICK + "latent_write/scatter", 600, 20),
            _op("fusion.6", TICK + "moe_router/dot_general", 620, 80),
            _op("fusion.7", "jit(paged_decode_step)/sample/argmax", 700, 100),
            _op("fusion.8", FILL + "mla_proj/dot_general", 800, 40)]
    ops += [kernel("latent_prefill_attn.9",
                   FILL + "latent_read_prefill/latent_prefill_attn",
                   840 + 20 * i, 20) for i in range(5)]
    ops += [_op("fusion.10", FILL + "attn_out/dot_general", 940, 60)]
    host = {"python": [("serve_tick_wait", None, 0, 400),
                       ("serve_tick_wait", None, 500, 300)]}
    counters = {"routed_total": 7680, "routed_here": 960, "experts_hit": 640,
                "expert_load_max": 160, "experts_held": 1280}
    spans = [
        {"name": "serve_decode_step", "ts": 0.2, "dur": 0.4, "ticks": 10,
         "tokens": 300, **counters, "latent_visible": 10_500_000},
        {"name": "serve_prefill", "ts": 0.7, "dur": 0.2, "bucket": 8192,
         "chunk": 2048, "offset": 4096, **counters,
         "latent_visible": 50_000_000},
        {"name": "serve_prefill", "ts": 1.4, "dur": 0.1, "bucket": 2048,
         "chunk": 2048, "offset": 0, **counters,
         "latent_visible": 10_000_000}]
    return _observe(runs, {"/device:TPU:0": {"XLA Ops": ops},
                           "/host:CPU": host}, spans)


def _roofline(flops, hbm, seconds):
    return 100.0 * max(flops / 197e12, hbm / 819e9) / seconds


SEEN = 1_050_000                        # a tick's mean of the decode span


@pytest.mark.parametrize("name,expected", [
    ("dense_latent_read_share.serve", 100.0 * (150 + 100 + 100) / 900),
    # 250 ns in 10 calls: two ticks of five layers
    ("latent_decode_attn_roofline.serve", _roofline(
        SEEN * 64 * (576 + 512) * 2, SEEN * 576 * 2, 125e-9)),
    # a unit's mean: 30M pairs, 2048 queries, 4096 positions; 100 ns in 5
    # calls: one unit of five layers
    ("latent_prefill_attn_roofline.serve", _roofline(
        30e6 * 64 * 320 * 2,
        5 * 2 * (2048 * 64 * 320 + 4096 * (64 * 256 + 64)), 100e-9)),
    ("latent_visible_per_row.serve", 10_500_000 / (300 * 5)),
])
def test_reader_on_a_synthetic_observation_of_the_familys_names(
        mla_obs, name, expected):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read(mla_obs) == pytest.approx(expected)


@pytest.mark.parametrize("name", OWN)
def test_reader_is_none_without_its_input(name, mla_obs, runs):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read({"kind": "none"}) is None
    assert reader.read(dict(mla_obs, kind="train")) is None
    # what a program without the dense read gives in a serving cell (the
    # parent, another family): spans without the counter, a trace of other
    # names
    bare = dict(mla_obs, xplane=None, spans=[
        {k: v for k, v in s.items() if k in ("name", "ts", "dur", "ticks",
                                              "tokens", "chunk", "offset")}
        for s in mla_obs["spans"]])
    assert reader.read(bare) is None
    other = _observe(runs, {
        "/device:TPU:0": {"XLA Ops": [
            _op("fusion.1", TICK + "latent_gather/gather", 0, 30),
            _op("fusion.2", TICK + "sparse_attn/dot_general", 30, 10)]},
        "/host:CPU": {"python": [("serve_tick_wait", None, 0, 40)]}},
        bare["spans"], name="serve-cell.other")
    assert reader.read(other) is None


def test_the_dense_reads_shares_cannot_pass_the_roofline_by_their_count():
    """The counts charge what the mathematics needs: one read of every
    visible entry at its published size, the products of every visible
    pair."""
    flops, hbm = mla_work.dense_tick_work(1000, MODEL)
    assert hbm == 1000 * 1152
    assert flops == 1000 * 64 * 1088 * 2
    assert flops / hbm == pytest.approx(120.9, abs=0.1)     # v5e: 240
    flops, hbm = mla_work.prefill_unit_work(1000, 8, 24, MODEL)
    assert flops == 1000 * 64 * 320 * 2
    assert hbm == 5 * 2 * (8 * 64 * 320 + 24 * (64 * 256 + 64))


def test_every_reader_agrees_with_its_benchmark_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in OWN:
        reader, entry = registry.load_layer_metric(REPO, name), entries[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
        assert entry["workloads"] == [REAL_CELL]
        assert entry["moves"] == "serve_tpot_ms_p90"
    # appended to the list of the metric ISSUE 32 named, and to no other
    assert entries["serve_tpot_ms_p90"]["workloads"][-1] == REAL_CELL
    assert REAL_CELL not in entries["serve_tokens_per_s"]["workloads"]
    loaded = registry.load_cell(REPO, REAL_CELL)
    assert loaded.job == "serve_closed_mla" and loaded.chips == 1
    assert loaded.end_to_end == ["serve_tpot_ms_p90", "setup_s"]
    assert set(loaded.per_layer) == set(OWN)
    # accepted readers of this family's names stay with the cells they have
    # (test_benchmark_latent.py, test_benchmark_hybrid.py): notes here
    assert set(NOTED) <= set(loaded.params["notes_from"])
    for name in loaded.params["notes_from"]:
        assert REAL_CELL not in entries[name]["workloads"], name
        registry.load_layer_metric(REPO, name)


def test_the_cells_file_fits_the_mix_and_the_engine():
    loaded = registry.load_cell(REPO, REAL_CELL)
    mix, engine = loaded.mix, loaded.params["engine"]
    assert mix["clients"] == 32 == engine["max_slots"] and mix["block"] == 20
    # the mix ISSUE 32 fixed before any code, as given
    assert mix["prompt_classes"] == [[2048, 0.10], [4096, 0.20],
                                     [8192, 0.40], [16384, 0.30]]
    assert mix["output_classes"] == [[128, 0.10], [256, 0.30], [512, 0.30],
                                     [1024, 0.20], [2048, 0.10]]
    assert mix["temperature"] == 0.0 and mix["ramp_completions"] == 32
    assert sum(n * s for n, s in mix["output_classes"]) == pytest.approx(652.8)
    assert loaded.params["trace_seconds"] == 4.0    # as the other serving cells
    # every class a whole number of a block's requests, none empty
    for _, share in mix["prompt_classes"] + mix["output_classes"]:
        assert share > 0
        assert abs(share * mix["block"] - round(share * mix["block"])) < 1e-9
    assert engine["prefill_chunk_tokens"] == 2048 and not engine["prefix_cache"]
    assert engine["kv_quant"] == "fp" and engine["page_size"] == 64
    assert [c for c, _ in mix["prompt_classes"]] == engine["prompt_buckets"]
    # the longest prompt and the longest answer fit a row; nothing refused
    assert engine["max_len"] == 16384 + 2048
    assert engine["num_pages"] * engine["page_size"] == \
        engine["max_slots"] * engine["max_len"]
    # the gap's mean is the one limit this cell brings: the float8 control
    # fails by it and by nothing else
    assert set(loaded.params["checks"]) == {"served_logit_gap_mean"}
    for name, why in loaded.params["checks_why"].items():
        assert why and "TO BE SET" not in why, name
    assert set(loaded.params["checks"]) <= set(loaded.params["checks_why"])


def test_the_configuration_file_states_its_cut_and_keeps_every_width():
    with open(os.path.join(
            REPO, "benchmark", "configs", "a.x-k1.ep16-d5.json")) as f:
        cfg = json.load(f)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192, "vocab_size": 163840}
    # the floors of a model_config cut: the dense layer and four layers
    # after it, >= 8 experts a layer, >= an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 5 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["router_experts"] == 192 and cfg["num_experts_per_tok"] == 8
    # every number of the catalog row's `config` that is not cut is here
    # under its own key, unchanged
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 131072,
        "model_type": "axk1", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_key_value_heads": 64,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128}
    assert {k: cfg[k] for k in catalog} == catalog
    dm = mla_moe_decoder.dims(cfg)
    assert (dm["d"], dm["f"], dm["ffn"], dm["heads"], dm["rq"], dm["rkv"],
            dm["nope"], dm["rope"], dm["v"], dm["router"], dm["held"]) == (
        7168, 2048, 18432, 64, 1536, 512, 128, 64, 128, 192, 12)
    assert mla_moe_decoder.softmax_scale(dm) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2)
    counts = mla_moe_weights.param_count(cfg)
    assert 3.48e9 < counts["total"] < 3.50e9        # 6.98 GB in bfloat16
    assert counts["mixer"] == 101_124_096             # the ISSUE's 101.1M
    for key in ("stands_for", "assumed", "layout", "why"):
        assert cfg[key]
    for item in ("all sizes", "layer", "absorbed form", "rope_scaling",
                 "rope", "feed-forward", "topk_method", "left out", "init"):
        assert cfg["assumed"][item], item
    # the program reads the same file
    from llama_pipeline_parallel_tpu.models.latent_moe.config import (
        LatentMoEConfig,
    )
    program = LatentMoEConfig.from_published(cfg)
    assert program.period == ("full",) and not program.has_indexer
    assert not program.attention_gate and not program.lora_rescale
    assert program.window_layers == 0 and program.full_layers == 5
    assert program.kind(False).softmax_scale == pytest.approx(
        mla_moe_decoder.softmax_scale(dm))
    assert (program.held, program.router_experts) == (12, 192)
