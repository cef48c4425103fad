"""The state-space / expert family in the benchmark: its job end to end at a
tiny size on the CPU (sound: correct; a served token altered where it is
emitted: not; the float8 control: not, by the gap check alone), its six
per-layer readers on synthetic observations, `ssm_work`'s counts against a
hand count at the cell's shapes, and the entries' agreement with their
files. Pins test membership, never position or equality of a list."""

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

from benchmark import harness, registry, scopes, ssm_moe_weights, ssm_work, xplane
from benchmark.reference import ssm_moe_decoder

CELL = "serve-tiny.ssm"
REAL_CELL = "serve-reason-64.nemotron3-super"
REAL_CONFIG = "nemotron-3-super-120b.ep4-d11"
READERS = ["ssm_share.serve", "ssm_step_roofline.serve",
           "ssm_scan_roofline.serve", "latent_expert_share.serve",
           "latent_experts_roofline.serve", "routed_here_per_row.serve"]
TINY_SSM = {
    "hidden_size": 32, "num_hidden_layers": 4,
    "hybrid_override_pattern": "ME*E", "vocab_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "expand": 2,
    "ssm_state_size": 8, "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "use_conv_bias": True, "mamba_proj_bias": False, "mlp_bias": False,
    "attention_bias": False, "norm_eps": 1e-5,
    "n_routed_experts": 4, "router_experts": 16, "expert_offset": 8,
    "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
    "moe_latent_size": 16, "moe_intermediate_size": 24,
    "n_shared_experts": 1, "moe_shared_expert_intermediate_size": 48,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "init_std": 0.15,
}


def make_root(tmp: str) -> str:
    """`benchmark_tiny`'s root with a state-space configuration and cell
    added by files and entries alone, as a PR adds them."""
    root = benchmark_tiny.make_root(tmp)
    bdir = os.path.join(root, "benchmark")
    benchmark_tiny._dump(os.path.join(bdir, "configs", "ssm.json"), {
        "name": "ssm", "source": "tests", "why": "tiny", **TINY_SSM,
        "compute_dtype": "float32", "weights_dtype": "float32",
        "reduced": {}, "assumed": {}, "layout": "cpu"})
    with open(os.path.join(bdir, "workloads", "serve-tiny.tiny.json")) as f:
        cell = json.load(f)
    cell.update(name=CELL, config="ssm", job="serve_closed_ssm",
                checks={"served_logit_gap_mean": 1e-4},
                notes_from=["decode_tick_ms.serve"])
    benchmark_tiny._dump(os.path.join(bdir, "workloads", CELL + ".json"), cell)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "ssm", "source": "tests",
                             "file": "benchmark/configs/ssm.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": CELL, "config": "ssm",
                               "traffic": "serve-tiny", "chips": 1,
                               "why": "tiny"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(CELL)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READERS:
        bench["per_layer"].append(dict(real[name], workloads=[CELL]))
    benchmark_tiny._dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("ssm")))


def _run(root, seed=11, trace=False, seconds=1.5):
    return harness.run_cell(root, CELL, seed=seed, seconds=seconds,
                            trace=trace, devices=jax.devices()[:1],
                            t_start=time.time())


def test_the_cell_is_correct_and_counts_its_experts_and_its_rows(root, capsys):
    res = _run(root, seed=2 ** 31 + 9)
    out = capsys.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 10
    # the two end-to-end metrics the cell reports
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "check routed_total_off_tokens_x_topk_x_layers: value=0.0" in out
    assert "check ssm_rows_off_tokens_x_layers: value=0.0" in out
    assert "check served_logit_gap_mean" in out
    assert "% of the router" in out and "state-space rows" in out


def test_the_cell_traced_prints_its_notes_and_reads_its_counter(root, capsys):
    res = _run(root, trace=True)
    out = capsys.readouterr().out
    assert res["correct"] is True
    # no TPU plane in a CPU trace: the five device readers find nothing to
    # read and the line leaves them out; the counter's reader does
    assert set(res["metrics"]) == {"routed_here_per_row.serve"}
    # 4 of 16 experts held, 4 chosen a row: 1 where the router is even
    assert 0.2 < res["metrics"]["routed_here_per_row.serve"]["value"] < 3.0
    assert "serve: note decode_tick_ms.serve = " in out


def test_a_served_token_altered_where_it_is_emitted_is_not_correct(
        root, monkeypatch):
    from llama_pipeline_parallel_tpu.serve.engine import RequestHandle

    real = RequestHandle._push

    def altered(self, token):
        real(self, (token + 1) % 256 if len(self.tokens_out) % 3 == 2
             else token)

    monkeypatch.setattr(RequestHandle, "_push", altered)
    assert _run(root, seed=8, seconds=1.0)["correct"] is False


def test_the_committed_control_fails_by_the_gap_check_alone(
        root, monkeypatch, capsys):
    """`SERVE_CLOSED_SSM_CONTROL=fp8`: the same run, the float8 reference's
    first choices in the served tokens' place; not correct, and the mean gap
    is the one check that is not OK."""
    job = registry.load_job(REPO, "serve_closed_ssm")
    monkeypatch.setenv(job.CONTROL_ENV, "fp8")
    res = _run(root, seed=5, seconds=1.0)
    out = capsys.readouterr().out
    assert res["correct"] is False and "CONTROL" in out
    not_ok = [line.split(":")[0] for line in out.splitlines()
              if line.startswith("check ") and line.endswith("NOT OK")]
    assert not_ok == ["check served_logit_gap_mean"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_reads_a_gap_the_reference_does_not(seed):
    """The reference's own greedy continuation has gap 0 exactly; the same
    mathematics with float8 products puts other tokens first, and the mean
    gap is above the limit the tiny cell keeps (1e-4)."""
    top = ssm_moe_weights.make_top(seed, TINY_SSM)
    layer_fn = ssm_moe_weights.layer_fn(seed, TINY_SSM, jnp.float32)
    prompt = np.random.default_rng(seed).integers(0, 256, 12).tolist()
    served = []
    for _ in range(20):
        ids = jnp.asarray([prompt + served], jnp.int32)
        served.append(int(jnp.argmax(ssm_moe_decoder.logits_fn(
            top, layer_fn, ids, TINY_SSM)[0, -1])))
    args = (top, layer_fn, [prompt], [served], TINY_SSM, 32)
    sound = ssm_moe_decoder.served_token_gaps(*args)[0]
    control = ssm_moe_decoder.served_token_gaps(*args, precision="fp8")[0]
    assert max(sound) == 0.0 and len(sound) == 20
    assert sum(control) / len(control) > 1e-4


def test_the_programs_weights_are_the_references_layers():
    """`make_program_weights` (one program, the served side) and
    `make_layer` (one layer at a time, the reference's side) draw the same
    values, in one layout."""
    tree = ssm_moe_weights.make_program_weights(5, TINY_SSM, jnp.bfloat16)
    for i in range(4):
        layer = ssm_moe_weights.make_layer(5, i, TINY_SSM, jnp.bfloat16)
        assert set(layer) == set(tree["layers"][i])
        for name, leaf in layer.items():
            np.testing.assert_array_equal(
                np.asarray(tree["layers"][i][name], np.float32),
                np.asarray(leaf, np.float32))
    counts = ssm_moe_weights.param_count(TINY_SSM)
    assert counts["total"] == sum(x.size for x in jax.tree.leaves(tree))


def test_the_reference_imports_nothing_of_the_program():
    import inspect

    for module in (ssm_moe_decoder, ssm_moe_weights, ssm_work):
        source = inspect.getsource(module)
        assert "import llama_pipeline_parallel_tpu" not in source
        assert "from llama_pipeline_parallel_tpu" not in source
    assert 'default_matmul_precision("highest")' in inspect.getsource(
        ssm_moe_decoder)


# -- the counts, against a hand count at the cell's shapes ---------------------------

@pytest.fixture(scope="module")
def real_model():
    with open(os.path.join(REPO, "benchmark", "configs",
                           REAL_CONFIG + ".json")) as f:
        return json.load(f)


def test_the_sizes_are_the_cells(real_model):
    assert ssm_work.sizes(real_model) == {
        "ssm_layers": 5, "expert_layers": 5, "heads": 128, "head_dim": 64,
        "state": 128, "groups": 8, "conv": 4, "chunk": 128,
        "conv_width": 10240, "latent": 1024, "width": 2688}


def test_step_work_is_a_hand_count(real_model):
    """64 rows x 5 layers: a [128, 64, 128] float32 state (4,194,304 B) and
    3 x 10,240 bfloat16 convolution inputs (61,440 B), read and written."""
    flops, hbm = ssm_work.step_work(64, ssm_work.sizes(real_model))
    assert hbm == 64 * 5 * 2 * (4_194_304 + 61_440) == 2_723_676_160
    assert flops == 5 * 64 * 5 * 128 * 64 * 128


def test_scan_work_is_a_hand_count(real_model):
    """A 1024-position unit: 8 chunks x 5 layers; a chunk has 128 x 129 / 2
    = 8,256 pairs under the mask: C B^T 2 x 8,256 x 128 x 8 groups, its
    product with x 2 x 8,256 x 64 x 128 heads, the chunk's state out and
    the state coming in 2 x 128 x 64 x 128 x 128 heads each."""
    flops, hbm = ssm_work.scan_work(1024, ssm_work.sizes(real_model))
    a_chunk = (2 * 8256 * 128 * 8 + 2 * 8256 * 64 * 128
               + 2 * (2 * 128 * 64 * 128 * 128))
    assert a_chunk == 16_908_288 + 135_266_304 + 536_870_912
    assert flops == 5 * 8 * a_chunk
    # x and y 8,192 wide, B and C 1,024 each, dt 128, two bytes each; the
    # state of a layer in and out in float32
    assert hbm == 5 * (1024 * 2 * (2 * 8192 + 2 * 1024 + 128)
                       + 2 * 4_194_304)


def test_expert_tick_work_is_a_hand_count(real_model):
    """Every held expert hit and 1,408 x 5 / 4 rows: the most a tick of 64
    rows asks for at an even router."""
    sz = ssm_work.sizes(real_model)
    flops, hbm = ssm_work.expert_tick_work(640, 1760, sz)
    assert hbm == (640 * 2 * 1024 * 2688 + 1760 * 2 * 1024) * 2
    assert flops == 1760 * 4 * 1024 * 2688
    # 11 MB an expert, as ISSUE 45 counts it
    assert ssm_work.expert_tick_work(1, 0, sz)[1] == 11_010_048


# -- the readers on synthetic observations ------------------------------------------

TICK = "jit(paged_decode_step)/"
FILL = "jit(prefill_prompt)/"


def _op(name, path, start, dur):
    return (sx.instruction(name), path, start, dur)


@pytest.fixture
def runs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path))
    return tmp_path


def _observe(runs, planes, spans, model, name="serve-cell.ssm"):
    cell = types.SimpleNamespace(name=name, model=model,
                                 params={"engine": {"page_size": 64}})
    run = runs / f"{cell.name}.42.1"
    run.mkdir()
    path = sx.write(run / "t.xplane.pb", planes)
    return {"kind": "serve", "cell": cell, "spans": list(spans),
            "xplane": xplane.read(path),
            "devices": [types.SimpleNamespace(device_kind="TPU v5 lite")]}


@pytest.fixture
def ssm_obs(runs, real_model):
    # two ticks and two prefill units in [0, 1000) ns, busy 900 (idle
    # [400, 500)):
    # tick 1: ssm_proj 20, ssm_conv 10, ssm_step 50, state_gather 20,
    #   state_write 30, ssm_norm 10, moe_latent_in 10, moe_experts 100 (the
    #   kernel under its scope), moe_latent_out 10, decode_attn 140
    # tick 2: ssm_step 70, moe_experts 80, moe_router 10, lm_head 140
    # prefills: ssm_scan 60 + 40, moe_experts 50, attn_core 50
    ops = [
        _op("fusion.1", TICK + "ssm_proj/dot_general", 0, 20),
        _op("fusion.2", TICK + "ssm_conv/mul", 20, 10),
        _op("fusion.3", TICK + "ssm_step/reduce", 30, 50),
        _op("fusion.4", TICK + "state_gather/slice", 80, 20),
        _op("fusion.5", TICK + "state_write/dynamic_update_slice", 100, 30),
        _op("fusion.6", TICK + "ssm_norm/mul", 130, 10),
        _op("fusion.7", TICK + "moe_latent_in/dot_general", 140, 10),
        _op("grouped_matmul.8", TICK + "moe_experts/pallas_call", 150, 100),
        _op("fusion.9", TICK + "moe_latent_out/dot_general", 250, 10),
        _op("fusion.10", TICK + "decode_attn/dot_general", 260, 140),
        _op("fusion.3", TICK + "ssm_step/reduce", 500, 70),
        _op("grouped_matmul.8", TICK + "moe_experts/pallas_call", 570, 80),
        _op("fusion.11", TICK + "moe_router/dot_general", 650, 10),
        _op("fusion.12", TICK + "lm_head/dot_general", 660, 140),
        _op("fusion.13", FILL + "ssm_scan/dot_general", 800, 60),
        _op("fusion.14", FILL + "moe_experts/pallas_call", 860, 50),
        _op("fusion.13", FILL + "ssm_scan/dot_general", 910, 40),
        _op("fusion.15", FILL + "attn_core/dot_general", 950, 50)]
    host = {"python": [("serve_tick_wait", None, 0, 400),
                       ("serve_tick_wait", None, 500, 300),
                       ("serve_prefill_enqueue", None, 790, 5),
                       ("serve_prefill_enqueue", None, 900, 5)]}
    spans = [
        {"name": "serve_decode_step", "ts": 1.0, "dur": 0.4, "ticks": 10,
         "tokens": 600, "routed_total": 66000, "routed_here": 16000,
         "experts_hit": 5000, "expert_load_max": 400, "experts_held": 6400,
         "expert_visits": 5000, "ssm_rows": 3000},
        {"name": "serve_decode_step", "ts": 2.0, "dur": 0.2, "ticks": 5,
         "tokens": 300, "routed_total": 33000, "routed_here": 8500,
         "experts_hit": 2500, "expert_load_max": 200, "experts_held": 3200,
         "expert_visits": 2600, "ssm_rows": 1500},
        {"name": "serve_prefill", "ts": 1.5, "dur": 0.1, "bucket": 256},
        {"name": "serve_prefill", "ts": 2.5, "dur": 0.1, "bucket": 1024}]
    return _observe(runs, {"/device:TPU:0": {"XLA Ops": ops},
                           "/host:CPU": host}, spans, real_model)


def _roofline(flops, hbm, seconds):
    return 100.0 * max(flops / 197e12, hbm / 819e9) / seconds


def _expected(name, model):
    sz = ssm_work.sizes(model)
    if name == "ssm_share.serve":
        return 100.0 * (20 + 10 + 50 + 20 + 30 + 10 + 70 + 60 + 40) / 900
    if name == "latent_expert_share.serve":
        return 100.0 * (10 + 100 + 10 + 80 + 10 + 50) / 900
    if name == "ssm_step_roofline.serve":
        # 60 rows a tick; (50 + 20 + 30 + 70) ns over two ticks
        return _roofline(*ssm_work.step_work(60, sz), 85e-9)
    if name == "ssm_scan_roofline.serve":
        # the window's mean unit is 640 positions; (60 + 40) ns over two
        return _roofline(*ssm_work.scan_work(640, sz), 50e-9)
    if name == "latent_experts_roofline.serve":
        # a tick: 500 experts hit, 24,500 / 15 rows; (100 + 80) ns over two
        return _roofline(*ssm_work.expert_tick_work(500, 24500 / 15, sz),
                         90e-9)
    return 24500 / (900 * 5)             # routed_here_per_row.serve


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_synthetic_observation(ssm_obs, real_model, name):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read(ssm_obs) == pytest.approx(_expected(name, real_model))


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_its_input(name, ssm_obs, runs, real_model):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read({"kind": "none"}) is None
    assert reader.read(dict(ssm_obs, kind="train")) is None
    # what a program without the family gives: spans without the counters,
    # a trace whose paths hold the dense vocabulary alone
    bare = dict(ssm_obs, xplane=None, spans=[
        {k: v for k, v in s.items() if k in ("name", "ts", "dur", "ticks")}
        for s in ssm_obs["spans"]])
    assert reader.read(bare) is None
    dense = _observe(runs, {
        "/device:TPU:0": {"XLA Ops": [
            _op("fusion.1", TICK + "kv_gather/gather", 0, 30),
            _op("fusion.2", TICK + "decode_mlp/dot_general", 30, 10)]},
        "/host:CPU": {"python": [("serve_tick_wait", None, 0, 40)]}},
        bare["spans"], real_model, name="serve-cell.dense")
    assert reader.read(dense) is None


# -- the entries and the files ---------------------------------------------------------

def test_every_reader_agrees_with_its_benchmark_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        reader, entry = registry.load_layer_metric(REPO, name), entries[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
        assert REAL_CELL in entry["workloads"]
    loaded = registry.load_cell(REPO, REAL_CELL)
    assert loaded.job == "serve_closed_ssm" and loaded.chips == 1
    assert set(READERS) <= set(loaded.per_layer)
    assert {"serve_tokens_per_s", "setup_s"} <= set(loaded.end_to_end)
    assert "serve_tpot_ms_p90" not in loaded.end_to_end
    assert loaded.config_name == REAL_CONFIG
    assert loaded.traffic_name == "serve-reason-64"
    for name in loaded.params["notes_from"]:
        assert name in entries and REAL_CELL not in entries[name]["workloads"]
    # every limit carries its reason
    assert set(loaded.params["checks"]) <= set(loaded.params["checks_why"])


def test_the_mix_is_the_issues(real_model):
    from benchmark import traffic

    mix = traffic.load_mix(REPO, "serve-reason-64")
    assert mix["clients"] == 64 and mix["block"] == 20
    assert mix["ramp_completions"] == 64 and mix["temperature"] == 0.0
    block = traffic.request_block(mix, 3_000_000_019, 0,
                                  real_model["vocab_size"])
    count = lambda key: {v: sum(1 for r in block if r[key] == v)
                         for v in {r[key] for r in block}}
    assert count("prompt_class") == {128: 6, 256: 6, 512: 5, 1024: 3}
    assert count("max_new_tokens") == {256: 6, 512: 8, 1024: 6}
    assert all(0 <= t < 32768 for r in block for t in r["prompt"])
    engine = registry.load_cell(REPO, REAL_CELL).params["engine"]
    # nothing is refused: the longest request fits a slot, every slot's
    # worst case fits the pool
    assert engine["max_len"] >= 1024 + 1024
    assert engine["num_pages"] * engine["page_size"] >= (
        engine["max_slots"] * engine["max_len"])
    assert (engine["prefill_chunk_tokens"], engine["prefix_cache"],
            engine["kv_quant"]) == (0, False, "fp")


def test_the_configuration_file_states_its_cut_and_keeps_every_width(
        real_model):
    cfg = real_model
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["source_url"] == cfg["source"])
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(cfg["reduced"]) == reduced
    for key, value in published["config"].items():
        if key in reduced:
            assert cfg["published"][key] == value, key
        elif key != "hybrid_override_pattern":
            assert cfg[key] == value, key
    # one whole period of the published pattern, by its published indices
    pattern = published["config"]["hybrid_override_pattern"]
    kept = cfg["kept_layers"]
    assert kept == list(range(kept[0], kept[0] + 11))
    assert cfg["hybrid_override_pattern"] == "".join(
        pattern[i] for i in kept) == "MEMEMEMEM*E"
    assert cfg["published"]["hybrid_override_pattern"] == pattern
    # the floors of a model_config cut: >= 8 experts a layer, >= an eighth
    # of the vocabulary
    assert cfg["n_routed_experts"] == 128 and cfg["router_experts"] == 512
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["num_experts_per_tok"] == 22
    assert [key[0] for key in cfg["assumed"] if key[1:2] == ":"] == list(
        "abcdefg")
    assert "FOUR" in cfg["layout"] or "four" in cfg["layout"]
    dm = ssm_moe_decoder.dims(cfg)
    assert (dm["d"], dm["H"], dm["P"], dm["N"], dm["G"], dm["latent"],
            dm["f"], dm["fs"], dm["heads"], dm["kv"], dm["hd"]) == (
        4096, 128, 64, 128, 8, 1024, 2688, 5376, 32, 2, 128)
    counts = ssm_moe_weights.param_count(cfg)
    assert counts["mamba_layer"] == 109_640_064
    assert counts["softmax_layer"] == 35_655_680
    assert counts["expert_layer"] - counts["routed_experts_per_layer"] == \
        54_530_560
    assert 9.29e9 < 2 * counts["total"] < 9.31e9       # bfloat16
    # and uncut it is the model as published: 120B-A12B
    uncut = {**cfg, **cfg["published"]}
    whole = ssm_moe_weights.param_count(uncut)
    assert 120.6e9 < whole["total"] < 120.7e9
    active = whole["total"] - 40 * (512 - 22) * 2 * 1024 * 2688
    assert 12.7e9 < active < 12.8e9
    for key in ("stands_for", "assumed", "layout", "why"):
        assert cfg[key]
