"""EvaByte's block in the benchmark: its job end to end at a tiny size on the
CPU (sound: correct; a served token altered where it is emitted: not; the
float8 control: not; the reference with its summaries left out: not), its
four per-layer readers on a synthetic trace and spans of its names,
`eva_work` against a hand count, the entries' agreement with their files,
the cell's file against the mix and the engine, and the configuration file
against the catalog row."""

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

from benchmark import eva_weights, eva_work, harness, registry, scopes, xplane
from benchmark.reference import eva_decoder

CELL = "serve-tiny.eva"
REAL_CELL = "serve-bytes-16.evabyte"
REAL_CONFIG = "evabyte-6.5b.pp4-d8"
OWN = ["eva_attn_share.serve", "eva_decode_attn_roofline.serve",
       "eva_prefill_attn_roofline.serve", "eva_visible_per_row.serve"]
NOTED = ["decode_tick_ms.serve", "tick_host_share.serve",
         "device_idle_share.serve", "queue_wait_ms_p90.serve",
         "ttft_ms_p90.serve", "prefill_chunk_ms.serve", "tick_gap_ms.serve",
         "tick_h2d_ms.serve"]
# window 32, chunk 4, pages of 8: a window is four pages, its eight
# summaries one; prompts of up to 96 and answers of up to 40 cross several
TINY_EVA = {
    "attention_class": "eva", "hidden_size": 32, "num_hidden_layers": 2,
    "vocab_size": 64, "intermediate_size": 48, "rms_norm_eps": 1e-5,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "max_position_embeddings": 256, "rope_theta": 1000,
    "window_size": 32, "chunk_size": 4, "num_pred_heads": 2,
    "norm_add_unit_offset": True, "fp32_skip_add": True, "fp32_logits": True,
    "seeded_init_std": 0.3,
}
ENGINE = {"page_size": 8, "max_slots": 4, "max_len": 136,
          "prompt_buckets": [16, 32, 64, 96], "num_pages": 40,
          "kv_quant": "fp", "prefix_cache": False,
          "prefill_chunk_tokens": 32, "max_queue": 64,
          "decode_span_every": 4}


def make_root(tmp: str) -> str:
    """`benchmark_tiny`'s root with the configuration, a mix of prompts on
    both sides of the unit and of the window, and a cell, added by files and
    entries alone, as a PR adds them."""
    root = benchmark_tiny.make_root(tmp)
    bdir = os.path.join(root, "benchmark")
    benchmark_tiny._dump(os.path.join(bdir, "configs", "eva.json"), {
        "name": "eva", "source": "tests", "why": "tiny", **TINY_EVA,
        "compute_dtype": "float32", "weights_dtype": "float32",
        "reduced": {}, "assumed": {}, "layout": "cpu"})
    benchmark_tiny._dump(os.path.join(bdir, "traffic", "serve-bytes-tiny.json"), {
        "kind": "closed_loop", "why": "tiny", "clients": 4, "block": 4,
        "prompt_classes": [[16, 0.25], [32, 0.25], [64, 0.25], [96, 0.25]],
        "output_classes": [[8, 0.5], [40, 0.5]],
        "ramp_completions": 2, "temperature": 0.0})
    benchmark_tiny._dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "name": CELL, "config": "eva", "traffic": "serve-bytes-tiny",
        "chips": 1, "job": "serve_closed_eva", "why": "tiny",
        "engine": dict(ENGINE),
        "check_requests": 3, "trace_seconds": 1.0,
        "notes_from": ["queue_wait_ms_p90.serve", "prefill_chunk_ms.serve"],
        "checks": {"served_logit_gap_mean": 1e-4}})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "eva", "source": "tests",
                             "file": "benchmark/configs/eva.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": CELL, "config": "eva",
                               "traffic": "serve-bytes-tiny", "chips": 1,
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
    return make_root(str(tmp_path_factory.mktemp("eva")))


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
    for exact in ("eva_window_visible_off_host_count",
                  "eva_summary_visible_off_host_count"):
        assert f"check {exact}: value=0.0" in out
    assert "check served_logit_gap_mean: value=" in out
    assert "tokens/s (a note: serve_tokens_per_s)" in out
    assert "page pool (window and summary pages, one pool)" in out
    assert "pooled ones (host's count" in out


def test_the_cell_traced_reads_spans_and_counters(root, capsys):
    res = _run(root, trace=True)
    out = capsys.readouterr().out
    assert res["correct"] is True
    # no TPU plane in a CPU trace: the device readers find nothing to read,
    # the spans' and the counters' readers do
    assert set(res["metrics"]) == {"decode_tick_ms.serve",
                                   "eva_visible_per_row.serve"}
    seen = res["metrics"]["eva_visible_per_row.serve"]["value"]
    # contexts of 9 to 135 positions: at most a window of exact entries and
    # four windows' 32 summaries
    assert 5.0 < seen < 64.0
    assert "eva_visible_per_row.serve: a decoding row reads" in out
    assert "serve: note queue_wait_ms_p90.serve = " in out
    assert "serve: note prefill_chunk_ms.serve = " in out


def test_a_served_token_altered_where_it_is_emitted_is_not_correct(
        root, monkeypatch):
    from llama_pipeline_parallel_tpu.serve.engine import RequestHandle

    real = RequestHandle._push

    def altered(self, token):
        real(self, (token + 1) % 64 if len(self.tokens_out) % 3 == 2
             else token)

    monkeypatch.setattr(RequestHandle, "_push", altered)
    assert _run(root, seed=8, seconds=1.0)["correct"] is False


def _not_ok(out: str) -> set:
    return {line.split()[1].rstrip(":") for line in out.splitlines()
            if line.startswith("check ") and line.endswith("NOT OK")}


@pytest.mark.parametrize("control", ["fp8", "no_summaries"])
@pytest.mark.parametrize("seed", [8, 2 ** 31 + 5])
def test_the_committed_controls_are_not_correct_by_the_gap_alone(
        root, monkeypatch, capsys, control, seed):
    """`SERVE_CLOSED_EVA_CONTROL` puts another computation in the program's
    place, the float8 reference or the reference with its summaries left
    out: the harness reports `correct: false`, by the limit on the gap and
    by no other check (the counts and the traffic are the sound run's)."""
    job = registry.load_job(root, "serve_closed_eva")
    monkeypatch.setenv(job.CONTROL_ENV, control)
    res = _run(root, seed=seed, seconds=1.0)
    out = capsys.readouterr().out
    assert res["correct"] is False and res["failed"] == 0
    assert f"serve: CONTROL (SERVE_CLOSED_EVA_CONTROL={control})" in out
    assert _not_ok(out) == {"served_logit_gap_mean"}


def test_a_control_the_job_does_not_know_is_refused(root, monkeypatch):
    job = registry.load_job(root, "serve_closed_eva")
    monkeypatch.setenv(job.CONTROL_ENV, "float4")
    with pytest.raises(KeyError, match="float4"):
        _run(root, seed=8, seconds=1.0)


def _greedy(seed, n_prompt=70, n_new=8, alter=()):
    params = eva_weights.make_reference_weights(seed, TINY_EVA, jnp.float32)
    prompt = np.random.default_rng(seed).integers(0, 64, n_prompt).tolist()
    served = []
    for _ in range(n_new):
        logits = eva_decoder.sequence_logits(params, prompt + served,
                                             TINY_EVA, 96)
        if alter:
            ids = eva_decoder._padded(prompt, served, 96)
            logits = eva_decoder.logits_fn(params, ids, TINY_EVA,
                                           alter=alter)[:len(prompt + served)]
        served.append(int(jnp.argmax(logits[-1])))
    return params, prompt, served, TINY_EVA, 96


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_both_controls_read_a_gap_the_reference_does_not(seed):
    """The reference's own greedy continuation has gap 0 exactly; the same
    mathematics with float8 products, or with no summaries, puts other
    tokens first, above the limit the tiny cell keeps (1e-4 in the mean)."""
    args = _greedy(seed)
    sound = eva_decoder.served_token_gaps(*args)
    assert max(sound) == 0.0 and len(sound) == 8
    for control in (dict(precision="fp8"), dict(alter=("no_summaries",))):
        gaps = eva_decoder.served_token_gaps(*args, **control)
        assert sum(gaps) / len(gaps) > 1e-4, control


def test_a_model_served_without_its_summaries_is_another_model():
    """Tokens chosen with `S` left empty lie below the reference's best; in
    the first window, where `S` is empty anyway, they are the same tokens."""
    args = _greedy(2, alter=("no_summaries",))
    assert max(eva_decoder.served_token_gaps(*args)) > 1e-3
    short = _greedy(2, n_prompt=10, alter=("no_summaries",))
    assert max(eva_decoder.served_token_gaps(*short)) == 0.0


def test_the_programs_weights_are_the_references():
    tree = eva_weights.make_program_weights(5, TINY_EVA, jnp.bfloat16)
    wide = eva_weights.make_reference_weights(5, TINY_EVA, jnp.bfloat16)
    same = jax.tree.map(lambda a, b: bool(
        (a.astype(jnp.float32) == b).all()) and b.dtype == jnp.float32,
        tree, wide)
    assert all(jax.tree.leaves(same))
    assert tree["lm_head"].shape == (32, 2 * 64)
    assert tree["layers"]["attn"]["mu"].shape == (2, 4, 8)
    assert float(jnp.abs(tree["layers"]["input_norm"]).max()) == 0.0
    # the layout is the program's own
    from llama_pipeline_parallel_tpu.models.eva import model as eva
    from llama_pipeline_parallel_tpu.models.eva.config import EvaConfig

    cfg = EvaConfig.from_published(TINY_EVA)
    want = jax.eval_shape(lambda: eva.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(
        lambda a: a.shape, want)
    counts = eva_weights.param_count(TINY_EVA)
    assert counts["total"] == sum(x.size for x in jax.tree.leaves(tree))
    with pytest.raises(ValueError, match="outside"):
        eva_weights.make_program_weights(2 ** 32, TINY_EVA)


def test_the_hosts_count_of_what_the_ticks_read():
    """A request of n prompt tokens and m received tokens ran m - 1 ticks
    taking in the tokens at positions n .. n + m - 2; each warm-up bucket one
    tick at position b."""
    model = {"window_size": 32, "chunk_size": 4, "num_hidden_layers": 3}
    records = [{"request": {"prompt": [0] * 30}, "tokens": [1, 2, 3, 4]},
               {"request": {"prompt": [0] * 70}, "tokens": [1]},
               {"request": {"prompt": [0] * 5}, "tokens": []}]
    # positions 16, 64 (warm-up), 30, 31, 32
    assert eva_work.host_visible(records, [16, 64], model) == (
        3 * (17 + 1 + 31 + 32 + 1), 3 * (0 + 16 + 0 + 0 + 8))


# -- the readers on synthetic observations ------------------------------------------

TICK = "jit(paged_decode_step)/while/body/closed_call/"
FILL = "jit(paged_prefill_chunk)/while/body/closed_call/"
MODEL = {"hidden_size": 4096, "num_hidden_layers": 8,
         "num_attention_heads": 32, "num_key_value_heads": 32,
         "window_size": 2048, "chunk_size": 16}


def _op(name, path, start, dur):
    return (sx.instruction(name), path, start, dur)


@pytest.fixture
def runs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path))
    return tmp_path


def _observe(runs, planes, spans, name="serve-cell.eva"):
    cell = types.SimpleNamespace(
        name=name, model=MODEL,
        params={"engine": {"page_size": 64, "max_len": 25600}})
    run = runs / f"{cell.name}.42.1"
    run.mkdir()
    path = sx.write(run / "t.xplane.pb", planes)
    return {"kind": "serve", "cell": cell, "spans": list(spans),
            "window": (0.0, 2.0), "xplane": xplane.read(path),
            "devices": [types.SimpleNamespace(device_kind="TPU v5 lite")]}


@pytest.fixture
def eva_obs(runs):
    # two ticks (8 reads each, the second with a pooling pass) and one unit
    # (8 kernel calls) in [0, 900) ns, busy 800 (idle [400, 500)):
    # tick 1: attn_qkv 40, eight reads of 30, one mask op of 10 under the
    #         same scope (once a tick, hoisted), decode_mlp 110
    # tick 2: eight reads of 20, eva_pool 30, eva_summary_write 10, lm_head 100
    # unit: eva_pool 8, eva_summary_write 2, eight kernels of 10, attn_out 10
    read = lambda start, dur: (
        sx.instruction("paged_decode_attn.3", "bf16[16,32,128]"),
        TICK + "eva_attn/paged_decode_attn", start, dur)
    ops = [_op("fusion.1", TICK + "attn_qkv/dot_general", 0, 40),
           _op("fusion.2", "jit(paged_decode_step)/eva_attn/broadcast", 40, 10)]
    ops += [read(50 + 30 * i, 30) for i in range(8)]
    ops += [_op("fusion.3", TICK + "decode_mlp/dot_general", 290, 110)]
    ops += [read(500 + 20 * i, 20) for i in range(8)]
    ops += [_op("fusion.4", "jit(paged_decode_step)/cond/branch_1_fun/while/"
                "body/eva_pool/reduce", 660, 30),
            _op("fusion.5", "jit(paged_decode_step)/cond/branch_1_fun/while/"
                "body/eva_summary_write/scatter", 690, 10),
            _op("fusion.6", "jit(paged_decode_step)/lm_head/dot_general",
                700, 100),
            _op("fusion.7", FILL + "eva_pool/reduce", 800, 8),
            _op("fusion.8", FILL + "eva_summary_write/scatter", 808, 2)]
    ops += [(sx.instruction("eva_prefill_attn.9", "bf16[1,2048,4096]"),
             FILL + "eva_attn_prefill/eva_prefill_attn", 810 + 10 * i, 10)
            for i in range(8)]
    ops += [_op("fusion.10", FILL + "attn_out/dot_general", 890, 10)]
    host = {"python": [("serve_tick_wait", None, 0, 400),
                       ("serve_tick_wait", None, 500, 300)]}
    spans = [
        {"name": "serve_decode_step", "ts": 0.2, "dur": 0.4, "ticks": 10,
         "tokens": 160, "eva_window_visible": 1_300_000,
         "eva_summary_visible": 700_000, "eva_summaries_written": 1024},
        {"name": "serve_prefill", "ts": 0.7, "dur": 0.2, "bucket": 8192,
         "chunk": 2048, "offset": 4096, "eva_window_visible": 16_785_408,
         "eva_summary_visible": 4_194_304, "eva_summaries_written": 1024},
        {"name": "serve_prefill", "ts": 1.4, "dur": 0.1, "bucket": 4096,
         "chunk": 2048, "offset": 0, "eva_window_visible": 8_000_000,
         "eva_summary_visible": 0, "eva_summaries_written": 0}]
    return _observe(runs, {"/device:TPU:0": {"XLA Ops": ops},
                           "/host:CPU": host}, spans)


def _roofline(flops, hbm, seconds):
    return 100.0 * max(flops / 197e12, hbm / 819e9) / seconds


SEEN = 200_000                          # a tick's mean of the decode span
PAIRS = (16_785_408 + 4_194_304 + 8_000_000) / 2


@pytest.mark.parametrize("name,expected", [
    # tick: 10 + 240 + 160 under eva_attn, 30 + 10 pooling; unit: 8 + 2 + 80
    ("eva_attn_share.serve", 100.0 * (410 + 40 + 90) / 800),
    # 410 ns under the scope; the costliest instruction ran 16 times: two
    # ticks of eight layers
    ("eva_decode_attn_roofline.serve", _roofline(
        SEEN * 2 * 2 * 128 * 32, SEEN * 16384, 205e-9)),
    # a unit's mean: 14.5M pairs, 2048 queries given 1600 + 2048 + 2048
    # entries; 80 ns in 8 calls: one unit of eight layers
    ("eva_prefill_attn_roofline.serve", _roofline(
        PAIRS * 2 * 2 * 128 * 32,
        8 * (2048 * 2 * 4096 * 2 + (1600 + 2048 + 2048) * 16384), 80e-9)),
    ("eva_visible_per_row.serve", 2_000_000 / (160 * 8)),
])
def test_reader_on_a_synthetic_observation_of_the_familys_names(
        eva_obs, name, expected):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read(eva_obs) == pytest.approx(expected)


def test_the_share_reader_prints_its_parts_tick_and_prefill_apart(
        eva_obs, capsys):
    registry.load_layer_metric(REPO, "eva_attn_share.serve").read(eva_obs)
    out = capsys.readouterr().out
    pct = lambda ns: f"{100.0 * ns / 800:.2f}"
    assert f"eva_pool {pct(30)} + {pct(8)}" in out
    assert f"eva_summary_write {pct(10)} + {pct(2)}" in out
    assert f"eva_attn {pct(410)} + {pct(0)}" in out
    assert f"eva_attn_prefill {pct(0)} + {pct(80)}" in out
    registry.load_layer_metric(REPO, "eva_visible_per_row.serve").read(eva_obs)
    out = capsys.readouterr().out
    assert "reads 1015.6 exact entries of its window and 546.9 pooled" in out
    assert "35.0% pooled" in out and "mean context of 9766 positions" in out


@pytest.mark.parametrize("name", OWN)
def test_reader_is_none_without_its_input(name, eva_obs, runs):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read({"kind": "none"}) is None
    assert reader.read(dict(eva_obs, kind="train")) is None
    # what a program without the family gives in a serving cell (the parent,
    # another family): spans without the counters, a trace of other names
    bare = dict(eva_obs, xplane=None, spans=[
        {k: v for k, v in s.items() if k in ("name", "ts", "dur", "ticks",
                                              "tokens", "chunk", "offset")}
        for s in eva_obs["spans"]])
    assert reader.read(bare) is None
    other = _observe(runs, {
        "/device:TPU:0": {"XLA Ops": [
            _op("fusion.1", TICK + "decode_attn/paged_decode_attn", 0, 30),
            _op("fusion.2", TICK + "decode_mlp/dot_general", 30, 10)]},
        "/host:CPU": {"python": [("serve_tick_wait", None, 0, 40)]}},
        bare["spans"], name="serve-cell.other")
    assert reader.read(other) is None


def test_the_work_counts_are_a_hand_count_and_cannot_pass_the_roofline():
    """One entry of either kind is a key and a value a head, 16,384 B at the
    published sizes; a visible entry costs a score product and a weighted
    sum over 128 numbers a head."""
    assert eva_work.entry_bytes(MODEL) == 2 * 32 * 128 * 2 == 16384
    flops, hbm = eva_work.tick_read_work(1000, MODEL)
    assert hbm == 1000 * 16384 and flops == 1000 * 2 * 2 * 128 * 32
    assert flops / hbm == 1.0                              # v5e: 240
    flops, hbm = eva_work.prefill_unit_work(1000, 8, 24, MODEL)
    assert flops == 1000 * 2 * 2 * 128 * 32
    assert hbm == 8 * (8 * 2 * 4096 * 2 + 24 * 16384)
    # ISSUE 36's row at 10.3k positions: 1024 exact + 580 pooled entries a
    # layer are 26 MB
    assert eva_work.tick_read_work(1604, MODEL)[1] == pytest.approx(
        26.3e6, rel=0.01)


def test_every_reader_agrees_with_its_benchmark_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in OWN:
        reader, entry = registry.load_layer_metric(REPO, name), entries[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
        assert entry["workloads"] == [REAL_CELL]
        assert entry["layer"] == "compressed-window attention layer"
    # appended to the list of the metric ISSUE 36 named, and to no other
    assert REAL_CELL in entries["serve_tpot_ms_p90"]["workloads"]
    assert REAL_CELL not in entries["serve_tokens_per_s"]["workloads"]
    assert [m["name"] for m in bench["per_layer"]][-4:] == OWN
    assert bench["workloads"][-1]["name"] == REAL_CELL
    assert bench["configs"][-1]["name"] == REAL_CONFIG
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers"]
    loaded = registry.load_cell(REPO, REAL_CELL)
    assert loaded.job == "serve_closed_eva" and loaded.chips == 1
    assert loaded.end_to_end == ["serve_tpot_ms_p90", "setup_s"]
    assert loaded.per_layer == OWN
    # accepted readers stay with the cells they have: notes here
    assert loaded.params["notes_from"] == NOTED
    for name in NOTED:
        assert REAL_CELL not in entries[name]["workloads"], name
        registry.load_layer_metric(REPO, name)


def test_the_cells_file_fits_the_mix_and_the_engine():
    loaded = registry.load_cell(REPO, REAL_CELL)
    mix, engine = loaded.mix, loaded.params["engine"]
    assert mix["clients"] == 16 == engine["max_slots"] and mix["block"] == 20
    # the mix ISSUE 36 fixed before any code, as given
    assert mix["prompt_classes"] == [[4096, 0.20], [8192, 0.30],
                                     [16384, 0.30], [24576, 0.20]]
    assert mix["output_classes"] == [[256, 0.30], [512, 0.40], [1024, 0.30]]
    assert mix["temperature"] == 0.0 and mix["ramp_completions"] == 16
    assert sum(n * s for n, s in mix["output_classes"]) == pytest.approx(588.8)
    assert loaded.params["trace_seconds"] == 4.0
    assert loaded.params["check_requests"] == 3
    for _, share in mix["prompt_classes"] + mix["output_classes"]:
        assert abs(share * mix["block"] - round(share * mix["block"])) < 1e-9
    assert engine == {
        "kv_cache": "paged", "page_size": 64, "max_slots": 16,
        "max_len": 25600, "prompt_buckets": [4096, 8192, 16384, 24576],
        "num_pages": 912, "max_queue": 32, "kv_quant": "fp",
        "prefix_cache": False, "prefill_chunk_tokens": 2048}
    assert [c for c, _ in mix["prompt_classes"]] == engine["prompt_buckets"]
    # the longest prompt and the longest answer fit a row; nothing is
    # refused: every slot's worst case fits the pool
    assert engine["max_len"] == 24576 + 1024
    from llama_pipeline_parallel_tpu.models.eva import decode
    from llama_pipeline_parallel_tpu.models.eva.config import EvaConfig

    cfg = EvaConfig.from_published(loaded.config)
    assert decode.table_width(cfg, 25600, 64) == 57 == 912 // 16
    assert len(decode.table_columns(cfg, 25600, 25600, 64)) == 56
    assert set(loaded.params["checks"]) == {"served_logit_gap_mean"}
    for name, why in loaded.params["checks_why"].items():
        assert why and "TO BE SET" not in why, name
    assert set(loaded.params["checks"]) <= set(loaded.params["checks_why"])
    for control in ("float8", "summaries"):
        assert control in loaded.params["checks_why"]["served_logit_gap_mean"]


def test_the_configuration_file_is_the_catalog_row_but_for_its_depth():
    with open(os.path.join(REPO, "benchmark", "configs",
                           REAL_CONFIG + ".json")) as f:
        cfg = json.load(f)
    catalog = {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
        "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
        "lazy_init": True, "max_position_embeddings": 32768,
        "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_chunks": None, "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["num_hidden_layers"] == 8        # 32 published: one stage of four
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["source"] == ("https://huggingface.co/EvaByte/EvaByte/blob/"
                             "main/config.json")
    assert cfg["seeded_init_std"] == 0.02 != cfg["init_std"]
    for key in ("stands_for", "assumed", "layout", "why"):
        assert cfg[key]
    for item in ("(a) pooling", "(b) windows", "(c) norm input", "init",
                 "heads"):
        assert cfg["assumed"][item], item
    assert "four" in cfg["layout"] and "pipeline" in cfg["layout"]
    counts = eva_weights.param_count(cfg)
    assert counts["layers"] == 8 * (202_375_168 + 2 * 32 * 128)
    assert counts["embed"] + counts["lm_head"] == 320 * 4096 * 9
    assert 3.26e9 < 2 * counts["total"] < 3.27e9      # bytes in bfloat16
