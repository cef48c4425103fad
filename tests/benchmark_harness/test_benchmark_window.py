"""The window / full softmax family in the benchmark: its job end to end at
a tiny size on the CPU, short whole-bucket prefills and chunked long ones in
one queue (sound: correct; a served token altered where it is emitted: not;
the float8 control: not, by the gap check alone), its nine per-layer readers
on synthetic observations, `window_work`'s counts against a hand count at the
cell's shapes, and the entries' agreement with their files. Pins test
membership, never position or equality of a list."""

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
    registry,
    scopes,
    window_moe_weights,
    window_work,
    xplane,
)
from benchmark.reference import window_moe_decoder

CELL = "serve-tiny.window"
REAL_CELL = "serve-mixed-64.mimo-v2-flash"
REAL_CONFIG = "mimo-v2-flash.ep16-d7"
READERS = ["window_attn_share.serve", "full_attn_share.serve",
           "kv_read_per_row.serve", "window_prefill_attn_roofline.serve",
           "full_chunk_attn_roofline.serve", "gqa_decode_attn_roofline.serve",
           "window_expert_share.serve", "window_decode_tick_ms.serve",
           "window_prefill_chunk_ms.serve"]
TINY_WINDOW = {
    "hidden_size": 32, "num_hidden_layers": 4, "vocab_size": 256,
    "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
    "num_attention_heads": 8, "head_dim": 24, "v_head_dim": 16,
    "swa_num_attention_heads": 8, "swa_head_dim": 24, "swa_v_head_dim": 16,
    "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
    "partial_rotary_factor": 0.334, "rope_theta": 5000000,
    "swa_rope_theta": 10000, "sliding_window": 8, "sliding_window_size": 8,
    "attention_chunk_size": 8, "attention_value_scale": 0.707,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "attention_bias": False, "hidden_act": "silu", "layernorm_epsilon": 1e-5,
    "intermediate_size": 64, "n_routed_experts": 4, "router_experts": 16,
    "expert_offset": 8, "num_experts_per_tok": 4, "n_group": 1,
    "topk_group": 1, "moe_intermediate_size": 24, "n_shared_experts": None,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "routed_scaling_factor": None, "init_std": 0.15,
}


def make_root(tmp: str) -> str:
    """`benchmark_tiny`'s root with a window configuration and cell added by
    files and entries alone, as a PR adds them. The engine prefills a bucket
    of 8 whole and a bucket of 16 in two chunks of two pages."""
    root = benchmark_tiny.make_root(tmp)
    bdir = os.path.join(root, "benchmark")
    benchmark_tiny._dump(os.path.join(bdir, "configs", "window.json"), {
        "name": "window", "source": "tests", "why": "tiny", **TINY_WINDOW,
        "compute_dtype": "float32", "weights_dtype": "float32",
        "reduced": {}, "assumed": {}, "layout": "cpu"})
    with open(os.path.join(bdir, "workloads", "serve-tiny.tiny.json")) as f:
        cell = json.load(f)
    cell.update(name=CELL, config="window", job="serve_closed_window",
                checks={"served_logit_gap_mean": 1e-4},
                notes_from=["decode_tick_ms.serve"])
    cell["engine"]["prefill_chunk_tokens"] = 8
    benchmark_tiny._dump(os.path.join(bdir, "workloads", CELL + ".json"), cell)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "window", "source": "tests",
                             "file": "benchmark/configs/window.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": CELL, "config": "window",
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
    return make_root(str(tmp_path_factory.mktemp("window")))


def _run(root, seed=11, trace=False, seconds=1.5):
    return harness.run_cell(root, CELL, seed=seed, seconds=seconds,
                            trace=trace, devices=jax.devices()[:1],
                            t_start=time.time())


def test_the_cell_is_correct_and_counts_its_experts_and_its_entries(
        root, capsys):
    res = _run(root, seed=2 ** 31 + 9)
    out = capsys.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 10
    # the two end-to-end metrics the cell reports
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert " tokens/s; gap between tokens over " in out      # the note
    assert "check routed_total_off_tokens_x_topk_x_layers: value=0.0" in out
    assert "check window_entries_read_off_host_count: value=0.0" in out
    assert "check full_entries_read_off_host_count: value=0.0" in out
    assert "check served_logit_gap_mean" in out
    assert "% of the router" in out and "ring entries" in out
    # both kinds of prefill unit ran in the one queue
    units = next(line for line in out.splitlines()
                 if line.startswith("serve: prefill units "))
    total, chunks = int(units.split()[3]), int(units.split("(")[1].split()[0])
    assert 0 < chunks < total


def test_the_cell_traced_prints_its_notes_and_reads_its_counters(root, capsys):
    res = _run(root, trace=True)
    out = capsys.readouterr().out
    assert res["correct"] is True
    # no TPU plane in a CPU trace: the six device readers find nothing to
    # read and the line leaves them out; the counters' and the spans' do
    assert set(res["metrics"]) == {
        "kv_read_per_row.serve", "window_decode_tick_ms.serve",
        "window_prefill_chunk_ms.serve"}
    assert all(res["metrics"][name]["value"] > 0 for name in res["metrics"])
    # a row of 10 to 24 positions: 2 full layers x 2 x 40 x 2 B an entry and
    # 2 window layers x 8 entries x 4 x 40 x 2 B
    assert 6e-3 < res["metrics"]["kv_read_per_row.serve"]["value"] < 14e-3
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
    """`SERVE_CLOSED_WINDOW_CONTROL=fp8`: the same run, the float8
    reference's first choices in the served tokens' place; not correct, and
    the mean gap is the one check that is not OK."""
    job = registry.load_job(REPO, "serve_closed_window")
    monkeypatch.setenv(job.CONTROL_ENV, "fp8")
    # a seed at which the float8 reference moves 5 of the sample's 20 first
    # choices (with so few served tokens one seed in four moves none)
    res = _run(root, seed=7, seconds=1.0)
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
    top = window_moe_weights.make_top(seed, TINY_WINDOW)
    layer_fn = window_moe_weights.layer_fn(seed, TINY_WINDOW, jnp.float32)
    prompt = np.random.default_rng(seed).integers(0, 256, 12).tolist()
    served = []
    for _ in range(20):
        ids = jnp.asarray([prompt + served], jnp.int32)
        served.append(int(jnp.argmax(window_moe_decoder.logits_fn(
            top, layer_fn, ids, TINY_WINDOW)[0, -1])))
    args = (top, layer_fn, [prompt], [served], TINY_WINDOW, 32)
    sound = window_moe_decoder.served_token_gaps(*args)[0]
    control = window_moe_decoder.served_token_gaps(*args, precision="fp8")[0]
    assert max(sound) == 0.0 and len(sound) == 20
    assert sum(control) / len(control) > 1e-4


def test_the_programs_weights_are_the_references_layers():
    """`make_program_weights` (one program, the served side) and
    `make_layer` (one layer at a time, the reference's side) draw the same
    values, in one layout; a window layer has its sinks, an expert layer a
    selection bias that is not zero."""
    tree = window_moe_weights.make_program_weights(5, TINY_WINDOW, jnp.bfloat16)
    for i in range(4):
        layer = window_moe_weights.make_layer(5, i, TINY_WINDOW, jnp.bfloat16)
        assert set(layer) == set(tree["layers"][i])
        assert ("sink" in layer) == bool(TINY_WINDOW["hybrid_layer_pattern"][i])
        assert ("mlp" in layer) != ("router" in layer)
        for a, b in zip(jax.tree.leaves(layer),
                        jax.tree.leaves(tree["layers"][i])):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    assert tree["layers"][1]["sink"].dtype == jnp.float32
    assert np.abs(np.asarray(tree["layers"][1]["router_bias"])).max() > 0
    counts = window_moe_weights.param_count(TINY_WINDOW)
    assert counts["total"] == sum(x.size for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11, 48])
def test_the_selection_bias_moves_some_choices_and_leaves_the_load_even(
        seed, real_model):
    """At the cell's router (4096 -> 256 at normal(0, 0.02), top-8 of the
    sigmoid scores + bias) the seeded bias moves a choice now and then and
    leaves a chip's sixteenth of the router near a sixteenth, whatever the
    seed: a sigmoid packs the largest scores together, and at ten times this
    bias two choices in five moved and a sixteenth's share followed the seed
    by a quarter, so the seed set the experts a tick read (PERF.md, PR 48)."""
    rng = np.random.default_rng(seed)
    d, router, k = (real_model["hidden_size"], real_model["router_experts"],
                    real_model["num_experts_per_tok"])
    held = real_model["n_routed_experts"]
    h = rng.standard_normal((2048, d), np.float32)
    scores = 1.0 / (1.0 + np.exp(-h @ (window_moe_weights.INIT_STD
                                        * rng.standard_normal((d, router),
                                                              np.float32))))

    def chosen(bias_std):
        bias = bias_std * rng.standard_normal(router, np.float32)
        top = np.argpartition(-(scores + bias), k, axis=-1)[:, :k]
        mask = np.zeros(scores.shape, bool)
        np.put_along_axis(mask, top, True, -1)
        return mask

    plain = chosen(0.0)
    for bias_std, moved_in, scatter_in in (
            (window_moe_weights.BIAS_STD, (0.02, 0.10), (0.0, 0.08)),
            (10 * window_moe_weights.BIAS_STD, (0.30, 0.55), (0.10, 0.50))):
        mask = chosen(bias_std)
        moved = 1.0 - (mask & plain).sum() / plain.sum()
        shares = mask.reshape(-1, router // held, held).sum((0, 2)) / mask.sum()
        scatter = shares.std() * (router // held)
        assert moved_in[0] < moved < moved_in[1], (bias_std, moved)
        assert scatter_in[0] <= scatter < scatter_in[1], (bias_std, scatter)
    assert f"normal(0, {window_moe_weights.BIAS_STD})" in \
        real_model["assumed"]["h: init"]


def test_the_reference_imports_nothing_of_the_program():
    import inspect

    for module in (window_moe_decoder, window_moe_weights, window_work):
        source = inspect.getsource(module)
        assert "import llama_pipeline_parallel_tpu" not in source
        assert "from llama_pipeline_parallel_tpu" not in source
    assert 'default_matmul_precision("highest")' in inspect.getsource(
        window_moe_decoder)


# -- the counts, against a hand count at the cell's shapes ---------------------------

@pytest.fixture(scope="module")
def real_model():
    with open(os.path.join(REPO, "benchmark", "configs",
                           REAL_CONFIG + ".json")) as f:
        return json.load(f)


def test_the_sizes_are_the_cells(real_model):
    sz = window_work.sizes(real_model)
    assert sz == {"window_layers": 5, "full_layers": 2, "heads": 64,
                  "dk": 192, "dv": 128, "kv_full": 4, "kv_window": 8,
                  "window": 128}
    # what a layer keeps of a position, as published: 5,120 B over the two
    # full layers, 655,360 B a slot's ring
    assert window_work.entry_bytes(sz, window_work.FULL) == 2560
    assert window_work.entry_bytes(sz, window_work.WINDOW) == 5120
    assert 128 * window_work.entry_bytes(sz, window_work.WINDOW) == 655_360


def test_tick_read_work_is_a_hand_count(real_model):
    """64 rows at 6,000 positions: 2 full layers x 384,000 entries of 2,560
    B, 5 window layers x 64 x 128 entries of 5,120 B; 64 heads x (192 + 128)
    x 2 FLOPs an entry."""
    sz = window_work.sizes(real_model)
    in_window, in_full = 5 * 64 * 128, 2 * 64 * 6000
    flops, hbm = window_work.tick_read_work(in_window, in_full, sz)
    assert hbm == 40_960 * 5120 + 768_000 * 2560 == 2_175_795_200
    assert flops == (40_960 + 768_000) * 64 * 320 * 2


def test_prefill_unit_work_is_a_hand_count(real_model):
    """A 2048-token chunk at offset 30,720 of a row: a window layer's band
    holds 2048 x 128 pairs, a full layer's queries see 2048 x 30,720 +
    2048 x 2049 / 2 pairs."""
    sz = window_work.sizes(real_model)
    band = 5 * 2048 * 128
    flops, hbm = window_work.prefill_unit_work(
        band, 2048, 2048 + 127, 5, window_work.WINDOW, sz)
    assert flops == band * 64 * 320 * 2
    assert hbm == 5 * (2048 * 64 * 320 * 2 + 2175 * 5120)
    causal = 2 * (2048 * 30_720 + 2048 * 2049 // 2)
    flops, hbm = window_work.prefill_unit_work(
        causal, 2048, 32_768, 2, window_work.FULL, sz)
    assert flops == causal * 64 * 320 * 2
    assert hbm == 2 * (2048 * 64 * 320 * 2 + 32_768 * 2560)


def test_the_hosts_count_of_entries_is_a_sum_over_ticks():
    sz = {"window": 8, "window_layers": 5, "full_layers": 2}
    rng = np.random.default_rng(0)
    records = [{"request": {"prompt": [0] * int(rng.integers(1, 30))},
                "tokens": [0] * int(rng.integers(0, 25))} for _ in range(40)]
    in_window = sum(min(b + 1, 8) for b in (8, 16))
    in_full = sum(b + 1 for b in (8, 16))
    for r in records:
        n = len(r["request"]["prompt"])
        for j in range(1, len(r["tokens"])):
            in_window += min(n + j, 8)
            in_full += n + j
    assert window_work.host_entries(records, (8, 16), sz) == (
        5 * in_window, 2 * in_full)


# -- the readers on synthetic observations ------------------------------------------

TICK = "jit(paged_decode_step)/"
CHUNK = "jit(paged_prefill_chunk)/"


def _op(name, path, start, dur):
    return (sx.instruction(name), path, start, dur)


@pytest.fixture
def runs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path))
    return tmp_path


def _observe(runs, planes, spans, model, name="serve-cell.window"):
    cell = types.SimpleNamespace(name=name, model=model,
                                 params={"engine": {"page_size": 64}})
    run = runs / f"{cell.name}.42.1"
    run.mkdir()
    path = sx.write(run / "t.xplane.pb", planes)
    return {"kind": "serve", "cell": cell, "spans": list(spans),
            "xplane": xplane.read(path),
            "devices": [types.SimpleNamespace(device_kind="TPU v5 lite")]}


@pytest.fixture
def window_obs(runs, real_model):
    # one tick and one chunk in [0, 1000) ns, busy 900 (idle [500, 600)):
    # the tick: 5 ring reads of 10 and 2 page reads of 100 (the one kernel
    #   under the kind's scope), ring_write 20, moe_experts 130, lm_head 100
    # the chunk: 5 banded kernels of 20, 2 causal kernels of 80, kv_gather
    #   40, mlp 100
    ops, at = [], 0

    def add(name, path, dur):
        nonlocal at
        if at == 500:
            at = 600
        ops.append(_op(name, path, at, dur))
        at += dur

    for i in range(5):
        add(f"paged_decode_attn.{i}", TICK + "window_decode_attn/pallas_call", 10)
    for i in range(2):
        add(f"paged_decode_attn.{5 + i}", TICK + "full_decode_attn/pallas_call",
            100)
    add("fusion.1", TICK + "ring_write/scatter", 20)
    add("grouped_matmul.2", TICK + "moe_experts/pallas_call", 130)
    add("fusion.3", TICK + "lm_head/dot_general", 100)
    assert at == 500
    for i in range(5):
        add(f"window_prefill_attn.{i}", CHUNK + "window_prefill_attn/pallas_call",
            20)
    for i in range(2):
        add(f"full_chunk_attn.{i}",
            CHUNK + "branch_1_fun/full_prefill_attn/pallas_call", 80)
    add("fusion.4", CHUNK + "kv_gather/gather", 40)
    add("fusion.5", CHUNK + "mlp/dot_general", 100)
    assert at == 1000
    host = {"python": [("serve_tick_wait", None, 0, 500),
                       ("serve_prefill_enqueue", None, 590, 5)]}
    spans = [
        {"name": "serve_decode_step", "ts": 1.0, "dur": 0.4, "ticks": 10,
         "tokens": 600, "routed_total": 28800, "routed_here": 1800,
         "experts_hit": 700, "expert_load_max": 60, "experts_held": 960,
         "expert_visits": 700, "window_entries_read": 384_000,
         "full_entries_read": 7_200_000},
        {"name": "serve_prefill", "ts": 1.5, "dur": 0.1, "bucket": 8192,
         "chunk": 2048, "offset": 2048, "window_entries_read": 5 * 2048 * 128,
         "full_entries_read": 2 * (2048 * 2048 + 2048 * 2049 // 2)},
        {"name": "serve_prefill", "ts": 2.5, "dur": 0.1, "bucket": 512,
         "chunk": 512, "offset": 0, "window_entries_read": 5 * 300 * 100,
         "full_entries_read": 2 * 300 * 301 // 2}]
    return _observe(runs, {"/device:TPU:0": {"XLA Ops": ops},
                           "/host:CPU": host}, spans, real_model)


def _roofline(flops, hbm, seconds):
    return 100.0 * max(flops / 197e12, hbm / 819e9) / seconds


def _expected(name, model):
    sz = window_work.sizes(model)
    if name == "window_attn_share.serve":
        return 100.0 * (5 * 10 + 5 * 20) / 900
    if name == "full_attn_share.serve":
        return 100.0 * (2 * 100 + 2 * 80) / 900
    if name == "kv_read_per_row.serve":
        return (384_000 * 5120 + 7_200_000 * 2560) / 600 / 1e6
    if name == "window_expert_share.serve":
        return 100.0 * 130 / 900
    if name == "window_decode_tick_ms.serve":
        return 1e3 * 0.4 / 10
    if name == "window_prefill_chunk_ms.serve":
        return 1e3 * 0.1                        # the one chunk of the two units
    if name == "gqa_decode_attn_roofline.serve":
        # a tick's mean over the spans' ten; 250 ns in the one traced tick
        return _roofline(*window_work.tick_read_work(38_400, 720_000, sz),
                         250e-9)
    if name == "window_prefill_attn_roofline.serve":
        # the mean unit of the two spans; 100 ns in the one traced unit
        pairs = (5 * 2048 * 128 + 5 * 300 * 100) / 2
        return _roofline(*window_work.prefill_unit_work(
            pairs, 1280, 1280 + 127, 5, window_work.WINDOW, sz), 100e-9)
    pairs = (2 * (2048 * 2048 + 2048 * 2049 // 2) + 2 * 300 * 301 // 2) / 2
    return _roofline(*window_work.prefill_unit_work(
        pairs, 1280, (4096 + 512) / 2, 2, window_work.FULL, sz), 160e-9)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_synthetic_observation(window_obs, real_model, name):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read(window_obs) == pytest.approx(
        _expected(name, real_model))


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_its_input(name, window_obs, runs, real_model):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read({"kind": "none"}) is None
    assert reader.read(dict(window_obs, kind="train")) is None
    # what a program without the family gives: spans without the counters,
    # a trace whose paths hold the dense vocabulary alone
    bare = dict(window_obs, xplane=None, spans=[
        {k: v for k, v in s.items() if k in ("name", "ts", "dur", "ticks",
                                             "tokens")}
        for s in window_obs["spans"]])
    assert reader.read(bare) is None
    dense = _observe(runs, {
        "/device:TPU:0": {"XLA Ops": [
            _op("paged_decode_attn.1", TICK + "decode_attn/pallas_call", 0, 30),
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
    assert loaded.job == "serve_closed_window" and loaded.chips == 1
    assert set(READERS) <= set(loaded.per_layer)
    # tokens/s is the cell's metric, as ISSUE 48 fixed it before any code;
    # the gap between tokens is printed as a note
    assert {"serve_tokens_per_s", "setup_s"} <= set(loaded.end_to_end)
    assert "serve_tpot_ms_p90" not in loaded.end_to_end
    assert loaded.config_name == REAL_CONFIG
    assert loaded.traffic_name == "serve-mixed-64"
    for name in loaded.params["notes_from"]:
        assert name in entries and REAL_CELL not in entries[name]["workloads"]
    # every limit carries its reason
    assert set(loaded.params["checks"]) <= set(loaded.params["checks_why"])
    # the configuration is the only one of its family, the cell its only cell
    assert [c["name"] for c in bench["configs"]].count(REAL_CONFIG) == 1
    assert [w["config"] for w in bench["workloads"]].count(REAL_CONFIG) == 1


def test_the_mix_and_the_engine_are_the_issues(real_model):
    from benchmark import traffic

    mix = traffic.load_mix(REPO, "serve-mixed-64")
    assert mix["clients"] == 64 and mix["block"] == 20
    assert mix["ramp_completions"] == 64 and mix["temperature"] == 0.0
    block = traffic.request_block(mix, 3_000_000_019, 0,
                                  real_model["vocab_size"])
    count = lambda key: {v: sum(1 for r in block if r[key] == v)
                         for v in {r[key] for r in block}}
    assert count("prompt_class") == {512: 6, 2048: 5, 8192: 4, 16384: 3,
                                     32768: 2}
    assert count("max_new_tokens") == {128: 4, 384: 8, 768: 5, 1536: 3}
    assert all(0 <= t < 19072 for r in block for t in r["prompt"])
    assert max(len(r["prompt"]) for r in block) <= 32768
    engine = registry.load_cell(REPO, REAL_CELL).params["engine"]
    assert engine == {
        "kv_cache": "paged", "page_size": 64, "max_slots": 64,
        "max_len": 34304, "prompt_buckets": [512, 2048, 8192, 16384, 32768],
        "num_pages": 14336, "max_queue": 64, "kv_quant": "fp",
        "prefix_cache": False, "prefill_chunk_tokens": 2048}
    # the longest request fits a slot, whose row of the table is 536 wide
    assert engine["max_len"] == 32768 + 1536 == 536 * 64


@pytest.mark.parametrize("scheduled", [True, False])
def test_every_seed_serves_one_schedule_where_the_mix_states_one(
        real_model, scheduled):
    """The work of a run is not the seed's to decide: with `schedule_seed`
    in the mix, two seeds serve the generator's own draw at that seed (class,
    prompt length and answer length of every request) and differ in the
    token ids and the sampling seeds alone; without it the job's stream is
    the generator's at the run's seed."""
    import itertools

    from benchmark import traffic

    job = registry.load_job(REPO, "serve_closed_window")
    mix = dict(traffic.load_mix(REPO, "serve-mixed-64"))
    assert isinstance(mix["schedule_seed"], int)
    if not scheduled:
        del mix["schedule_seed"]
    vocab, n = real_model["vocab_size"], 60
    take = lambda stream: list(itertools.islice(stream, n))
    shape = lambda reqs: [(r["prompt_class"], len(r["prompt"]),
                           r["max_new_tokens"]) for r in reqs]
    a, b = (take(job.scheduled_stream(mix, seed, vocab))
            for seed in (2 ** 31 + 7, 5))
    again = take(job.scheduled_stream(mix, 2 ** 31 + 7, vocab))
    assert a == again                       # the same seed, the same inputs
    assert all(0 <= t < vocab for r in a for t in r["prompt"])
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert [r["seed"] for r in a] != [r["seed"] for r in b]
    if scheduled:
        drawn = take(traffic.request_stream(mix, mix["schedule_seed"], vocab))
        assert shape(a) == shape(b) == shape(drawn)
        assert set(a[0]) == set(drawn[0])
    else:
        assert shape(a) != shape(b)
        assert a == take(traffic.request_stream(mix, 2 ** 31 + 7, vocab))
    # what `_drive` draws from is this stream
    assert job._hybrid.traffic.request_stream is job.scheduled_stream


def test_the_configuration_file_states_its_cut_and_keeps_every_width(
        real_model):
    cfg = real_model
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["source_url"] == cfg["source"])
    assert published["name"] == "MiMo-V2-Flash"
    lists = {"hybrid_layer_pattern", "moe_layer_freq"}
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"} | lists
    assert set(cfg["reduced"]) == reduced
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == REAL_CONFIG)
    assert set(entry["reduced"]) == reduced and entry["source"] == cfg["source"]
    for key, value in published["config"].items():
        if key in reduced:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    # every published width, unchanged
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["v_head_dim"], cfg["num_key_value_heads"],
            cfg["swa_num_key_value_heads"], cfg["sliding_window"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["router_experts"], cfg["intermediate_size"]) == (
        4096, 64, 192, 128, 4, 8, 128, 2048, 8, 256, 16384)
    # layer 0 and one whole period of the published pattern, by its indices
    kept = cfg["kept_layers"]
    assert kept == [0] + list(range(6, 12))
    for key in lists:
        assert cfg[key] == [published["config"][key][i] for i in kept]
    assert cfg["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert cfg["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    # the floors of a model_config cut: a whole period and four layers behind
    # the dense one, >= 8 experts a layer, >= an eighth of the vocabulary
    assert cfg["n_routed_experts"] == 16 and cfg["expert_offset"] == 0
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_shared_experts"] is None
    assert cfg["routed_scaling_factor"] is None
    assert [key[0] for key in cfg["assumed"] if key[1:2] == ":"] == list(
        "abcdefghi")
    assert "SIXTEEN" in cfg["layout"] and "256 WIDE" in cfg["layout"]
    dm = window_moe_decoder.dims(cfg)
    assert (dm["d"], dm["heads"], dm["dk"], dm["dv"], dm["rot"], dm["kv_full"],
            dm["kv_window"], dm["window"], dm["f"], dm["ffn"], dm["router"],
            dm["held"], dm["topk"], dm["scale"]) == (
        4096, 64, 192, 128, 64, 4, 8, 128, 2048, 16384, 256, 16, 8, 1.0)
    counts = window_moe_weights.param_count(cfg)
    assert counts["full_attention"] == 89_133_056
    assert counts["window_attention"] == 94_376_000
    assert counts["expert"] == 25_165_824
    assert 6.85e9 < 2 * counts["total"] < 6.87e9       # bfloat16
    # and uncut it is the model as published: 309B-A15B
    whole = window_moe_weights.param_count({**cfg, **cfg["published"]})
    assert 308.7e9 < whole["total"] < 308.9e9
    active = whole["total"] - 47 * (256 - 8) * counts["expert"]
    assert 15.3e9 < active < 15.5e9
    for key in ("stands_for", "assumed", "layout", "why"):
        assert cfg[key]


def test_the_program_reads_the_file_as_the_reference_does(real_model):
    """`WindowMoEConfig.from_published` and the reference's `dims` take the
    same numbers from the cell's file."""
    job = registry.load_job(REPO, "serve_closed_window")
    cfg = job.model_config(registry.load_cell(REPO, REAL_CELL))
    dm = window_moe_decoder.dims(real_model)
    assert cfg.family == "window_moe" and cfg.dtype == jnp.bfloat16
    assert (cfg.pattern, cfg.moe_layers) == (dm["pattern"], dm["moe"])
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim,
            cfg.v_head_dim, cfg.rotary_dim, cfg.full_kv_heads,
            cfg.window_kv_heads, cfg.sliding_window, cfg.value_scale) == (
        dm["d"], dm["heads"], dm["dk"], dm["dv"], dm["rot"], dm["kv_full"],
        dm["kv_window"], dm["window"], dm["v_scale"])
    assert (cfg.full_rope_theta, cfg.window_rope_theta) == (5e6, 1e4)
    assert (cfg.router_experts, cfg.held, cfg.expert_offset,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor) == (
        256, 16, 0, 8, 1.0)
    assert job.page_pool_bytes(registry.load_cell(REPO, REAL_CELL)) == (
        2 * 14337 * 64 * 4 * (256 + 128) * 2)
