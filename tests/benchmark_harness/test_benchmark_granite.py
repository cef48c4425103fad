"""The dense state-space block in the benchmark: its job end to end at a tiny
size on the CPU, short whole-bucket prefills and chunks that carry a slot's
state in one queue (sound: correct; a served token altered where it is
emitted: not; the float8 control: not, by the gap check alone), its six
per-layer readers on synthetic observations, `granite_work`'s counts against
a hand count, and the entries' agreement with their files. Pins test
membership, never position or equality of a list."""

import json
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import pytest

import benchmark_tiny
import synthetic_xplane as sx
from conftest import REPO

from benchmark import (
    granite_hybrid_weights,
    granite_work,
    harness,
    registry,
    scopes,
    ssm_work,
    traffic,
    xplane,
)
from benchmark.reference import granite_hybrid_decoder

sys.path.insert(0, os.path.join(REPO, "tests"))
import granite_tiny  # noqa: E402

CELL = "serve-tiny.granite"
REAL_CELL = "serve-rag-48.granite4-h-micro"
REAL_CONFIG = "granite-4.0-h-micro.d40"
READERS = ["mamba_share.serve", "mamba_step_roofline.serve",
           "mamba_scan_roofline.serve", "state_carry_share.serve",
           "mamba_chunk_ms.serve", "mamba_tick_ms.serve"]
TINY = granite_tiny.MODEL
TICK = "jit(paged_decode_step)/"
CHUNK = "jit(paged_prefill_chunk)/"


def make_root(tmp: str) -> str:
    """`benchmark_tiny`'s root with the tiny dense block and a cell added by
    files and entries alone, as a PR adds them. The engine prefills a bucket
    of 8 whole and a bucket of 16 in two chunks of two pages."""
    root = benchmark_tiny.make_root(tmp)
    bdir = os.path.join(root, "benchmark")
    benchmark_tiny._dump(os.path.join(bdir, "configs", "granite.json"), {
        "name": "granite", "source": "tests", "why": "tiny", **TINY,
        "compute_dtype": "float32", "weights_dtype": "float32",
        "reduced": {}, "assumed": {}, "layout": {"kv_pack": 2}})
    with open(os.path.join(bdir, "workloads", "serve-tiny.tiny.json")) as f:
        cell = json.load(f)
    # the notes are the real cell's: a reader that cannot read this
    # configuration's keys fails the traced run here, not on the chip
    with open(os.path.join(REPO, "benchmark", "workloads",
                           REAL_CELL + ".json")) as f:
        notes = json.load(f)["notes_from"]
    cell.update(name=CELL, config="granite", job="serve_closed_granite",
                checks={"served_logit_gap_mean": 1e-4}, notes_from=notes)
    cell["engine"]["prefill_chunk_tokens"] = 8
    benchmark_tiny._dump(os.path.join(bdir, "workloads", CELL + ".json"), cell)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "granite", "source": "tests",
                             "file": "benchmark/configs/granite.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": CELL, "config": "granite",
                               "traffic": "serve-tiny", "chips": 1,
                               "why": "tiny"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tpot_ms_p90":
            m["workloads"].append(CELL)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READERS:
        bench["per_layer"].append(dict(real[name], workloads=[CELL]))
    benchmark_tiny._dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("granite")))


def _run(root, seed=11, trace=False, seconds=1.5):
    return harness.run_cell(root, CELL, seed=seed, seconds=seconds,
                            trace=trace, devices=jax.devices()[:1],
                            t_start=time.time())


def test_the_cell_is_correct_and_its_counters_meet_the_hosts_counts(
        root, capsys):
    res = _run(root, seed=2 ** 31 + 9)
    out = capsys.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 10
    # the two end-to-end metrics the cell reports; tokens/s is a note
    assert set(res["metrics"]) == {"serve_tpot_ms_p90", "setup_s"}
    assert " tokens/s; gap between tokens over " in out
    for check in ("ssm_rows_off_tokens_x_layers",
                  "kv_entries_read_off_host_count",
                  "ssm_positions_off_host_count",
                  "state_carries_off_host_count",
                  "finished_prompts_not_scanned_whole"):
        assert f"check {check}: value=0.0" in out
    assert "check served_logit_gap_mean" in out
    # both kinds of prefill unit ran in the one queue, and chunks carried
    units = next(line for line in out.splitlines()
                 if line.startswith("serve: prefill units "))
    total, chunks = int(units.split()[3]), int(units.split("(")[1].split()[0])
    assert 0 < chunks < total
    carried = next(line for line in out.splitlines()
                   if line.startswith("serve: state-space: "))
    assert int(carried.split(" of them carried")[0].split()[-1]) > 0


def test_the_cell_traced_prints_its_notes_and_reads_its_spans(root, capsys):
    res = _run(root, trace=True)
    out = capsys.readouterr().out
    assert res["correct"] is True
    # no TPU plane in a CPU trace: the four device readers find nothing to
    # read and the line leaves them out; the two span readers do
    assert set(res["metrics"]) == {"mamba_chunk_ms.serve",
                                   "mamba_tick_ms.serve"}
    assert all(res["metrics"][name]["value"] > 0 for name in res["metrics"])
    for name in registry.load_cell(REPO, REAL_CELL).params["notes_from"]:
        assert f"serve: note {name} = " in out
    assert "mamba_chunk_ms.serve: " in out and "carried their slot" in out


def test_a_served_token_altered_where_it_is_emitted_is_not_correct(
        root, monkeypatch):
    from llama_pipeline_parallel_tpu.serve.engine import RequestHandle

    real = RequestHandle._push

    def altered(self, token):
        real(self, (token + 1) % 128 if len(self.tokens_out) % 3 == 2
             else token)

    monkeypatch.setattr(RequestHandle, "_push", altered)
    assert _run(root, seed=8, seconds=1.0)["correct"] is False


@pytest.mark.parametrize("control", ["fp8", "nocarry"])
def test_a_committed_control_fails_by_the_gap_check_alone(
        root, monkeypatch, capsys, control):
    """`SERVE_CLOSED_GRANITE_CONTROL=fp8`: the same run, the float8
    reference's first choices in the served tokens' place. `=nocarry`: every
    chunk finds its slot's row of the recurrent store zeroed, the counters
    and the mask as they were. Not correct, and the mean gap is the one
    check that is not OK."""
    job = registry.load_job(REPO, "serve_closed_granite")
    monkeypatch.setenv(job.CONTROL_ENV, control)
    res = _run(root, seed=7, seconds=1.0)
    out = capsys.readouterr().out
    assert res["correct"] is False and f"{job.CONTROL_ENV}={control}" in out
    not_ok = [line.split(":")[0] for line in out.splitlines()
              if line.startswith("check ") and line.endswith("NOT OK")]
    assert not_ok == ["check served_logit_gap_mean"]


def test_a_control_of_another_name_is_refused(root, monkeypatch):
    job = registry.load_job(REPO, "serve_closed_granite")
    monkeypatch.setenv(job.CONTROL_ENV, "bf16")
    with pytest.raises(ValueError, match="fp8 or nocarry"):
        _run(root, seed=7, seconds=1.0)


def test_the_programs_weights_are_the_references_layers():
    """One draw, two layouts: a published layer's two halves are two
    entries of the program's list, and the head is the table."""
    program = granite_hybrid_weights.make_program_weights(5, TINY, jnp.float32)
    top = granite_hybrid_weights.make_top(5, TINY, jnp.float32)
    layer_fn = granite_hybrid_weights.layer_fn(5, TINY, jnp.float32)
    assert "lm_head" not in program and len(program["layers"]) == 10
    assert (program["embed"]["embedding"] == top["embed"]).all()
    for i in range(5):
        layer = layer_fn(i)
        mixer, dense = program["layers"][2 * i], program["layers"][2 * i + 1]
        assert sorted(dense) == ["mlp", "post_norm"]
        assert set(layer) == set(mixer) | set(dense)
        assert not set(mixer) & set(dense)
        for name, leaf in {**mixer, "post_norm": dense["post_norm"]}.items():
            assert (leaf == layer[name]).all(), name
        for name, leaf in dense["mlp"].items():
            assert (leaf == layer["mlp"][name]).all(), name
    again = granite_hybrid_weights.make_layer(5, 2, TINY, jnp.float32)
    other = granite_hybrid_weights.make_layer(6, 2, TINY, jnp.float32)
    assert (again["wq"] == layer_fn(2)["wq"]).all()
    assert not (again["wq"] == other["wq"]).all()
    with pytest.raises(ValueError, match="seed"):
        granite_hybrid_weights.make_top(2 ** 32, TINY)


def test_the_reference_imports_nothing_of_the_program():
    import inspect

    for module in (granite_hybrid_decoder, granite_hybrid_weights,
                   granite_work):
        source = inspect.getsource(module)
        assert "import llama_pipeline_parallel_tpu" not in source
        assert "from llama_pipeline_parallel_tpu" not in source
    assert 'default_matmul_precision("highest")' in inspect.getsource(
        granite_hybrid_decoder)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_reads_a_gap_the_reference_does_not(seed):
    import numpy as np

    top = granite_hybrid_weights.make_top(seed, TINY, jnp.float32)
    layer_fn = granite_hybrid_weights.layer_fn(seed, TINY, jnp.float32)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, 128, 12).tolist()
    ids = jnp.asarray([prompt + [0] * 20], jnp.int32)
    logits = granite_hybrid_decoder.logits_fn(top, layer_fn, ids, TINY)
    served = np.asarray(jnp.argmax(logits[0, 11:31], axis=-1)).tolist()
    sound = granite_hybrid_decoder.served_token_gaps(
        top, layer_fn, [prompt], [served[:1]], TINY, 8)
    assert sound == [[0.0]]
    # greedy tokens served one after another: the reference's own choices
    # (one compiled shape: the row so far, padded at the end to 48)
    seq, out = list(prompt), []
    for _ in range(36):
        row = granite_hybrid_decoder.logits_fn(
            top, layer_fn,
            jnp.asarray([seq + [0] * (48 - len(seq))], jnp.int32), TINY)
        out.append(int(jnp.argmax(row[0, len(seq) - 1])))
        seq.append(out[-1])
    own = granite_hybrid_decoder.served_token_gaps(
        top, layer_fn, [prompt], [out], TINY, 8)
    assert max(own[0]) < 1e-5
    # the float8 reference puts another token first at 4 to 6 of the 36
    # places (mean gap 0.006 to 0.009 over these seeds)
    control = granite_hybrid_decoder.served_token_gaps(
        top, layer_fn, [prompt], [out], TINY, 8, "fp8")
    assert sum(control[0]) / len(control[0]) > 1e-3


# -- the counts, against a hand count ---------------------------------------------

@pytest.fixture(scope="module")
def real_model():
    with open(os.path.join(REPO, "benchmark", "configs",
                           REAL_CONFIG + ".json")) as f:
        return json.load(f)


def test_the_sizes_are_the_cells(real_model):
    sz = granite_work.sizes(real_model)
    assert sz == {"ssm_layers": 36, "softmax_layers": 4, "heads": 64,
                  "head_dim": 64, "state": 128, "groups": 1, "conv": 4,
                  "chunk": 256, "conv_width": 4352, "kv_heads": 8,
                  "kv_head_dim": 64}
    # a slot's row: 75.5 MB of float32 state and 0.94 MB of inputs
    assert 36 * 64 * 64 * 128 * 4 == 75_497_472
    assert granite_work.slot_row_bytes(sz) == 75_497_472 + 36 * 3 * 4352 * 2
    # the expert block's work functions take these sizes as they are
    flops, hbm = ssm_work.step_work(48, sz)
    assert flops == 5 * 48 * 36 * 64 * 64 * 128
    assert hbm == 2 * (4 * 48 * 36 * 64 * 64 * 128 + 2 * 48 * 36 * 3 * 4352)
    flops, hbm = ssm_work.scan_work(2048, sz)
    pairs = 256 * 257 / 2
    assert flops == 36 * 8 * (2 * pairs * 128 + 2 * pairs * 64 * 64
                              + 4 * 256 * 64 * 128 * 64)
    assert hbm == 36 * (2048 * 2 * (2 * 4096 + 2 * 128 + 64)
                        + 2 * 4 * 64 * 64 * 128)


def test_the_hosts_counts_are_sums_over_ticks_and_places(real_model):
    sz = granite_work.sizes(real_model)
    records = [
        {"request": {"prompt": [0] * 10}, "tokens": [1, 2, 3, 4]},  # 3 ticks
        {"request": {"prompt": [0] * 7}, "tokens": [5]},            # none
        {"request": {"prompt": [0] * 3}, "tokens": []}]             # cut
    got = granite_work.host_tick_counts(records, [512, 2048], sz)
    rows = 3 + 2
    contexts = (11 + 12 + 13) + 513 + 2049
    assert got == {"rows": rows, "ssm_rows": rows * 36,
                   "kv_entries_read": contexts * 4}
    units = [
        # whole bucket of 512 behind 212 pads
        {"bucket": 512, "prompt": 300, "offset": 0, "chunk": 512,
         "chunks_skipped": 0},
        # a bucket of 8192, 5000 tokens: pad 3192, one chunk skipped; the
        # first run chunk holds 4096 - 3192 places and carries nothing
        {"bucket": 8192, "prompt": 5000, "offset": 2048, "chunk": 2048,
         "chunks_skipped": 1},
        {"bucket": 8192, "prompt": 5000, "offset": 4096, "chunk": 2048},
        {"bucket": 8192, "prompt": 5000, "offset": 6144, "chunk": 2048}]
    got = granite_work.host_unit_counts(units, sz)
    assert got["ssm_positions"] == (300 + 5000) * 36
    assert got["state_carries"] == 2 * 36
    assert got["state_bytes_carried"] == 2 * granite_work.slot_row_bytes(sz)


def test_a_finished_prompt_is_held_to_the_units_of_one_request(real_model):
    """The units are the program's own report; the prompts are the clients'.
    A request whose units scanned its whole prompt answers ONE prompt of its
    length; a unit that never ran, or a chunk that scanned short, leaves its
    request answering none."""
    sz = granite_work.sizes(real_model)
    unit = lambda request, prompt, places: {
        "request": request, "prompt": prompt, "ssm_positions": places * 36}
    units = [unit("a", 300, 300), unit("b", 5000, 904), unit("b", 5000, 2048),
             unit("b", 5000, 2048), unit("c", 300, 300)]
    whole = granite_work.prompts_not_scanned_whole
    assert whole(units, [300, 5000, 300], sz) == 0
    assert whole(units, [300, 5000], sz) == 0          # c is still decoding
    assert whole(units, [300, 300, 300], sz) == 1      # two requests, three
    assert whole(units[:2] + units[3:], [300, 5000, 300], sz) == 1
    short = units[:2] + [unit("b", 5000, 2047)] + units[3:]
    assert whole(short, [300, 5000, 300], sz) == 1
    assert whole([], [512], sz) == 1


# -- the readers on a synthetic observation ------------------------------------------

def _op(name, path, start, dur):
    return (sx.instruction(name), path, start, dur)


@pytest.fixture
def runs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path))
    return tmp_path


def _observe(runs, planes, spans, model, name="serve-cell.granite"):
    cell = types.SimpleNamespace(name=name, model=model,
                                 params={"engine": {"page_size": 64}})
    run = runs / f"{cell.name}.42.1"
    run.mkdir()
    path = sx.write(run / "t.xplane.pb", planes)
    return {"kind": "serve", "cell": cell, "spans": list(spans),
            "xplane": xplane.read(path),
            "devices": [types.SimpleNamespace(device_kind="TPU v5 lite")]}


COUNTERS = dict(ssm_rows=0, ssm_positions=0, kv_entries_read=0,
                state_carries=0, state_bytes_carried=0)


@pytest.fixture
def granite_obs(runs, real_model):
    # one tick and one chunk in [0, 1000) ns, busy 900 (idle [500, 600)):
    # the tick: ssm_step 200 (the kernel), state_gather 20, state_write 30,
    #   ssm_proj 50, decode_mlp 100, decode_attn 40, lm_head 60
    # the chunk: state_carry_in 10, ssm_scan 150, ssm_proj 60,
    #   state_carry_out 20, mlp 100, attn_core 60
    ops, at = [], 0

    def add(name, path, dur):
        nonlocal at
        if at == 500:
            at = 600
        ops.append(_op(name, path, at, dur))
        at += dur

    add("ssm_state_step.1", TICK + "ssm_step/pallas_call", 200)
    add("fusion.1", TICK + "state_gather/gather", 20)
    add("fusion.2", TICK + "state_write/scatter", 30)
    add("fusion.3", TICK + "ssm_proj/dot_general", 50)
    add("fusion.4", TICK + "decode_mlp/dot_general", 100)
    add("paged_decode_attn.1", TICK + "decode_attn/pallas_call", 40)
    add("fusion.5", TICK + "lm_head/dot_general", 60)
    assert at == 500
    add("fusion.6", CHUNK + "state_carry_in/dynamic_slice", 10)
    add("fusion.7", CHUNK + "ssm_scan/dot_general", 150)
    add("fusion.8", CHUNK + "ssm_proj/dot_general", 60)
    add("fusion.9", CHUNK + "state_carry_out/dynamic_update_slice", 20)
    add("fusion.10", CHUNK + "mlp/dot_general", 100)
    add("full_chunk_attn.1", CHUNK + "attn_core/pallas_call", 60)
    assert at == 1000
    host = {"python": [("serve_tick_wait", None, 0, 500),
                       ("serve_prefill_enqueue", None, 590, 5)]}
    spans = [
        {"name": "serve_decode_step", "ts": 1.0, "dur": 0.4, "ticks": 10,
         "tokens": 400, **COUNTERS, "ssm_rows": 400 * 36,
         "kv_entries_read": 4 * 400 * 3000},
        {"name": "serve_prefill", "ts": 1.5, "dur": 0.1, "bucket": 8192,
         "prompt": 8000, "chunk": 2048, "offset": 2048, **COUNTERS,
         "ssm_positions": 36 * 2048, "state_carries": 36},
        {"name": "serve_prefill", "ts": 2.5, "dur": 0.3, "bucket": 512,
         "prompt": 300, "chunk": 512, "offset": 0, "chunks_skipped": 0,
         **COUNTERS, "ssm_positions": 36 * 300}]
    return _observe(runs, {"/device:TPU:0": {"XLA Ops": ops},
                           "/host:CPU": host}, spans, real_model)


def _roofline(flops, hbm, seconds):
    return 100.0 * max(flops / 197e12, hbm / 819e9) / seconds


def _expected(name, model):
    sz = granite_work.sizes(model)
    if name == "mamba_share.serve":
        return 100.0 * (200 + 20 + 30 + 50 + 10 + 150 + 60 + 20) / 900
    if name == "state_carry_share.serve":
        return 100.0 * (10 + 20) / 900
    if name == "mamba_chunk_ms.serve":
        return 1e3 * 0.1                    # the one chunk of the two units
    if name == "mamba_tick_ms.serve":
        return 1e3 * 0.4 / 10
    if name == "mamba_step_roofline.serve":
        # 40 rows a tick; 250 ns in the one traced tick under the three
        return _roofline(*ssm_work.step_work(40, sz), 250e-9)
    # the mean unit of the two spans, 1280 places; 150 ns under ssm_scan
    return _roofline(*ssm_work.scan_work(1280, sz), 150e-9)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_synthetic_observation(granite_obs, real_model, name):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read(granite_obs) == pytest.approx(
        _expected(name, real_model))


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_its_input(name, granite_obs, runs, real_model):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read({"kind": "none"}) is None
    assert reader.read(dict(granite_obs, kind="train")) is None
    # what a program without the counters gives (the parent of the PR that
    # added them): spans without them, whatever the trace holds
    bare = dict(granite_obs, spans=[
        {k: v for k, v in s.items() if k in ("name", "ts", "dur", "ticks",
                                             "tokens", "bucket", "chunk")}
        for s in granite_obs["spans"]])
    assert reader.read(bare) is None
    assert reader.read(dict(bare, xplane=None)) is None


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
        assert entry["moves"] == "serve_tpot_ms_p90"
    loaded = registry.load_cell(REPO, REAL_CELL)
    assert loaded.job == "serve_closed_granite" and loaded.chips == 1
    assert set(READERS) <= set(loaded.per_layer)
    # the gap between tokens is the cell's metric, as ISSUE 53 fixed it
    # before any code; tokens a second are printed as a note
    assert {"serve_tpot_ms_p90", "setup_s"} <= set(loaded.end_to_end)
    assert "serve_tokens_per_s" not in loaded.end_to_end
    assert loaded.config_name == REAL_CONFIG
    assert loaded.traffic_name == "serve-rag-48"
    for name in loaded.params["notes_from"]:
        assert name in entries and REAL_CELL not in entries[name]["workloads"]
    # every limit carries its reason
    assert set(loaded.params["checks"]) <= set(loaded.params["checks_why"])
    # the configuration is the only one of its shape, the cell its only cell
    assert [c["name"] for c in bench["configs"]].count(REAL_CONFIG) == 1
    assert [w["config"] for w in bench["workloads"]].count(REAL_CONFIG) == 1


def test_the_mix_and_the_engine_are_the_issues(real_model):
    mix = traffic.load_mix(REPO, "serve-rag-48")
    assert mix["clients"] == 48 and mix["block"] == 20
    assert mix["ramp_completions"] == 48 and mix["temperature"] == 0.0
    assert isinstance(mix["schedule_seed"], int)
    assert str(mix["schedule_seed"]) in mix["why"]
    block = traffic.request_block(mix, 3_000_000_019, 0,
                                  real_model["vocab_size"])
    count = lambda key: {v: sum(1 for r in block if r[key] == v)
                         for v in {r[key] for r in block}}
    # whole-number quotas of a block of 20
    assert count("prompt_class") == {512: 6, 2048: 7, 8192: 5, 16384: 2}
    assert count("max_new_tokens") == {160: 5, 384: 7, 768: 5, 1536: 3}
    assert all(0 <= t < 100352 for r in block for t in r["prompt"])
    assert max(len(r["prompt"]) for r in block) <= 16384
    engine = registry.load_cell(REPO, REAL_CELL).params["engine"]
    assert engine == {
        "kv_cache": "paged", "page_size": 64, "max_slots": 48,
        "max_len": 17920, "prompt_buckets": [512, 2048, 8192, 16384],
        "num_pages": 7168, "max_queue": 48, "kv_quant": "fp",
        "prefix_cache": False, "prefill_chunk_tokens": 2048}
    # the longest request fits a slot, whose row of the table is 280 wide
    assert engine["max_len"] == 16384 + 1536 == 280 * 64
    # every run serves the one schedule (the window job's stream)
    job = registry.load_job(REPO, "serve_closed_granite")
    assert job._hybrid.traffic.request_stream.__name__ == "scheduled_stream"
    import itertools

    shape = lambda seed: [
        (len(r["prompt"]), r["max_new_tokens"]) for r in itertools.islice(
            job._hybrid.traffic.request_stream(mix, seed, 100352), 30)]
    assert shape(5) == shape(2 ** 31 + 7)


def test_the_configuration_file_cuts_nothing_and_keeps_every_key(real_model):
    cfg = real_model
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["source_url"] == cfg["source"])
    assert published["name"] == "granite-4.0-h-micro"
    assert cfg["reduced"] == {} and cfg["reduced_why"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == REAL_CONFIG)
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    for key, value in published["config"].items():
        assert cfg[key] == value, key
    assert (cfg["compute_dtype"], cfg["weights_dtype"], cfg["state_dtype"]) == (
        "bfloat16", "bfloat16", "float32")
    assert [key[0] for key in cfg["assumed"] if key[1:2] == ":"] == list(
        "abcdefgh")
    assert cfg["layout"]["kv_pack"] == 2
    for key in ("stands_for", "assumed", "layout", "why", "published"):
        assert cfg[key]
    counts = granite_hybrid_weights.param_count(cfg)
    assert counts["mamba_layer"] == 76_182_976
    assert counts["softmax_layer"] == 60_821_504
    assert counts["table_and_norm"] == 205_520_896 + 2048
    assert counts["total"] == 3_191_396_096
    assert 6.38e9 < 2 * counts["total"] < 6.39e9       # bfloat16


def test_the_program_reads_the_file_as_the_reference_does(real_model):
    """`SsmMoEConfig.from_published` and the reference's `dims` take the
    same numbers from the cell's file."""
    job = registry.load_job(REPO, "serve_closed_granite")
    cfg = job.model_config(registry.load_cell(REPO, REAL_CELL))
    dm = granite_hybrid_decoder.dims(real_model)
    assert cfg.family == "ssm_moe" and cfg.dtype == jnp.bfloat16
    assert cfg.pattern == "".join(
        "M-" if t == "mamba" else "*-" for t in dm["types"])
    assert cfg.num_hidden_layers == 40 and cfg.recurrent_layers == 36
    assert cfg.kv_cache_layers == 4 and cfg.expert_layers == 0
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_groups, cfg.ssm_conv, cfg.dense_intermediate_size) == (
        dm["d"], dm["heads"], dm["kv"], dm["hd"], dm["H"], dm["P"], dm["N"],
        dm["G"], dm["conv"], dm["f"])
    # the file's `layout` writes down what the program works out from the
    # head's width: it is handed no such key
    assert cfg.ssm_chunk == 256
    assert cfg.kv_pack == real_model["layout"]["kv_pack"] == 128 // dm["hd"]
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attn_scale,
            cfg.logits_scaling, cfg.tie_word_embeddings) == (
        dm["embed_x"], dm["residual_x"], dm["attn_x"], dm["logits_div"],
        dm["tied"]) == (12.0, 0.22, 1 / 64, 8.0, True)
    assert cfg.vocab_size == dm["vocab"] == 100352
