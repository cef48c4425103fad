"""The drafting latent model in the benchmark: its job end to end at a tiny
size on the CPU (sound: correct, with drafts accepted and the counters
meeting the host's counts; each of the three committed controls: not
correct), `spec_work`'s counts against a hand count, its five per-layer
readers on synthetic observations (None where there is nothing to read), the
weights on both sides, and the entries' agreement with their files. Pins
test membership, never position or equality of a list."""

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
    glm_mtp_weights,
    harness,
    registry,
    scopes,
    spec_work,
    traffic,
    xplane,
)
from benchmark.reference import glm_dsa_mtp_decoder

sys.path.insert(0, os.path.join(REPO, "tests"))
import glm_mtp_tiny  # noqa: E402

CELL = "serve-tiny.glm"
REAL_CELL = "serve-agent-32.glm5"
REAL_CONFIG = "glm-5.ep16-d5-mtp"
READERS = ["spec_accept_rate.serve", "verify_tick_ms.serve",
           "mtp_share.serve", "verify_attn_roofline.serve",
           "verify_index_kept_share.serve"]
TINY = glm_mtp_tiny.SMALL_VOCAB          # chance accepts drafts at 16 ids
TICK = "jit(paged_decode_step)/"
CHUNK = "jit(paged_prefill_chunk)/"
LIMITS = {"served_logit_gap_mean": 1e-3, "second_query_gap_mean": 1e-3,
          "mtp_draft_gap_mean": 1e-3, "selection_missed_share": 0.01}


def make_root(tmp: str) -> str:
    """`benchmark_tiny`'s root with the tiny drafting model and a cell added
    by files and entries alone, as a PR adds them. The engine prefills a
    bucket of 8 whole and a bucket of 16 in two chunks of two pages."""
    root = benchmark_tiny.make_root(tmp)
    bdir = os.path.join(root, "benchmark")
    benchmark_tiny._dump(os.path.join(bdir, "configs", "glm.json"), {
        "name": "glm", "source": "tests", "why": "tiny", **TINY,
        "compute_dtype": "float32", "weights_dtype": "float32",
        "reduced": {}, "assumed": {}, "layout": "cpu"})
    with open(os.path.join(bdir, "workloads", "serve-tiny.tiny.json")) as f:
        cell = json.load(f)
    cell.update(name=CELL, config="glm", job="serve_closed_latent_mtp",
                checks=dict(LIMITS))
    cell["engine"].update(prefill_chunk_tokens=8, max_len=32, num_pages=32)
    benchmark_tiny._dump(os.path.join(bdir, "workloads", CELL + ".json"), cell)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "glm", "source": "tests",
                             "file": "benchmark/configs/glm.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": CELL, "config": "glm",
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
    return make_root(str(tmp_path_factory.mktemp("glm")))


def _run(root, seed=11, trace=False, seconds=1.5):
    return harness.run_cell(root, CELL, seed=seed, seconds=seconds,
                            trace=trace, devices=jax.devices()[:1],
                            t_start=time.time())


EXACT = ("emitted_off_the_one_token_reading",
         "tokens_made_off_row_ticks_plus_accepted",
         "drafts_offered_off_row_ticks", "index_counts_off_host_count",
         "routed_total_off_queries_and_module_positions",
         "dead_entries_off_refused_drafts",
         "prefill_mtp_positions_off_host_count")


def test_the_cell_is_correct_and_its_counters_meet_the_hosts_counts(
        root, capsys):
    res = _run(root, seed=2 ** 31 + 9)
    out = capsys.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 10
    # the two end-to-end metrics the cell reports; tokens/s is a note
    assert set(res["metrics"]) == {"serve_tpot_ms_p90", "setup_s"}
    assert " tokens/s; gap between tokens over " in out
    for check in EXACT:
        assert f"check {check}: value=0.0" in out
    for check in LIMITS:
        assert f"check {check}: value=" in out
    # chance accepted drafts at sixteen ids, and the host counted them
    line = next(l for l in out.splitlines()
                if l.startswith("serve: drafting: "))
    accepted = int(line.split(" tokens (")[1].split()[0])
    assert accepted >= 1 and f"host's count" in line
    assert f" {accepted} accepted)" in line


def test_the_cell_traced_reads_its_spans(root, capsys):
    res = _run(root, trace=True)
    out = capsys.readouterr().out
    assert res["correct"] is True
    # no TPU plane in a CPU trace: the two device readers find nothing to
    # read and the line leaves them out; the three span readers do
    assert set(res["metrics"]) == {"spec_accept_rate.serve",
                                   "verify_tick_ms.serve",
                                   "verify_index_kept_share.serve"}
    assert res["metrics"]["verify_tick_ms.serve"]["value"] > 0
    assert 0 < res["metrics"]["verify_index_kept_share.serve"]["value"] <= 100
    assert "spec_accept_rate.serve: " in out and "tokens a row-tick" in out


def test_a_served_token_altered_where_it_is_emitted_is_not_correct(
        root, monkeypatch):
    from llama_pipeline_parallel_tpu.serve.engine import RequestHandle

    real = RequestHandle._push

    def altered(self, token):
        real(self, (token + 1) % 16 if len(self.tokens_out) % 3 == 2
             else token)

    monkeypatch.setattr(RequestHandle, "_push", altered)
    assert _run(root, seed=8, seconds=1.0)["correct"] is False


@pytest.mark.parametrize("part,fails", [
    (6, "mtp_draft_gap_mean"), (7, "second_query_gap_mean")])
def test_what_the_timed_run_recorded_is_what_is_rated(root, monkeypatch,
                                                      capsys, part, fails):
    """The draft a tick verified and its second query's first choice are
    rated as the ENGINE's ticks put them in their fetched vectors: altered
    there, on the host's side of the read, with the programs, the served
    tokens and the replay as sound as ever, the run is not correct."""
    from llama_pipeline_parallel_tpu.models import tick_io

    real = tick_io.split_drafting

    def altered(fetched, slots):
        parts = list(real(fetched, slots))
        parts[part] = (parts[part] + 1) % 16
        return tuple(parts)

    monkeypatch.setattr(tick_io, "split_drafting", altered)
    res = _run(root, seed=7, seconds=1.0)
    out = capsys.readouterr().out
    assert res["correct"] is False
    assert f"check {fails}: " in out
    not_ok = [line.split(":")[0][len("check "):] for line in out.splitlines()
              if line.startswith("check ") and line.endswith("NOT OK")]
    assert fails in not_ok and "served_logit_gap_mean" not in not_ok


@pytest.mark.parametrize("control,fails", [
    ("fp8", "served_logit_gap_mean"),
    ("most_recent", "selection_missed_share"),
    ("unshifted", "mtp_draft_gap_mean")])
def test_a_committed_control_is_not_correct(root, monkeypatch, capsys,
                                            control, fails):
    """Each control alters the reference, not the run: the float8
    reference's first choices; a selection of the most recent positions; a
    module fed the unshifted token, which fails the module's checks and
    none of the trunk's."""
    job = registry.load_job(REPO, "serve_closed_latent_mtp")
    monkeypatch.setenv(job.CONTROL_ENV, control)
    res = _run(root, seed=7, seconds=1.0)
    out = capsys.readouterr().out
    assert res["correct"] is False and f"{job.CONTROL_ENV}={control}" in out
    not_ok = [line.split(":")[0][len("check "):] for line in out.splitlines()
              if line.startswith("check ") and line.endswith("NOT OK")]
    assert fails in not_ok
    if control == "unshifted":
        # the module's own checks (its draft, its layer's selection) and no
        # check of the trunk
        assert set(not_ok) <= {"mtp_draft_gap_mean", "selection_missed_share"}
    assert not set(not_ok) & set(EXACT)


def test_a_control_of_another_name_is_refused(root, monkeypatch):
    job = registry.load_job(REPO, "serve_closed_latent_mtp")
    monkeypatch.setenv(job.CONTROL_ENV, "bf16")
    with pytest.raises(ValueError, match="one of"):
        _run(root, seed=7, seconds=1.0)


# -- the weights and the reference ---------------------------------------------------

def test_the_programs_weights_are_the_references_layers():
    model = glm_mtp_tiny.MODEL
    program = glm_mtp_weights.make_program_weights(5, model, jnp.float32)
    top = glm_mtp_weights.make_top(5, model, jnp.float32)
    layer_fn = glm_mtp_weights.layer_fn(5, model, jnp.float32)
    assert (program["embed"]["embedding"] == top["embed"]).all()
    assert (program["mtp"]["eh_proj"] == top["mtp"]["eh_proj"]).all()
    first, module = layer_fn(0), layer_fn(3)
    assert "mlp" in first and "moe" in layer_fn(1) and "moe" in module
    assert "wg" not in first["mixer"]
    assert (program["first"]["attn"]["wqb"] == first["mixer"]["wqb"]).all()
    assert (program["periods"]["full"]["wkva"][1]
            == layer_fn(2)["mixer"]["wkva"]).all()
    assert (program["mtp"]["attn"]["wqi"] == module["mixer"]["wqi"]).all()
    assert (program["mtp"]["moe"]["gate"][0] == module["moe"]["gate"]).all()
    other = glm_mtp_weights.make_layer(6, 3, model, jnp.float32)
    assert not (other["mixer"]["wqi"] == module["mixer"]["wqi"]).all()
    with pytest.raises(ValueError, match="seed"):
        glm_mtp_weights.make_top(2 ** 32, model)


def test_the_reference_imports_nothing_of_the_program():
    import inspect

    for module in (glm_dsa_mtp_decoder, glm_mtp_weights, spec_work):
        source = inspect.getsource(module)
        assert "import llama_pipeline_parallel_tpu" not in source
        assert "from llama_pipeline_parallel_tpu" not in source


def test_the_unshifted_module_is_another_module():
    model = glm_mtp_tiny.MODEL
    top = glm_mtp_weights.make_top(3, model, jnp.float32)
    layer_fn = glm_mtp_weights.layer_fn(3, model, jnp.float32)
    ids = jnp.asarray([list(range(3, 23))], jnp.int32)
    sound = glm_dsa_mtp_decoder.forward(top, layer_fn, ids, model)
    wrong = glm_dsa_mtp_decoder.forward(top, layer_fn, ids, model,
                                        alter=("unshifted",))
    assert jnp.allclose(sound["logits"], wrong["logits"])
    assert float(jnp.abs(sound["mtp_logits"] - wrong["mtp_logits"]).max()) > 0.1
    bare = glm_dsa_mtp_decoder.forward(top, layer_fn, ids, model, heads=False)
    assert "logits" not in bare and "mtp_logits" not in bare
    assert jnp.allclose(
        glm_dsa_mtp_decoder.module_logits(top, bare["mtp_hidden"], model),
        sound["mtp_logits"], atol=1e-5)


# -- the counts, against a hand count ---------------------------------------------

@pytest.fixture(scope="module")
def real_model():
    with open(os.path.join(REPO, "benchmark", "configs",
                           REAL_CONFIG + ".json")) as f:
        return json.load(f)


def test_the_hosts_counts_are_sums_over_ticks_queries_and_caches(real_model):
    # a prompt of 2000: three ticks, the second accepting its draft
    got = spec_work.host_verify_counts([(2000, [1, 2, 1])], real_model)
    held = [2001, 2002, 2004]
    seen = sum(5 * k + 5 * (k + 1) + k for k in held) + 2003
    kept = sum(5 * min(k, 2048) + 5 * min(k + 1, 2048) + min(k, 2048)
               for k in held) + 2003
    assert got["index_visible"] == seen and got["index_selected"] == kept
    assert (got["row_ticks"], got["tokens"], got["accepted"]) == (3, 4, 1)
    assert got["mtp_positions"] == 4
    assert got["routed_total"] == 8 * (2 * 4 * 3 + 4)
    assert got["dead_entries"] == 2 * 5
    both = spec_work.host_verify_counts([(2000, [1, 2, 1]), (5000, [1])],
                                        real_model)
    assert both["index_selected"] == kept + 11 * 2048
    assert both["index_visible"] == seen + 5 * 5001 + 5 * 5002 + 5001
    assert spec_work.host_verify_counts([], real_model)["tokens"] == 0


def test_a_units_module_positions_are_its_places_but_the_prompts_last():
    unit = lambda prompt, bucket, offset, chunk: {
        "prompt": prompt, "bucket": bucket, "offset": offset, "chunk": chunk}
    assert spec_work.host_unit_positions([unit(300, 1024, 0, 1024)]) == 299
    # 3000 tokens in a bucket of 4096: the first chunk holds 952 of them
    # (places 1096 to 2047), the second the other 2048 less the last
    assert spec_work.host_unit_positions(
        [unit(3000, 4096, 0, 2048), unit(3000, 4096, 2048, 2048)]) == 2999
    assert spec_work.host_unit_positions([unit(3000, 4096, 0, 2048)]) == 952
    # a chunk of nothing but pads holds none
    assert spec_work.host_unit_positions([unit(100, 4096, 0, 2048)]) == 0


def test_the_sparse_reads_work_is_bytes_bound_at_the_published_widths(
        real_model):
    flops, hbm = spec_work.verify_read_work(1000.0, 500.0, real_model)
    assert hbm == 1000 * 256 + 500 * 1152
    assert flops == 1000 * 32 * 128 * 2 + 500 * 64 * (576 + 512) * 2
    assert hbm / 819e9 > flops / 197e12


# -- the readers on a synthetic observation ------------------------------------------

def _op(name, path, start, dur):
    return (sx.instruction(name), path, start, dur)


@pytest.fixture
def runs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path))
    return tmp_path


SPEC = dict(spec_offered=310, spec_accepted=10, spec_tokens=330,
            spec_dead_entries=1500, mtp_positions=330, row_ticks=320,
            tokens_discarded=3, index_visible=4_000_000,
            index_selected=3_000_000, ticks_ahead=9)


@pytest.fixture
def mtp_obs(runs, real_model):
    # one tick and one chunk in [0, 1000) ns, busy 900 (idle [500, 600)):
    # the tick: index_score 100, sparse_attn 150 (the kernel), latent_gather
    #   50, mtp_layer/sparse_attn 40, mtp_proj 20, mtp_head 30, decode_mlp 110
    # the chunk: sparse_attn 200, mtp_layer/mla_proj 60, mtp_embed 10, mlp 130
    ops, at = [], 0

    def add(name, path, dur):
        nonlocal at
        if at == 500:
            at = 600
        ops.append(_op(name, path, at, dur))
        at += dur

    add("fusion.1", TICK + "index_score/dot_general", 100)
    add("sparse_latent_attn.1", TICK + "sparse_attn/pallas_call", 150)
    add("fusion.2", TICK + "latent_gather/gather", 50)
    add("sparse_latent_attn.2", TICK + "mtp_layer/sparse_attn/pallas_call", 40)
    add("fusion.3", TICK + "mtp_proj/dot_general", 20)
    add("fusion.4", TICK + "mtp_head/lm_head/dot_general", 30)
    add("fusion.5", TICK + "decode_mlp/dot_general", 110)
    assert at == 500
    add("sparse_latent_attn.3", CHUNK + "sparse_attn/pallas_call", 200)
    add("fusion.6", CHUNK + "mtp_layer/mla_proj/dot_general", 60)
    add("fusion.7", CHUNK + "mtp_embed/gather", 10)
    add("fusion.8", CHUNK + "mlp/dot_general", 130)
    assert at == 1000
    host = {"python": [("serve_tick_wait", None, 0, 500)]}
    spans = [
        {"name": "serve_decode_step", "ts": 1.0, "dur": 0.25, "ticks": 10,
         "tokens": 330, **SPEC},
        {"name": "serve_prefill", "ts": 1.5, "dur": 0.1, "bucket": 4096,
         "prompt": 3000, "chunk": 2048, "offset": 2048, **SPEC}]
    cell = types.SimpleNamespace(name="serve-cell.glm", model=real_model,
                                 params={"engine": {"page_size": 64}})
    run = runs / f"{cell.name}.42.1"
    run.mkdir()
    path = sx.write(run / "t.xplane.pb", {
        "/device:TPU:0": {"XLA Ops": ops}, "/host:CPU": host})
    return {"kind": "serve", "cell": cell, "spans": spans,
            "xplane": xplane.read(path),
            "devices": [types.SimpleNamespace(device_kind="TPU v5 lite")]}


def _expected(name, model):
    if name == "spec_accept_rate.serve":
        return 100.0 * 10 / 310
    if name == "verify_tick_ms.serve":
        return 1e3 * 0.25 / 10
    if name == "mtp_share.serve":
        return 100.0 * (40 + 20 + 30 + 60 + 10) / 900
    if name == "verify_index_kept_share.serve":
        return 75.0
    # one traced tick, 340 ns under the four scopes, the module's among them
    flops, hbm = spec_work.verify_read_work(400_000, 300_000, model)
    return 100.0 * max(flops / 197e12, hbm / 819e9) / 340e-9


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_synthetic_observation(mtp_obs, real_model, name):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read(mtp_obs) == pytest.approx(_expected(name, real_model))


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_its_input(name, mtp_obs, runs):
    reader = registry.load_layer_metric(REPO, name)
    assert reader.read({"kind": "none"}) is None
    assert reader.read(dict(mtp_obs, kind="train")) is None
    # what a program that does not draft gives (the parent of the PR that
    # added drafting, or another family): spans without the counters, and a
    # trace without the module's names
    bare = dict(mtp_obs, spans=[
        {k: v for k, v in s.items() if k in (
            "name", "ts", "dur", "ticks", "tokens", "bucket", "chunk",
            "index_visible", "index_selected")}
        for s in mtp_obs["spans"]], xplane=None)
    assert reader.read(bare) is None


# -- the entries and the files ---------------------------------------------------------

def test_every_reader_agrees_with_its_benchmark_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        reader, entry = registry.load_layer_metric(REPO, name), entries[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
        assert entry["workloads"] == [REAL_CELL]
        assert entry["moves"] == "serve_tpot_ms_p90"
    loaded = registry.load_cell(REPO, REAL_CELL)
    assert loaded.job == "serve_closed_latent_mtp" and loaded.chips == 1
    assert set(loaded.per_layer) == set(READERS)
    assert {"serve_tpot_ms_p90", "setup_s"} <= set(loaded.end_to_end)
    assert "serve_tokens_per_s" not in loaded.end_to_end
    assert loaded.config_name == REAL_CONFIG
    assert loaded.traffic_name == "serve-agent-32"
    # every limit carries its reason
    assert set(loaded.params["checks"]) <= set(loaded.params["checks_why"])
    assert [c["name"] for c in bench["configs"]].count(REAL_CONFIG) == 1
    assert [w["config"] for w in bench["workloads"]].count(REAL_CONFIG) == 1


def test_the_mix_and_the_engine_are_the_issues():
    mix = traffic.load_mix(REPO, "serve-agent-32")
    assert (mix["kind"], mix["clients"], mix["block"]) == ("closed_loop", 32, 20)
    assert mix["prompt_classes"] == [[1024, 0.20], [2048, 0.40], [4096, 0.40]]
    assert mix["output_classes"] == [[192, 0.20], [448, 0.30], [896, 0.40],
                                     [1792, 0.10]]
    assert mix["ramp_completions"] == 32 and mix["temperature"] == 0.0
    assert mix["schedule_seed"] == 17
    block = traffic.request_block(mix, 17, 0, 19360)
    assert sorted(r["prompt_class"] for r in block) == (
        [1024] * 4 + [2048] * 8 + [4096] * 8)
    engine = registry.load_cell(REPO, REAL_CELL).params["engine"]
    assert (engine["max_slots"], engine["page_size"]) == (32, 64)
    assert engine["prompt_buckets"] == [1024, 2048, 4096]
    assert engine["prefill_chunk_tokens"] == 2048
    assert engine["max_len"] >= 4096 + 1792 + 2
    assert engine["num_pages"] * 64 == 32 * engine["max_len"]
    assert engine["kv_quant"] == "fp" and engine["prefix_cache"] is False


def test_the_configuration_file_keeps_every_width_and_says_what_it_cut(
        real_model):
    published = {"num_hidden_layers": 78, "first_k_dense_replace": 3,
                 "n_routed_experts": 256, "vocab_size": 154880}
    assert real_model["published"] == published
    assert set(real_model["reduced"]) == set(published)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == REAL_CONFIG)
    assert set(entry["reduced"]) == set(published)
    for key, value in {
            "hidden_size": 6144, "num_attention_heads": 64,
            "q_lora_rank": 2048, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
            "qk_rope_head_dim": 64, "v_head_dim": 256, "index_n_heads": 32,
            "index_head_dim": 128, "index_topk": 2048,
            "intermediate_size": 12288, "moe_intermediate_size": 2048,
            "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
            "num_nextn_predict_layers": 1, "router_experts": 256}.items():
        assert real_model[key] == value, key
    assert real_model["rope_parameters"]["rope_theta"] == 1000000
    job = registry.load_job(REPO, "serve_closed_latent_mtp")
    cfg = job.model_config(types.SimpleNamespace(config=real_model))
    assert cfg.drafts and cfg.page_depth == 6 and cfg.held == 16
    assert glm_mtp_weights.param_count(real_model)["total"] == 4802856704
