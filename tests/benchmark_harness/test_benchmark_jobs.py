"""The jobs end to end at a tiny size on the CPU, through the harness's own
`run_cell` (everything but the look for a chip): sound runs come out correct,
runs with the timed path broken underneath come out not correct, and a
configuration, cell and per-layer metric added as files alone are found."""

import json
import os
import time

import jax
import pytest

import benchmark_tiny
from benchmark import harness, registry


def _run(root, cell, seed=11, trace=False, devices=1, seconds=1.0):
    return harness.run_cell(root, cell, seed=seed, seconds=seconds,
                            trace=trace, devices=jax.devices()[:devices],
                            t_start=time.time())


def _line_is_whole(res, names):
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert set(res["metrics"]) == set(names)
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"]["count"] >= 1 and res["device"]["platform"] == "cpu"
    json.dumps(res)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchmark_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_training_cell_is_correct_and_reports_its_metrics(root):
    res = _run(root, "train-tiny.tiny", seed=2 ** 31 + 7)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    _line_is_whole(res, ["train_tokens_per_s", "setup_s"])
    assert not os.listdir(os.path.join(root, "benchmark", ".runs"))


def test_training_cell_traced_reads_its_per_layer_metrics(root):
    res = _run(root, "train-tiny.tiny", trace=True)
    assert res["correct"] is True
    # no TPU plane in a CPU trace: the device readers find nothing to read
    assert set(res["metrics"]) == {"data_wait_share.train"}
    assert 0 <= res["metrics"]["data_wait_share.train"]["value"] <= 100


def test_pipelined_training_cell_on_four_virtual_devices(tmp_path):
    root = benchmark_tiny.make_root(str(tmp_path), pp=4, layers=4)
    res = _run(root, "train-tiny.tiny", devices=4)
    assert res["correct"] is True and res["device"]["count"] == 4


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    from llama_pipeline_parallel_tpu.parallel import train_step as ts

    real = ts.make_train_step

    def broken(*args, **kwargs):
        step = real(*args, **kwargs)

        def frozen(state, batch):
            new_state, metrics = step(state, batch)
            keep = jax.tree.map(lambda x: x.copy(), new_state)
            return keep._replace(params=jax.tree.map(
                lambda new, old: old * 1.0 + 0.0 * new, new_state.params,
                _FIRST.setdefault("params", jax.tree.map(
                    lambda x: x.copy(), new_state.params)))), metrics

        return frozen

    _FIRST = {}
    monkeypatch.setattr(ts, "make_train_step", broken)
    res = _run(root, "train-tiny.tiny", seed=5)
    assert res["correct"] is False


def test_a_batch_with_rows_left_out_is_not_correct(root, monkeypatch):
    from llama_pipeline_parallel_tpu.parallel import train_step as ts

    real = ts.make_train_step

    def broken(*args, **kwargs):
        step = real(*args, **kwargs)

        def half(state, batch):
            rows = batch["input_ids"].shape[0]
            first = {k: v.at[rows // 2:].set(v[:rows // 2])
                     for k, v in batch.items()}   # second half never seen
            return step(state, first)

        return half

    monkeypatch.setattr(ts, "make_train_step", broken)
    res = _run(root, "train-tiny.tiny", seed=6)
    assert res["correct"] is False


def test_serving_cell_is_correct_and_reports_its_metrics(root):
    res = _run(root, "serve-tiny.tiny", seed=2 ** 31 + 9, seconds=1.5)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 10
    _line_is_whole(res, ["serve_tokens_per_s", "serve_tpot_ms_p90",
                         "setup_s"])


def test_serving_cell_traced_reads_the_engines_spans(root):
    res = _run(root, "serve-tiny.tiny", trace=True, seconds=1.5)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"decode_tick_ms.serve",
                                   "queue_wait_ms_p90.serve",
                                   "ttft_ms_p90.serve"}


def test_a_served_token_altered_where_it_is_emitted_is_not_correct(
        root, monkeypatch):
    from llama_pipeline_parallel_tpu.serve.engine import RequestHandle

    real = RequestHandle._push

    def altered(self, token):
        real(self, (token + 1) % 256 if len(self.tokens_out) % 3 == 2
             else token)

    monkeypatch.setattr(RequestHandle, "_push", altered)
    res = _run(root, "serve-tiny.tiny", seed=8, seconds=1.0)
    assert res["correct"] is False


def test_a_later_pr_adds_by_files_alone(tmp_path):
    """A configuration, a mix, a cell and a per-layer metric that exist only
    as new files and new BENCHMARK.json entries are found and run; no file
    that was there is edited."""
    root = benchmark_tiny.make_root(str(tmp_path))
    bdir = os.path.join(root, "benchmark")
    before = {}
    for folder, _, names in os.walk(bdir):
        for n in names:
            p = os.path.join(folder, n)
            before[p] = open(p, "rb").read()

    def load(p):
        with open(p) as f:
            return json.load(f)

    cfg = dict(load(os.path.join(bdir, "configs", "tiny.json")),
               name="tiny-wide", intermediate_size=192)
    benchmark_tiny._dump(os.path.join(bdir, "configs", "tiny-wide.json"), cfg)
    mix = dict(load(os.path.join(bdir, "traffic", "train-tiny.json")),
               seq_length=16)
    benchmark_tiny._dump(os.path.join(bdir, "traffic", "train-short.json"), mix)
    cell = dict(load(os.path.join(bdir, "workloads", "train-tiny.tiny.json")),
                name="train-short.tiny-wide", config="tiny-wide",
                traffic="train-short")
    benchmark_tiny._dump(
        os.path.join(bdir, "workloads", "train-short.tiny-wide.json"), cell)
    with open(os.path.join(bdir, "layer_metrics", "steps_in_window.py"),
              "w") as f:
        f.write('LAYER = "trainer host loop"\nUNIT = "steps"\n'
                'MOVES = "train_tokens_per_s"\nSOURCE = "program_span"\n\n\n'
                'def read(obs):\n'
                '    if obs.get("kind") != "train":\n        return None\n'
                '    return float(sum(1 for s in obs["spans"]\n'
                '                     if s["name"] == "step_dispatch"))\n')
    bench = load(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": "tiny-wide", "source": "tests",
                             "file": "benchmark/configs/tiny-wide.json",
                             "reduced": [], "why": "added by files"})
    bench["workloads"].append({"name": "train-short.tiny-wide",
                               "config": "tiny-wide", "traffic": "train-short",
                               "chips": 1, "why": "added by files"})
    bench["end_to_end"][0]["workloads"].append("train-short.tiny-wide")
    bench["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_span", "layer": "trainer host loop",
        "moves": "train_tokens_per_s",
        "workloads": ["train-short.tiny-wide"]})
    benchmark_tiny._dump(os.path.join(root, "BENCHMARK.json"), bench)

    found = registry.load_cell(root, "train-short.tiny-wide")
    assert found.model["intermediate_size"] == 192
    assert found.mix["seq_length"] == 16
    assert found.per_layer == ["steps_in_window"]
    res = _run(root, "train-short.tiny-wide", trace=True)
    assert res["correct"] is True
    assert res["metrics"]["steps_in_window"]["value"] >= 2
    for p, body in before.items():
        assert open(p, "rb").read() == body, f"{p} was edited"
