"""`sample_share.serve` (PR 31): the share of device busy time under the
program's `sample` scope, on a synthetic trace with and without events
there, `None` without a trace, and its agreement with `BENCHMARK.json`."""

import json
import os
import types

import pytest
import synthetic_xplane as sx
from conftest import REPO

from benchmark import registry, scopes, xplane

NAME = "sample_share.serve"
TICK = "jit(paged_decode_step)/"
CELLS = ["serve-closed-16.deepseek", "serve-closed-64.solar-open2"]


def _op(name, path, start, dur):
    return (sx.instruction(name), path, start, dur)


@pytest.fixture
def runs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path))
    return tmp_path


def _observe(runs, ops, name="serve-cell.sampled"):
    cell = types.SimpleNamespace(name=name)
    run = runs / f"{name}.7.1"
    run.mkdir()
    path = sx.write(run / "t.xplane.pb", {
        "/device:TPU:0": {"XLA Ops": ops},
        "/host:CPU": {"python": [("serve_tick_wait", None, 0, 1000)]}})
    return {"kind": "serve", "cell": cell, "spans": [],
            "xplane": xplane.read(path)}


# a tick of the program before the sampler read its batch: two sorts and
# the draw in the open; busy 800 of [0, 1000) ns
SORTING = [
    _op("fusion.1", TICK + "while/body/closed_call/decode_mlp/dot_general",
        0, 300),
    _op("fusion.2", TICK + "lm_head/dot_general", 300, 100),
    _op("sort.6", TICK + "sample/vmap()/sort", 400, 150),
    _op("sort.9", TICK + "sample/vmap()/sort", 550, 150),
    _op("fusion.3", TICK + "sample/vmap()/argmax", 700, 40),
    _op("fusion.4", "jit(sample_rowwise)/vmap()/sort", 900, 60)]
# the same tick once an all-greedy batch takes the argmax branch: what is
# under `sample` is the key split and one branch of the switch
GREEDY = [
    _op("fusion.1", TICK + "while/body/closed_call/decode_mlp/dot_general",
        0, 300),
    _op("fusion.2", TICK + "lm_head/dot_general", 300, 100),
    _op("fusion.5", TICK + "sample/vmap()/random_split", 400, 2),
    _op("fusion.3", TICK + "sample/cond/branch_0_fun/argmax", 402, 6),
    _op("fusion.7", "jit(sample_rowwise)/cond/branch_0_fun/argmax", 900, 2)]


@pytest.mark.parametrize("ops,expected", [
    (SORTING, 100.0 * (150 + 150 + 40) / 800),
    (GREEDY, 100.0 * (2 + 6) / 410),
    (GREEDY[:2], 0.0),
], ids=["two-sorts-a-tick", "argmax-branch", "nothing-under-sample"])
def test_share_of_busy_time_under_the_samplers_scope(runs, ops, expected):
    reader = registry.load_layer_metric(REPO, NAME)
    assert reader.read(_observe(runs, ops)) == pytest.approx(expected)


def test_none_without_a_trace_another_kind_or_a_program_without_names(runs):
    reader = registry.load_layer_metric(REPO, NAME)
    obs = _observe(runs, SORTING)
    assert reader.read({"kind": "none"}) is None
    assert reader.read(dict(obs, kind="train")) is None
    assert reader.read(dict(obs, xplane=None)) is None          # untraced
    bare = _observe(runs, [_op("fusion.1", "jit(step)/dot_general", 0, 10)],
                    name="serve-cell.bare")
    assert reader.read(bare) is None


def test_the_reader_agrees_with_its_benchmark_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["per_layer"][-1]["name"] == NAME    # appended, nothing moved
    entry = bench["per_layer"][-1]
    reader = registry.load_layer_metric(REPO, NAME)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])
    assert entry["better"] == "lower" and entry["workloads"] == CELLS
    # the cells that report the metric it moves, and only those, read it
    e2e = next(m for m in bench["end_to_end"] if m["name"] == reader.MOVES)
    assert e2e["workloads"] == CELLS
    for cell in CELLS:
        assert NAME in registry.load_cell(REPO, cell).per_layer
    assert NAME not in registry.load_cell(REPO, "serve-long-32.dots3").per_layer
