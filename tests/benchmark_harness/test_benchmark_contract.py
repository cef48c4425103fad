"""BENCHMARK.json against the contract and against the files it names."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import registry

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$"
                   r"|head_dim|expansion|experts_per_tok")


@pytest.fixture(scope="module")
def bench():
    return registry.load_benchmark(REPO)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark", "tests/benchmark_harness"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert {w["chips"] for w in bench["workloads"]} <= {1, 4}


def _entries(bench):
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[key]:
            yield key, entry


def test_names_units_and_one_line_texts(bench):
    seen = {}
    for key, entry in _entries(bench):
        assert NAME.match(entry["name"]), entry["name"]
        group = "metric" if key in ("end_to_end", "per_layer") else key
        assert entry["name"] not in seen.setdefault(group, set())
        seen[group].add(entry["name"])
        for text in ("why", "layer", "source"):
            if text in entry and key != "end_to_end" and key != "per_layer":
                assert 1 <= len(entry[text]) <= 200
                assert "\n" not in entry[text] and "\t" not in entry[text]
        if key in ("end_to_end", "per_layer"):
            assert UNIT.match(entry["unit"]), entry["unit"]
            assert entry["better"] in ("lower", "higher")
            assert entry["source"] in SOURCES
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_metrics_keep_to_their_keys_and_bounds(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", reporting)) <= reporting
    for cell in cells:
        cell_e2e = [m for m in bench["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(cell_e2e) >= 2
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])


def test_every_name_resolves_to_its_file(bench):
    under = tuple(p + "/" for p in bench["paths"])
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["file"].startswith(under) and c["name"] in used
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert set(c["reduced"]) == set(body["reduced"])
        assert len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        for key in ("why", "assumed", "layout"):
            assert body[key]
    for w in bench["workloads"]:
        cell = registry.load_cell(REPO, w["name"])
        assert cell.params["why"] and cell.mix["why"]
        assert hasattr(registry.load_job(REPO, cell.job), "run")
        assert cell.end_to_end and cell.per_layer
    for m in bench["per_layer"]:
        reader = registry.load_layer_metric(REPO, m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        assert reader.read({"kind": "none"}) is None   # nothing to read


def test_files_under_paths_keep_to_the_allowed_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    listed = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard",
         "--"] + bench["paths"], cwd=REPO, capture_output=True, text=True,
        check=True).stdout.split("\n")
    for path in filter(None, listed):
        assert ok.match(path), path


def test_perf_md_and_the_files_name_the_same_things(bench):
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    on_disk = {
        "configs": {n[:-5] for n in os.listdir(
            os.path.join(REPO, "benchmark", "configs"))},
        "workloads": {n[:-5] for n in os.listdir(
            os.path.join(REPO, "benchmark", "workloads"))},
    }
    for key in ("configs", "workloads"):
        named = {e["name"] for e in bench[key]}
        assert named <= on_disk[key]
        for name in on_disk[key]:
            assert f"`{name}`" in perf, f"PERF.md does not name {name}"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert f"`{m['name']}`" in perf, f"PERF.md does not name {m['name']}"
    layers = {m["layer"] for m in bench["per_layer"]}
    for layer in layers:
        assert layer in perf


def test_run_py_refuses_the_cpu_and_names_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "train-sft-4k.mistral-d2", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode not in (0, None)
    assert "'cpu'" in out.stderr and "not a TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_run_py_refuses_an_unknown_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "no-such-cell",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and "no-such-cell" in out.stderr
    assert '"correct"' not in out.stdout
