"""Finds everything a cell is made of by the names in `BENCHMARK.json`.

One file per thing, so that a later PR adds a configuration, a traffic mix, a
cell, a job or a per-layer metric by adding files and entries and edits
nothing that is there:

    benchmark/configs/<config>.json          sizes as run, source, reduced, assumed
    benchmark/traffic/<traffic>.json         the mix's parameters (benchmark/traffic.py)
    benchmark/workloads/<cell>.json          config + traffic + job + the program's settings
    benchmark/jobs/<job>.py                  run(ctx) -> outcome
    benchmark/layer_metrics/<metric>.py      LAYER, UNIT, MOVES, SOURCE, read(obs)
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from benchmark import traffic


# the sizes `LlamaConfig` takes, under the names the sources' config.json uses
LLAMA_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
    "rms_norm_eps", "rope_theta")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    if not os.path.exists(path):
        raise FileNotFoundError(f"{name}: no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_benchmark(root: str) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict          # benchmark/configs/<config>.json: sizes at the top level
    mix: dict             # benchmark/traffic/<traffic>.json
    params: dict          # benchmark/workloads/<cell>.json
    end_to_end: list      # names of the end-to-end metrics this cell reports
    per_layer: list       # names of the per-layer metrics read in this cell

    @property
    def model(self) -> dict:
        return self.config

    def llama_config_sizes(self) -> dict:
        return {k: self.config[k] for k in LLAMA_CONFIG_KEYS}

    @property
    def job(self) -> str:
        return self.params["job"]


def _lists_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}; it has "
                       f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    params = _load_json(os.path.join(root, "benchmark", "workloads",
                                     f"{name}.json"))
    for key, want in (("config", entry["config"]), ("traffic", entry["traffic"]),
                      ("chips", entry["chips"])):
        if params.get(key) != want:
            raise ValueError(f"workloads/{name}.json says {key}="
                             f"{params.get(key)!r}, BENCHMARK.json {want!r}")
    e2e = [m["name"] for m in bench["end_to_end"] if _lists_cell(m, name)]
    layer = [m["name"] for m in bench["per_layer"]
             if _lists_cell(m, name) and m["moves"] in e2e]
    return Cell(
        name=name, chips=entry["chips"], config_name=entry["config"],
        traffic_name=entry["traffic"],
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        mix=traffic.load_mix(root, entry["traffic"]),
        params=params, end_to_end=e2e, per_layer=layer)


def load_job(root: str, job: str):
    return _load_module(os.path.join(root, "benchmark", "jobs", f"{job}.py"),
                        f"benchmark_job_{job}")


def load_layer_metric(root: str, name: str):
    return _load_module(
        os.path.join(root, "benchmark", "layer_metrics", f"{name}.py"),
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"))
