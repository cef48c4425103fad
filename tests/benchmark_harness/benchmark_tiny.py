"""A throw-away benchmark root at a tiny size, for the CPU tests: its own
BENCHMARK.json, configuration, mixes and cells in a temporary directory, with
the real jobs and per-layer readers copied beside them. The harness reads
everything through the root it is given, so nothing here is special-cased."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 256,
    "max_position_embeddings": 128, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-05,
}

TRAIN_OVERRIDES = {
    "attention": "exact", "logging_steps": 2, "learning_rate": 0.001,
    "weight_decay": 0.001, "adam_beta1": 0.9, "adam_beta2": 0.99,
    "adam_eps": 1e-08, "max_grad_norm": 5.0, "warmup_steps": 0,
    "total_steps": 1000000,
}

LOOSE_CHECKS = {"loss_abs_gap": 1e-4, "grad_norm_rel_gap": 1e-3,
                "stage_grad_norm_rel_gap": 1e-3,
                "stage_update_norm_rel_gap": 1e-3}


def _dump(path: str, obj: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp: str, *, pp: int = 1, layers: int = 2,
              compute_dtype: str = "float32") -> str:
    """Writes the root and returns it. Cells: `train-tiny.tiny` (pp stages
    on pp devices) and `serve-tiny.tiny` (4 closed-loop clients)."""
    root = os.path.join(tmp, "root")
    bdir = os.path.join(root, "benchmark")
    for sub in ("jobs", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        os.path.join(bdir, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    config = {"name": "tiny", "source": "tests", "why": "tiny",
              **TINY_MODEL, "num_hidden_layers": layers,
              "compute_dtype": compute_dtype, "master_dtype": "float32",
              "weights_dtype": "float32",
              "reduced": {}, "assumed": {}, "layout": "cpu"}
    _dump(os.path.join(bdir, "configs", "tiny.json"), config)
    _dump(os.path.join(bdir, "traffic", "train-tiny.json"), {
        "kind": "train_rows", "why": "tiny", "seq_length": 32,
        "rows_per_microbatch": 2, "microbatches": max(2, pp)})
    _dump(os.path.join(bdir, "traffic", "serve-tiny.json"), {
        "kind": "closed_loop", "why": "tiny", "clients": 4, "block": 20,
        "prompt_classes": [[8, 0.5], [16, 0.5]],
        "output_classes": [[4, 0.5], [8, 0.5]],
        "ramp_completions": 2, "temperature": 0.0})
    _dump(os.path.join(bdir, "workloads", "train-tiny.tiny.json"), {
        "name": "train-tiny.tiny", "config": "tiny", "traffic": "train-tiny",
        "chips": pp, "job": "train", "why": "tiny",
        "trainer": {"mesh": {"pp": pp, "dp": 1}, "dataset_steps": 512,
                    "follow_steps": 3, "trace_steps": 2,
                    "overrides": dict(TRAIN_OVERRIDES)},
        "checks": dict(LOOSE_CHECKS)})
    _dump(os.path.join(bdir, "workloads", "serve-tiny.tiny.json"), {
        "name": "serve-tiny.tiny", "config": "tiny", "traffic": "serve-tiny",
        "chips": 1, "job": "serve_closed", "why": "tiny",
        "engine": {"kv_cache": "paged", "page_size": 4, "max_slots": 4,
                   "max_len": 24, "prompt_buckets": [8, 16], "num_pages": 24,
                   "kv_quant": "fp", "prefix_cache": False,
                   "prefill_chunk_tokens": 0, "max_queue": 64,
                   "decode_span_every": 4},
        "check_requests": 3, "trace_seconds": 1.0,
        "checks": {"served_logit_gap": 1e-3}})
    _dump(os.path.join(root, "BENCHMARK.json"), {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "tests",
                     "file": "benchmark/configs/tiny.json", "reduced": [],
                     "why": "tiny"}],
        "workloads": [
            {"name": "train-tiny.tiny", "config": "tiny",
             "traffic": "train-tiny", "chips": pp, "why": "tiny"},
            {"name": "serve-tiny.tiny", "config": "tiny",
             "traffic": "serve-tiny", "chips": 1, "why": "tiny"}],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["train-tiny.tiny"]},
            {"name": "serve_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["serve-tiny.tiny"]},
            {"name": "serve_tpot_ms_p90", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["serve-tiny.tiny"]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "data_wait_share.train", "unit": "%", "better": "lower",
             "source": "program_span", "layer": "trainer host loop",
             "moves": "train_tokens_per_s", "workloads": ["train-tiny.tiny"]},
            {"name": "decode_tick_ms.serve", "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "serving engine decode tick",
             "moves": "serve_tpot_ms_p90", "workloads": ["serve-tiny.tiny"]},
            {"name": "queue_wait_ms_p90.serve", "unit": "ms",
             "better": "lower", "source": "program_span",
             "layer": "serving engine admission",
             "moves": "serve_tokens_per_s", "workloads": ["serve-tiny.tiny"]},
            {"name": "ttft_ms_p90.serve", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "serving engine admission",
             "moves": "serve_tokens_per_s", "workloads": ["serve-tiny.tiny"]}],
    })
    return root
