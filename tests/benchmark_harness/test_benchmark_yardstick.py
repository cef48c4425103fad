"""The yardstick's arithmetic: FLOP count, peaks table, percentiles, traffic."""

import collections
import json
import os

import pytest

from benchmark import flops, peaks, stats, traffic, weights

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


# hand counts: per layer q and o are d*d, k and v d*kv_dim, gate/up/down d*f
HAND = {
    # Mistral: 2*4096*4096 + 2*4096*1024 + 3*4096*14336 = 218,103,808 a layer;
    # head 4096*32768 = 134,217,728
    ("mistral-7b-v0.3.d2", 4096):
        6 * (2 * 218_103_808 + 134_217_728) + 6 * 2 * 4096 * 4096,
    ("mistral-7b-v0.3.d8-pp4", 2048):
        6 * (8 * 218_103_808 + 134_217_728) + 6 * 8 * 4096 * 2048,
    # DeepSeek: 4*4096*4096 + 3*4096*11008 = 202,375,168 a layer;
    # head 4096*102400 = 419,430,400
    ("deepseek-llm-7b.serve", 4096):
        6 * (4 * 202_375_168 + 419_430_400) + 6 * 4 * 4096 * 4096,
}


@pytest.mark.parametrize("name,seq", sorted(HAND))
def test_flop_count_matches_hand_count(name, seq):
    assert flops.train_flops_per_token(_config(name), seq) == HAND[(name, seq)]


@pytest.mark.parametrize("name,seq", sorted(HAND))
def test_trainer_count_is_higher_by_table_and_full_attention(name, seq):
    model = _config(name)
    ours = flops.train_flops_per_token(model, seq)
    theirs = flops.trainer_count(model, seq)
    n, d, v = (model["num_hidden_layers"], model["hidden_size"],
               model["vocab_size"])
    assert theirs - ours == 6 * (v * d + 2 * n * d + d) + 6 * n * d * seq


def test_issue_numbers_for_the_training_cells():
    # 3.62 and 11.7 GFLOP a token (ISSUE 23)
    assert round(HAND[("mistral-7b-v0.3.d2", 4096)] / 1e9, 2) == 3.62
    assert round(HAND[("mistral-7b-v0.3.d8-pp4", 2048)] / 1e9, 1) == 11.7


@pytest.mark.parametrize("name,layer,embed_head", [
    ("mistral-7b-v0.3.d2", 218.1, 268.4),
    ("deepseek-llm-7b.serve", 202.4, 838.9)])
def test_parameter_counts_in_the_config_files(name, layer, embed_head):
    model = _config(name)
    count = weights.param_count(model)
    n = model["num_hidden_layers"]
    assert round(count["layers"] / n / 1e6, 1) == layer
    assert round((count["embed"] + count["lm_head"]) / 1e6, 1) == embed_head


def test_peaks_table_knows_the_v5e_and_refuses_anything_else():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError, match="no published peaks"):
            peaks.peaks_for(kind)


@pytest.mark.parametrize("values,q,want", [
    ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 90, 4.6),
    ([10.0], 90, 10.0),
    ([1.0, 2.0, float("inf")], 90, float("inf")),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def _mix(name):
    with open(os.path.join(REPO, "benchmark", "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_closed_loop_draws_repeat_from_the_seed_and_differ_across_seeds():
    mix = _mix("serve-closed-16")
    a = traffic.request_block(mix, 3_000_000_019, 0, 102400)
    b = traffic.request_block(mix, 3_000_000_019, 0, 102400)
    c = traffic.request_block(mix, 5, 0, 102400)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    assert a != traffic.request_block(mix, 3_000_000_019, 1, 102400)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_closed_loop_block_has_exactly_the_stated_shares(seed):
    mix = _mix("serve-closed-16")
    block = traffic.request_block(mix, seed, 3, 102400)
    assert len(block) == mix["block"]
    prompts = collections.Counter(r["prompt_class"] for r in block)
    outputs = collections.Counter(r["max_new_tokens"] for r in block)
    assert prompts == {128: 35, 256: 25, 512: 20, 1024: 12, 2048: 8}
    assert outputs == {64: 40, 128: 35, 256: 20, 512: 5}
    bounds = {128: 0, 256: 128, 512: 256, 1024: 512, 2048: 1024}
    for r in block:
        assert bounds[r["prompt_class"]] < len(r["prompt"]) <= r["prompt_class"]
        assert all(0 <= t < 102400 for t in r["prompt"])


def test_shares_that_do_not_fill_a_block_are_refused():
    mix = dict(_mix("serve-closed-16"), block=30)
    with pytest.raises(ValueError, match="whole number"):
        traffic.request_block(mix, 1, 0, 100)


def test_training_rows_are_a_function_of_seed_and_index():
    rows = traffic.SeededRows(seed=2 ** 31 + 3, vocab_size=32768,
                              seq_length=64, length=10)
    again = traffic.SeededRows(seed=2 ** 31 + 3, vocab_size=32768,
                               seq_length=64, length=10)
    assert (rows[4]["input_ids"] == again[4]["input_ids"]).all()
    assert (rows[4]["input_ids"] != rows[5]["input_ids"]).any()
    assert (rows[4]["labels"] == rows[4]["input_ids"]).all()
    assert rows[4]["input_ids"].max() < 32768
    with pytest.raises(IndexError):
        rows[10]
