"""`benchmark/scopes.py`: the classifier on real `op_name` paths of both
training programs, and the reduction on a small synthetic `.xplane.pb` whose
events carry their paths where the profiler keeps them (synthetic_xplane.py):
nested events, two device planes, a host plane with tick annotations."""

import pytest
import synthetic_xplane as sx

from benchmark import scopes, xplane

# Real paths: `jit(train_step)` of the pp=1 cell (one stage: its B units are
# the whole step) and of the pp=4 cell (compiled on CPU at a tiny size; the
# paths do not depend on the backend).
PP1 = "jit(train_step)/while/body/closed_call/"
PP4 = "jit(train_step)/shard_map/while/body/closed_call/"
LAYER = "while/body/closed_call/"

CLASSIFIED = [
    (PP1 + "pp_fwd/jvp()/" + LAYER + "attn_core/bqhd,bkhd->bhqk/dot_general",
     "forward", "attn_core"),
    (PP4 + "pp_fwd/" + LAYER + "checkpoint/mlp/dot_general",
     "forward", "mlp"),
    (PP1 + "pp_bwd/transpose(jvp())/" + LAYER
     + "checkpoint/rematted_computation/mlp/dot_general",
     "recompute", "mlp"),                                # JAX's remat marker
    (PP4 + "pp_recompute/jvp()/" + LAYER + "attn_qkv/dot_general",
     "recompute", "attn_qkv"),                           # the schedule's own
    (PP4 + "pp_recompute/jvp()/cond/branch_1_fun/lm_head_loss/lm_head/"
     "dot_general", "recompute", "lm_head"),
    (PP4 + "pp_bwd/transpose(jvp())/" + LAYER + "checkpoint/attn_out/"
     "dot_general", "backward", "attn_out"),
    (PP1 + "pp_bwd/transpose(jvp())/cond/branch_1_fun/lm_head_loss/"
     "jit(log_softmax)/mul", "backward", "lm_head_loss"),
    ("jit(train_step)/shard_map/while/body/pp_w/transpose(jvp())/" + LAYER
     + "checkpoint/mlp/dot_general", "weight-gradient", "mlp"),
    ("jit(train_step)/shard_map/while/body/pp_w/pp_recompute/jvp()/" + LAYER
     + "mlp/dot_general", "recompute", "mlp"),           # a W unit's replay
    ("jit(train_step)/optimizer/mul", "optimizer", "optimizer"),
    ("jit(train_step)/grad_clip/reduce_sum", "optimizer", "grad_clip"),
    (PP4 + "pp_handoff/ppermute", "hand-off", "pp_handoff"),
    ("jit(train_step)/shard_map/grad_reduce/psum", "other", "grad_reduce"),
    ("jit(train_step)/numerics/reduce_sum", "other", "numerics"),
    # one stage: the B unit's forward is the step's only forward
    (PP1 + "pp_bwd/pp_fwd/jvp()/" + LAYER + "mlp/dot_general",
     "forward", "mlp"),
    (PP1 + "pp_bwd/add", "backward", "pp_bwd"),
    (PP4 + "pp_bwd/pp_recompute/jvp()/" + LAYER + "mlp/dot_general",
     "recompute", "mlp"),
    ("jit(train_step)/while", "other", None),
    ("", "other", None),
    # a program that names nothing: the parent's paths
    ("jit(_step)/jit(main)/while/body/closed_call/transpose(jvp())/"
     "dot_general", "backward", None),
    # the serving tick
    ("jit(paged_decode_step)/while/body/closed_call/kv_gather/gather",
     "forward", "kv_gather"),
    ("jit(paged_decode_step)/while/body/closed_call/decode_mlp/cast_weights/"
     "convert_element_type", "forward", "cast_weights"),
]


@pytest.mark.parametrize("path,klass,leaf", CLASSIFIED)
def test_classifier_and_leaf_scope_on_real_paths(path, klass, leaf):
    assert scopes.classify(path) == klass
    assert scopes.leaf_scope(path) == leaf
    assert klass in scopes.CLASSES


def test_vocabulary_is_the_programs():
    from llama_pipeline_parallel_tpu.utils import trace

    assert sorted(scopes.VOCABULARY) == sorted(trace.SCOPES)


# -- the reduction on a synthetic trace ---------------------------------------

def _op(name, path, start, dur):
    return (sx.instruction(name), path, start, dur)


@pytest.fixture
def pb(tmp_path):
    # stage 0 (device 0): a `while` [0,100) whose body holds a forward op
    # [0,30), a recompute op [30,50), a backward op [50,90) with a kernel
    # nested in it [60,70), and a hand-off [90,100); then an optimizer op
    # [100,120) and idle until 200
    dev0 = {"XLA Ops": [
        _op("while.1", "jit(train_step)/while", 0, 100),
        _op("fusion.1", PP4 + "pp_fwd/" + LAYER + "mlp/dot_general", 0, 30),
        _op("fusion.2", PP4 + "pp_recompute/jvp()/" + LAYER
            + "mlp/dot_general", 30, 20),
        _op("fusion.3", PP4 + "pp_bwd/transpose(jvp())/" + LAYER
            + "checkpoint/mlp/dot_general", 50, 40),
        _op("flash_fwd.2", PP4 + "pp_bwd/transpose(jvp())/" + LAYER
            + "checkpoint/rematted_computation/attn_core/flash_fwd/"
              "pallas_call", 60, 10),
        _op("collective-permute-start.1", PP4 + "pp_handoff/ppermute", 90, 10),
        _op("fusion.9", "jit(train_step)/optimizer/mul", 100, 20),
        _op("copy-done.1", None, 199, 1)],
        "XLA Modules": [_op("jit_train_step", None, 0, 200)]}
    # stage 1 (device 1): busy the whole window in one forward op
    dev1 = {"XLA Ops": [
        _op("fusion.1", PP4 + "pp_fwd/" + LAYER + "mlp/dot_general", 0, 200)]}
    host = {"python": [("serve_tick_wait", None, 0, 100)]}
    return sx.write(tmp_path / "t.xplane.pb", {
        "/device:TPU:0": dev0, "/device:TPU:1": dev1, "/host:CPU": host})


def test_read_joins_each_event_with_its_path(pb):
    trace = scopes.read(pb)
    assert sorted(trace["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    events = trace["devices"]["/device:TPU:0"]
    assert len(events) == 8                       # not the module line
    by_name = {op.instruction: (op.path, op.start_ns, op.end_ns)
               for op in events}
    assert by_name["fusion.3"] == (
        PP4 + "pp_bwd/transpose(jvp())/" + LAYER + "checkpoint/mlp/dot_general",
        50.0, 90.0)
    assert by_name["copy-done.1"][0] == ""        # an event with no path
    assert {op.result for op in events} == {"bf16[8,128]"}
    # the same clock and names as the names-only reader
    plain = xplane.read(pb)["devices"]["/device:TPU:0"]
    assert [(xplane.short_name(n), s, e) for n, s, e in plain] == [
        (op.instruction, op.start_ns, op.end_ns) for op in events]


def test_class_and_scope_shares_are_self_time_over_busy(pb):
    trace = scopes.read(pb)
    # device 0: busy 121 of 200; the while's own time is 0 (its body fills
    # it), the backward op's own time is 40 - 10
    classes = scopes.class_shares(trace)
    d0 = lambda ns: 100.0 * ns / 121 / 2
    assert classes["forward"] == pytest.approx(d0(30) + 50.0)
    assert classes["recompute"] == pytest.approx(d0(20 + 10))
    assert classes["backward"] == pytest.approx(d0(30))
    assert classes["hand-off"] == pytest.approx(d0(10))
    assert classes["optimizer"] == pytest.approx(d0(20))
    assert classes["other"] == pytest.approx(d0(1))
    assert sum(classes.values()) == pytest.approx(100.0)
    leaves = scopes.leaf_shares(trace)
    assert leaves["mlp"] == pytest.approx(d0(30 + 20 + 30) + 50.0)
    assert leaves["attn_core"] == pytest.approx(d0(10))
    assert leaves["(no scope)"] == pytest.approx(d0(1))
    assert sum(leaves.values()) == pytest.approx(100.0)


def test_share_under_counts_every_depth_and_either_base(pb):
    trace = scopes.read(pb)
    assert scopes.share_under(trace, ("pp_bwd",)) == pytest.approx(
        100.0 * 40 / 121 / 2)                     # the nested kernel too
    assert scopes.share_under(trace, ("pp_handoff",), of="window") == \
        pytest.approx(100.0 * 10 / 200 / 2)


def test_kernel_durations_by_the_kernels_name(pb):
    trace = scopes.read(pb)
    assert scopes.kernel_durations(trace, "flash_fwd") == [10.0]
    assert scopes.kernel_durations(trace, "flash") == []   # not a prefix match


def test_bubble_by_stage_uses_each_stages_plane_and_counts(pb):
    trace = scopes.read(pb)
    schedule = [
        {"stage": 0, "devices": [0], "f": 22, "f_masked": 6, "b": 22,
         "b_masked": 6, "w": 0, "w_masked": 0},
        {"stage": 1, "devices": [1], "f": 4, "f_masked": 1, "b": 4,
         "b_masked": 1, "w": 0, "w_masked": 0}]
    got = scopes.bubble_by_stage(trace, schedule)
    # stage 0: F time 30, B time 20 + 40 (recompute + backward, kernel in it)
    assert got[0] == pytest.approx(100.0 * (30 + 60) * 6 / 22 / 121)
    assert got[1] == pytest.approx(100.0 * 200 / 4 / 200)
    schedule[1]["devices"] = [7]                  # a plane the trace lacks
    assert scopes.bubble_by_stage(trace, schedule) is None


def test_for_observation_finds_the_runs_own_trace(pb, tmp_path, monkeypatch):
    import shutil
    import types

    cell = types.SimpleNamespace(name="train-x.tiny")
    obs = {"kind": "train", "cell": cell, "xplane": xplane.read(pb)}
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path / "runs"))
    assert scopes.for_observation(obs, "train") is None   # no run directory
    run = tmp_path / "runs" / "train-x.tiny.123.1" / "measured" / "profile"
    run.mkdir(parents=True)
    shutil.copy(pb, run / "host.xplane.pb")
    assert sorted(scopes.for_observation(obs, "train")["devices"]) == [
        "/device:TPU:0", "/device:TPU:1"]
    assert scopes.for_observation(obs, "serve") is None   # the other kind
    assert scopes.for_observation(dict(obs, xplane=None), "train") is None
    # an untraced run's directory (`.0`) is not looked at
    other = types.SimpleNamespace(name="train-y.tiny")
    (tmp_path / "runs" / "train-y.tiny.123.0").mkdir()
    assert scopes.for_observation(dict(obs, cell=other), "train") is None


def test_a_program_that_names_nothing_reads_as_none(tmp_path, monkeypatch):
    import types

    run = tmp_path / "runs" / "train-x.tiny.5.1"
    run.mkdir(parents=True)
    path = sx.write(run / "t.xplane.pb", {"/device:TPU:0": {"XLA Ops": [
        _op("fusion.1", "jit(_step)/jit(main)/transpose(jvp())/dot_general",
            0, 10)]}})
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path / "runs"))
    obs = {"kind": "train", "xplane": xplane.read(path),
           "cell": types.SimpleNamespace(name="train-x.tiny")}
    assert scopes.read(path)["named"] is False
    assert scopes.for_observation(obs, "train") is None
