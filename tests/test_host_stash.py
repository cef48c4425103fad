"""Host-DRAM residual offload (utils/host_stash.py + the pipeline hooks).

The CI `Offload` gate: tiering the zb1 W-queue and the schedules' stage-input
ring buffer to host memory must change WHERE bytes live, never their values —
offload on/off is asserted bit-exact across the schedule parity grid (the
test_zero_bubble.py assertion style), the stash traffic must be structurally
ASYNC (device_put data movement in the jaxpr, no host-sync primitive in the
lowered step), the byte models preflight consumes are pinned, and the chaos
leg proves a SIGKILL with residuals resident on host resumes to bit parity.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh
from llama_pipeline_parallel_tpu.utils import host_stash

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny(num_hidden_layers=8)


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(jax.random.PRNGKey(0), cfg)


def make_batch(cfg, batch_size=8, seqlen=16, seed=42):
    rng = np.random.RandomState(seed)
    ids = rng.randint(3, cfg.vocab_size, size=(batch_size, seqlen)).astype(np.int32)
    mask = np.ones((batch_size, seqlen), np.int32)
    mask[:, -3:] = 0
    labels = ids.copy()
    labels[mask == 0] = llama.IGNORE_INDEX
    labels[:, :2] = llama.IGNORE_INDEX
    pos = np.broadcast_to(np.arange(seqlen, dtype=np.int32),
                          (batch_size, seqlen)).copy()
    return {
        "input_ids": jnp.asarray(ids),
        "attention_mask": jnp.asarray(mask),
        "position_ids": jnp.asarray(pos),
        "labels": jnp.asarray(labels),
    }


def run_schedule(params, batch, cfg, pp, schedule, v=1, dp=1, tp=1,
                 microbatches=4, chunks=1, **offload):
    mesh = make_mesh(MeshConfig(pp=pp, dp=dp, tp=tp))
    manifest = StageManifest.for_config(cfg, pp, virtual_stages=v)
    stacked = pl.stack_stages(params, manifest)
    pcfg = pl.PipelineConfig(num_stages=pp, num_microbatches=microbatches,
                             schedule=schedule, virtual_stages=v,
                             accum_chunks=chunks, **offload)
    fn = jax.jit(pl.make_pipeline_loss_and_grad(mesh, cfg, pcfg, stacked))
    loss, grads = fn(stacked, batch)
    return float(loss), pl.unstack_stages(grads, manifest)


def assert_tree_bitexact(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


@pytest.fixture(scope="module")
def flat_reference(cfg, params):
    """One flat no-offload run shared by the fast-lane parity tests (every
    schedule below is already proven bit-equal to it in test_zero_bubble /
    test_interleaved, so it is the one baseline that covers them all)."""
    batch = make_batch(cfg)
    loss, grads = run_schedule(params, batch, cfg, 2, "1f1b")
    return batch, loss, grads


# ---------------------------------------------------------------------------
# Parity: offload on == offload off, bit for bit
# ---------------------------------------------------------------------------

def test_zb1_wgrad_and_acts_offload_bitexact(cfg, params, devices,
                                             flat_reference, monkeypatch):
    """Both tiers at once under zb1 (the offload conf's combination plus
    the ring): values must round-trip the host untouched — loss AND grads
    bit-equal to the flat no-offload schedule. Transfers forced on: on CPU
    the gate would otherwise elide them, and this test exists to run the
    real device_put round trip (plain jit lowers it cleanly there)."""
    monkeypatch.setattr(host_stash, "transfers_enabled", lambda: True)
    batch, l_ref, g_ref = flat_reference
    l, g = run_schedule(params, batch, cfg, 2, "zb1", v=2,
                        offload_wgrad=True, offload_activations=True)
    assert l == l_ref
    assert_tree_bitexact(g, g_ref)


@pytest.mark.slow  # round gate: the zb1 both-tiers case above keeps the
# bit-exactness acceptance in the tier-1 lane; these two variants ride
# with the rest of the grid to respect the 870s budget
def test_1f1b_activation_offload_bitexact(cfg, params, devices,
                                          flat_reference, monkeypatch):
    """The flat schedule's ring buffer tiered to host: same stage inputs
    come back for every backward recompute."""
    monkeypatch.setattr(host_stash, "transfers_enabled", lambda: True)
    batch, l_ref, g_ref = flat_reference
    l, g = run_schedule(params, batch, cfg, 2, "1f1b",
                        offload_activations=True)
    assert l == l_ref
    assert_tree_bitexact(g, g_ref)


@pytest.mark.slow
def test_offload_parity_gated_off(cfg, params, devices, flat_reference,
                                  monkeypatch):
    """The gated-off mode (what the CPU backend runs by default): same
    schedule restructuring, stores device-resident, still bit-exact."""
    assert not host_stash.transfers_enabled()
    batch, l_ref, g_ref = flat_reference
    l, g = run_schedule(params, batch, cfg, 2, "zb1", v=2,
                        offload_wgrad=True, offload_activations=True)
    assert l == l_ref
    assert_tree_bitexact(g, g_ref)


@pytest.mark.slow
@pytest.mark.parametrize("pp,schedule,v,kw", [
    (2, "interleaved_1f1b", 2, {"offload_activations": True}),
    (4, "zb1", 2, {"offload_wgrad": True}),
    (2, "zb1", 1, {"offload_wgrad": True, "offload_activations": True}),
    (4, "1f1b", 1, {"offload_activations": True}),
])
def test_offload_parity_grid(cfg, params, devices, flat_reference, pp,
                             schedule, v, kw, monkeypatch):
    """The rest of the pp x schedule x v grid (round gate) — each still
    pinned against the ONE flat reference (these shapes are all bit-equal
    to it, per test_zero_bubble/test_interleaved)."""
    monkeypatch.setattr(host_stash, "transfers_enabled", lambda: True)
    batch, l_ref, g_ref = flat_reference
    l, g = run_schedule(params, batch, cfg, pp, schedule, v=v, **kw)
    assert l == l_ref
    assert_tree_bitexact(g, g_ref)


@pytest.mark.slow
@pytest.mark.parametrize("tp,chunks", [(2, 1), (1, 2)])
def test_offload_parity_hybrids_on_vs_off(cfg, params, devices, tp, chunks,
                                          monkeypatch):
    """tp sharding and chunked accumulation change the numerics baseline
    itself (vocab-parallel CE / per-chunk fp32 fold order), so these
    hybrids pin offload ON against offload OFF at the SAME config — the
    knob's actual contract. The tp leg drives the split head's
    vocab-parallel grads through a host-tiered W queue, the hybrid most
    likely to break independently."""
    monkeypatch.setattr(host_stash, "transfers_enabled", lambda: True)
    batch = make_batch(cfg)
    l_off, g_off = run_schedule(params, batch, cfg, 2, "zb1", v=2, tp=tp,
                                chunks=chunks)
    l_on, g_on = run_schedule(params, batch, cfg, 2, "zb1", v=2, tp=tp,
                              chunks=chunks, offload_wgrad=True,
                              offload_activations=True)
    assert l_on == l_off
    assert_tree_bitexact(g_on, g_off)


# ---------------------------------------------------------------------------
# Structural: transfers are async data movement, not host syncs
# ---------------------------------------------------------------------------

def test_stash_transfers_async_no_host_sync(cfg, params, devices,
                                            monkeypatch):
    """The acceptance's structural assertion: with offload on, the scan
    phases' stash traffic appears in the jaxpr as `device_put` data
    movement targeting the pinned_host/device memory kinds (XLA lowers
    these to async copy-start/copy-done pairs), and the lowered program
    contains NO host-synchronizing primitive — no callback, no
    infeed/outfeed — anywhere a blocking sync could hide. Off, the jaxpr
    carries no memory-space traffic at all (the knob adds nothing); gated
    off (the CPU backend's default), likewise."""
    batch = make_batch(cfg)
    mesh = make_mesh(MeshConfig(pp=2))
    manifest = StageManifest.for_config(cfg, 2, virtual_stages=2)
    stacked = pl.stack_stages(params, manifest)

    def build(**offload):
        pcfg = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                                 schedule="zb1", virtual_stages=2, **offload)
        return pl.make_pipeline_loss_and_grad(mesh, cfg, pcfg, stacked)

    monkeypatch.setattr(host_stash, "transfers_enabled", lambda: True)
    on = build(offload_wgrad=True, offload_activations=True)
    jaxpr_on = str(jax.make_jaxpr(on)(stacked, batch))
    # pushes D2H: ring (warmup+steady) + W-queue pair (steady+drain);
    # pops H2D: ring read, W-drain prefetch pair + its initial fetch
    assert jaxpr_on.count("MemorySpace.Host") >= 6, \
        jaxpr_on.count("MemorySpace.Host")
    assert jaxpr_on.count("MemorySpace.Device") >= 4
    assert "device_put" in jaxpr_on

    off = build()
    jaxpr_off = str(jax.make_jaxpr(off)(stacked, batch))
    assert "MemorySpace" not in jaxpr_off

    # the lowered step: transfers must not smuggle in a host round-trip
    text = jax.jit(on).lower(stacked, batch).as_text()
    for marker in ("callback", "infeed", "outfeed", "SendToHost",
                   "RecvFromHost"):
        assert marker not in text, f"host-sync marker {marker!r} in HLO"

    # the backend gate: on CPU the default mode emits no transfer at all —
    # the program the sharded-jit partitioner sees is annotation-free
    monkeypatch.undo()
    gated = str(jax.make_jaxpr(build(offload_wgrad=True,
                                     offload_activations=True))(
                                         stacked, batch))
    assert "MemorySpace" not in gated


def test_wdrain_prefetches_one_unit_ahead(cfg, params, devices):
    """Pin the double-buffered drain's structure: the offloaded W-drain
    scan carries the NEXT unit's residual pair (two extra hidden-shaped
    carries vs the in-HBM drain), so the H2D fetch of unit g+1 is in
    flight while unit g replays."""
    batch = make_batch(cfg)
    mesh = make_mesh(MeshConfig(pp=2))
    manifest = StageManifest.for_config(cfg, 2, virtual_stages=2)
    stacked = pl.stack_stages(params, manifest)

    def sub_jaxprs(v):
        if hasattr(v, "eqns"):       # open Jaxpr (shard_map's param)
            return [v]
        if hasattr(v, "jaxpr"):      # ClosedJaxpr (scan/pjit's param)
            return [v.jaxpr]
        if isinstance(v, (tuple, list)):  # cond branches
            return [j for x in v for j in sub_jaxprs(x)]
        return []

    def scan_carry_counts(jaxpr, acc=None):
        acc = [] if acc is None else acc
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                acc.append(eqn.params["num_carry"])
            for v in eqn.params.values():
                for j in sub_jaxprs(v):
                    scan_carry_counts(j, acc)
        return acc

    def counts(offload):
        pcfg = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                                 schedule="zb1", virtual_stages=2,
                                 offload_wgrad=offload)
        fn = pl.make_pipeline_loss_and_grad(mesh, cfg, pcfg, stacked)
        return sorted(scan_carry_counts(
            jax.make_jaxpr(fn)(stacked, batch).jaxpr))

    counts_off, counts_on = counts(False), counts(True)
    # offload adds exactly TWO carries (the prefetched x/dy residual pair)
    # to exactly ONE scan — the W-drain (the grad-accumulator-only scan;
    # the phase scans and the within-chunk layer scans are untouched)
    assert len(counts_on) == len(counts_off)
    deltas = sorted(a - b for a, b in zip(counts_on, counts_off))
    assert deltas == [0] * (len(deltas) - 1) + [2], (counts_off, counts_on)


# ---------------------------------------------------------------------------
# The staging-layer primitives + byte models
# ---------------------------------------------------------------------------

def test_stash_push_pop_roundtrip_and_garbage_slot(monkeypatch):
    monkeypatch.setattr(host_stash, "transfers_enabled", lambda: True)  # real transfers on CPU
    v = jnp.arange(4.0)

    @jax.jit
    def drill():
        # memory-kind transfers only exist inside jit (the schedules'
        # usage); stash_init is called there too
        buf = host_stash.stash_init(3, (4,), jnp.float32)
        buf = host_stash.stash_push(buf, v, jnp.int32(1), jnp.bool_(True))
        # invalid write must land in the garbage slot, not slot 2
        buf = host_stash.stash_push(buf, v * 9, jnp.int32(2), jnp.bool_(False))
        return (host_stash.stash_pop(buf, jnp.int32(1)),
                host_stash.stash_pop(buf, jnp.int32(2)), buf)

    got1, got2, buf = drill()
    assert buf.shape == (4, 4)  # 3 slots + 1 garbage
    np.testing.assert_array_equal(np.asarray(got1), np.asarray(v))
    np.testing.assert_array_equal(np.asarray(got2), np.zeros(4))
    np.testing.assert_array_equal(np.asarray(buf)[3], 9 * np.asarray(v))


def test_transfer_gate_is_the_backend_alone():
    # elided on the CPU backend (XLA-CPU's SPMD partitioner rejects the
    # placement annotations under sharded jit), emitted everywhere else;
    # no environment switch can turn them off on a chip
    assert host_stash.transfers_enabled() is False


def test_measure_transfer_bandwidth_smoke():
    bw = host_stash.measure_transfer_bandwidth(nbytes=1 << 16, reps=1)
    assert bw["h2d_gibps"] > 0 and bw["d2h_gibps"] > 0


def _pcfg(schedule, s, m, c=1, v=1, **kw):
    return pl.PipelineConfig(num_stages=s, num_microbatches=m,
                             accum_chunks=c, schedule=schedule,
                             virtual_stages=v, **kw)


def test_activation_ring_model():
    # flat: min(2S-1, m) per flush; chunked: min(2vS-1, mv)
    assert pl.activation_ring_slots(_pcfg("1f1b", 4, 16)) == 7
    assert pl.activation_ring_slots(_pcfg("1f1b", 4, 2)) == 2
    assert pl.activation_ring_slots(_pcfg("1f1b", 1, 8)) == 0
    assert pl.activation_ring_slots(_pcfg("gpipe", 4, 8)) == 0
    assert pl.activation_ring_slots(_pcfg("interleaved_1f1b", 4, 16, v=2)) == 15
    assert pl.activation_ring_slots(_pcfg("zb1", 8, 256, v=2)) == 31
    assert pl.activation_ring_slots(_pcfg("zb1", 2, 8, c=4, v=2)) == 4
    # bytes: slots x [mb, L, d] x dtype (the 65B pp8 v2 shape: 31 x 64 MiB)
    assert pl.activation_ring_bytes(_pcfg("zb1", 8, 256, v=2), 8, 512,
                                    8192, 2) == 31 * 8 * 512 * 8192 * 2


def test_host_stash_bytes_model():
    dims = (8, 512, 8192, 2)
    slot = 8 * 512 * 8192 * 2
    off = _pcfg("zb1", 8, 256, v=2)
    assert pl.host_stash_bytes(off, *dims) == 0  # nothing tiered
    wg = _pcfg("zb1", 8, 256, v=2, offload_wgrad=True)
    assert pl.host_stash_bytes(wg, *dims) == (
        pl.wgrad_stash_bytes(wg, *dims) + 2 * slot)  # + garbage slots
    both = _pcfg("zb1", 8, 256, v=2, offload_wgrad=True,
                 offload_activations=True)
    assert pl.host_stash_bytes(both, *dims) == (
        pl.wgrad_stash_bytes(both, *dims) + 2 * slot
        + pl.activation_ring_bytes(both, *dims) + slot)
    # ~64 GiB of W stash at the reference micro-batch shape — the number
    # the offload conf's header and docs/PREFLIGHT.md quote
    assert round(pl.wgrad_stash_bytes(wg, *dims) / (1 << 30)) == 64


# ---------------------------------------------------------------------------
# Validation + config plumbing
# ---------------------------------------------------------------------------

def test_offload_wgrad_requires_zb1():
    with pytest.raises(ValueError, match="zb1"):
        _pcfg("1f1b", 2, 4, offload_wgrad=True)
    with pytest.raises(ValueError, match="zb1"):
        _pcfg("interleaved_1f1b", 2, 4, v=2, offload_wgrad=True)


def test_offload_activations_rejects_gpipe():
    with pytest.raises(ValueError, match="gpipe"):
        _pcfg("gpipe", 2, 4, offload_activations=True)


def test_offload_config_block_parses():
    from llama_pipeline_parallel_tpu.train import (
        _offload_flags,
        build_manifest,
        build_pipeline_config,
    )

    assert _offload_flags({}) == (False, False)
    assert _offload_flags({"offload": {"wgrad_stash": True}}) == (True, False)
    assert _offload_flags({"offload": {"activations": True}}) == (False, True)
    with pytest.raises(ValueError, match="unknown offload"):
        _offload_flags({"offload": {"wgrad": True}})

    cfg = LlamaConfig.tiny(num_hidden_layers=8)
    raw = {"pipeline_schedule": "zb1", "virtual_stages": 2,
           "gradient_accumulation_steps": 2,
           "offload": {"wgrad_stash": True, "activations": True}}
    pcfg = build_pipeline_config(raw, MeshConfig(pp=2),
                                 build_manifest(raw, cfg, 2))
    assert pcfg.offload_wgrad and pcfg.offload_activations


def test_offload_static_metrics_keys():
    from llama_pipeline_parallel_tpu.train import _offload_static

    off = _pcfg("zb1", 2, 4, v=2)
    assert _offload_static(off, 2, 16, 64, 4) == {}
    on = _pcfg("zb1", 2, 4, v=2, offload_wgrad=True,
               offload_activations=True)
    static = _offload_static(on, 2, 16, 64, 4)
    assert static["offload_stash"] == "wgrad_stash+activations"
    assert static["offload_stash_resident_gib"] == round(
        pl.host_stash_bytes(on, 2, 16, 64, 4) / (1 << 30), 6)
    assert static["offload_stash_resident_gib"] > 0  # KiB resolution: the
    # tiny shapes the trainer e2e logs must not flatten to an all-zero key


# ---------------------------------------------------------------------------
# Trainer e2e + chaos (round gate)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_trainer_offload_end_to_end(tmp_path, devices):
    """run_training with the host stash on: final loss bit-matches the
    no-offload zb1 run, the metrics line + health.json carry the
    offload_stash keys, and a plain run carries neither (no always-zero
    columns)."""
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager
    from llama_pipeline_parallel_tpu.train import run_training

    model_cfg = LlamaConfig.tiny(dtype=jnp.float32)
    man = StageManifest.for_config(model_cfg, 2)
    warm_dir = str(tmp_path / "warm")
    CheckpointManager(warm_dir).save(
        0, pl.stack_stages(llama.init_params(jax.random.PRNGKey(7), model_cfg),
                           man), man, model_cfg)

    def cfg_for(out, **kw):
        base = {
            "output_dir": str(tmp_path / out),
            "mesh": {"pp": 2, "dp": 2},
            "model": {"preset": "tiny", "dtype": "float32"},
            "model_name_or_path": warm_dir,
            "dataset": {"synthetic": True, "seq_length": 16,
                        "pseudo_dataset_len": 128},
            "seed": 7,
            "per_device_train_batch_size": 2,
            "gradient_accumulation_steps": 2,
            "pipeline_schedule": "zb1",
            "virtual_stages": 2,
            "max_steps": 3,
            "learning_rate": 1e-3,
            "warmup_steps": 1,
            "logging_steps": 1,
            "save_steps": 0,
            "save_final": False,
        }
        base.update(kw)
        return base

    plain = run_training(cfg_for("plain"))
    off = run_training(cfg_for("off", offload={"wgrad_stash": True,
                                               "activations": True}))
    assert off["final_loss"] == plain["final_loss"]

    lines = [json.loads(l) for l in
             open(os.path.join(str(tmp_path / "off"), "metrics.jsonl"))]
    assert lines[0]["offload_stash"] == "wgrad_stash+activations"
    assert lines[0]["offload_stash_resident_gib"] > 0
    plain_lines = [json.loads(l) for l in
                   open(os.path.join(str(tmp_path / "plain"), "metrics.jsonl"))]
    assert "offload_stash" not in plain_lines[0]
    health = json.load(open(os.path.join(str(tmp_path / "off"), "health.json")))
    assert health["offload_stash"] == "wgrad_stash+activations"


@pytest.mark.slow
def test_chaos_sigkill_with_host_residuals_resumes_bitexact(tmp_path):
    """The chaos leg: the fault plan SIGKILLs the trainer AT THE STEP SITE
    while the host stash is live (zb1 + offload.wgrad_stash — W residuals
    tier through host DRAM every step), the supervisor restarts it, and the
    resumed run — whose in-flight host residuals died with the process —
    restores the last verified checkpoint and finishes with the final loss
    bit-matching an unfaulted offload run."""
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager
    from llama_pipeline_parallel_tpu.utils import faults

    out = str(tmp_path / "chaos")
    ref = str(tmp_path / "straight")
    env_base = {**os.environ,
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "LPT_RETRY_BASE_DELAY_S": "0.01"}

    def train_cmd(output_dir):
        return [sys.executable, "train.py", "--config", "conf/tiny_smoke.yaml",
                "--platform", "cpu", f"output_dir={output_dir}",
                "pipeline_schedule=zb1", "virtual_stages=2",
                "offload.wgrad_stash=true", "offload.activations=true",
                "max_steps=6", "total_steps=6", "save_steps=2",
                "logging_steps=1", "save_final=true", "attention=exact"]

    plan = {"faults": [{"site": "step", "op": "die", "at_step": 4,
                        "marker": os.path.join(out, "fault.fired")}]}
    sup = subprocess.run(
        [sys.executable, "tools/supervisor.py", "--output-dir", out,
         "--max-restarts", "2", "--hang-timeout-s", "600",
         "--poll-s", "0.2", "--"] + train_cmd(out),
        cwd=_REPO, env={**env_base, faults.ENV_PLAN: json.dumps(plan)},
        capture_output=True, text=True, timeout=540)
    assert sup.returncode == 0, (
        f"supervisor failed:\n{sup.stdout[-3000:]}\n{sup.stderr[-3000:]}")
    assert os.path.exists(os.path.join(out, "fault.fired"))
    ledger = [json.loads(l)
              for l in open(os.path.join(out, "incarnations.jsonl"))]
    assert [r["outcome"] for r in ledger] == ["crash", "clean"]
    mgr = CheckpointManager(out)
    assert mgr.latest_step() == 6
    mgr.verify(6)

    straight = subprocess.run(train_cmd(ref), cwd=_REPO, env=env_base,
                              capture_output=True, text=True, timeout=360)
    assert straight.returncode == 0, straight.stdout[-3000:]

    def losses(d):
        lines = [json.loads(l) for l in open(os.path.join(d, "metrics.jsonl"))]
        return {l["step"]: l["loss"] for l in lines if "loss" in l}

    # bit parity at the final step: resume from checkpoint-2 replayed the
    # exact batch stream, host residuals reconstructed from scratch
    assert losses(out)[6] == losses(ref)[6]
