"""Synthetic traffic generator (tools/serve_traffic.py): deterministic
Poisson traces with prompt/output length mixes, replayed against a live
paged engine — the load source behind bench.py's `extra:serve-prefill-*`
row."""

import jax
import numpy as np
import pytest

import serve_traffic  # tools/ on sys.path via conftest
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.serve import ServeConfig, ServeEngine


def test_parse_mix_normalizes_and_validates():
    mix = serve_traffic.parse_mix("64:3,256:1")
    assert mix == ((64, 0.75), (256, 0.25))
    assert serve_traffic.parse_mix("64") == ((64, 1.0),)
    assert serve_traffic.mix_label(mix) == "64:0.75,256:0.25"
    with pytest.raises(ValueError):
        serve_traffic.parse_mix("")
    with pytest.raises(ValueError):
        serve_traffic.parse_mix("64:0,128:0")     # zero total weight
    with pytest.raises(ValueError):
        serve_traffic.parse_mix("0:1")            # lengths must be >= 1


def test_poisson_trace_deterministic_and_mixed():
    prompt_mix = serve_traffic.parse_mix("8:0.5,16:0.5")
    output_mix = serve_traffic.parse_mix("4:1")
    a = serve_traffic.poisson_trace(7, 10.0, 200, prompt_mix, output_mix)
    b = serve_traffic.poisson_trace(7, 10.0, 200, prompt_mix, output_mix)
    assert a == b                                   # seeded: bit-identical
    c = serve_traffic.poisson_trace(8, 10.0, 200, prompt_mix, output_mix)
    assert a != c
    assert a[0].arrival_s == 0.0                    # trace starts at t=0
    arrivals = [t.arrival_s for t in a]
    assert arrivals == sorted(arrivals)
    # exponential gaps at 10 rps: mean gap ~0.1s (loose statistical sanity)
    gaps = np.diff(arrivals)
    assert 0.05 < float(np.mean(gaps)) < 0.2
    assert {t.prompt_len for t in a} == {8, 16}     # both mix arms drawn
    assert {t.max_new_tokens for t in a} == {4}
    assert len({t.seed for t in a}) > 190           # per-request seeds vary
    with pytest.raises(ValueError):
        serve_traffic.poisson_trace(0, 0.0, 10, prompt_mix, output_mix)
    with pytest.raises(ValueError):
        serve_traffic.poisson_trace(0, 1.0, 0, prompt_mix, output_mix)


def test_run_trace_against_chunked_paged_engine():
    """Replay a short high-rate trace against the chunked paged engine
    shape (shared with tests/test_paged_serving.py): every request either
    completes or is counted as shed load, and the summary carries the SLO
    percentiles + prefill-chunk gauges bench records as row metadata."""
    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=2, max_len=48, prompt_buckets=(8, 32), page_size=4,
        kv_cache="paged", num_pages=24, prefill_chunk_tokens=8,
        max_queue=32, metrics_every=1, decode_span_every=1))
    trace_reqs = serve_traffic.poisson_trace(
        0, 200.0, 8, serve_traffic.parse_mix("8:0.5,24:0.5"),
        serve_traffic.parse_mix("4:0.5,8:0.5"))
    summary = serve_traffic.run_trace(engine, trace_reqs, time_scale=0.05)
    engine.shutdown()
    shed = (summary["refused_pages"] + summary["refused_overload"]
            + summary["rejected_shape"])
    assert summary["requests"] == 8
    assert summary["submitted"] + shed == 8
    assert summary["requests_completed"] == summary["submitted"]
    assert summary["tokens_generated"] >= 4 * summary["submitted"] > 0
    assert "ttft_p50_ms" in summary
    assert summary["prefill_chunks_total"] >= summary["submitted"]
    # a prompt of 8 is one whole bucket; one of 24 in the bucket of 32 is
    # four chunks of 8, the first of them nothing but pads and never run
    long = sum(1 for tr in trace_reqs if tr.prompt_len == 24)
    assert summary["prefill_chunks_skipped_total"] <= long
    if not shed:
        assert summary["prefill_chunks_skipped_total"] == long
        assert (summary["prefill_chunks_total"]
                + summary["prefill_chunks_skipped_total"]
                == (8 - long) + 4 * long)
    assert summary["pages_total"] == 24


def test_parse_tenant_mix_normalizes_and_validates():
    mix = serve_traffic.parse_tenant_mix("free:4,paid:1")
    assert mix == (("free", 0.8), ("paid", 0.2))
    assert serve_traffic.parse_tenant_mix("paid") == (("paid", 1.0),)
    assert serve_traffic.tenant_mix_label(mix) == "free:0.8,paid:0.2"
    with pytest.raises(ValueError):
        serve_traffic.parse_tenant_mix("")
    with pytest.raises(ValueError):
        serve_traffic.parse_tenant_mix(":1")          # empty tenant name
    with pytest.raises(ValueError):
        serve_traffic.parse_tenant_mix("free:0,paid:0")  # zero total weight


def test_poisson_trace_tenants_deterministic_and_legacy_identical():
    prompt_mix = serve_traffic.parse_mix("8:0.5,16:0.5")
    output_mix = serve_traffic.parse_mix("4:1")
    tenant_mix = serve_traffic.parse_tenant_mix("free:0.8,paid:0.2")
    a = serve_traffic.poisson_trace(7, 10.0, 200, prompt_mix, output_mix,
                                    tenant_mix=tenant_mix)
    b = serve_traffic.poisson_trace(7, 10.0, 200, prompt_mix, output_mix,
                                    tenant_mix=tenant_mix)
    assert a == b                                   # seeded: bit-identical
    tenants = [t.tenant for t in a]
    assert set(tenants) == {"free", "paid"}         # both arms drawn
    assert 100 < tenants.count("free") < 200        # roughly the 0.8 weight

    # the tenant draw happens AFTER the per-request length/seed draws, so
    # a tenantless trace is bit-identical to one generated before tenants
    # existed — stamping tenants changes ONLY the tenant field
    legacy = serve_traffic.poisson_trace(7, 10.0, 200, prompt_mix,
                                         output_mix)
    assert all(t.tenant is None for t in legacy)
    assert [(t.arrival_s, t.prompt_len, t.max_new_tokens, t.seed)
            for t in legacy] == \
        [(t.arrival_s, t.prompt_len, t.max_new_tokens, t.seed) for t in a]
