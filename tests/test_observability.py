"""Observability: FLOPs/MFU accounting, profiler step gating, Tracking
backends (reference §5.1/§5.5: FlopsCounter, step-scoped profiling,
Tracking multiplexer)."""

import os

import jax.numpy as jnp
import pytest

from polyrl_tpu.models import decoder
from polyrl_tpu.utils import flops as flops_lib
from polyrl_tpu.utils.metrics import Tracking


def test_param_count_llama8b_ballpark():
    cfg = decoder.get_config("llama3-8b")
    p = flops_lib.param_count(cfg)
    assert 7.5e9 < p < 8.5e9          # Llama-3.1-8B ≈ 8.03B


def test_flops_per_token_scales_with_context():
    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    short = flops_lib.flops_per_token(cfg, 128)
    long = flops_lib.flops_per_token(cfg, 4096)
    assert long > short               # attention quadratic term
    inf = flops_lib.flops_per_token(cfg, 128, training=False)
    assert short == pytest.approx(3 * inf)


def test_step_metrics_and_mfu():
    cfg = decoder.get_config("llama3-8b")
    fc = flops_lib.FlopsCounter(cfg, peak_tflops=197.0, n_chips=4)
    m = fc.step_metrics(n_tokens=100_000, mean_context_len=1024,
                        step_time_s=10.0)
    assert set(m) == {"perf/tflops_all_chips", "perf/tflops_per_chip",
                      "perf/mfu"}
    assert m["perf/tflops_per_chip"] == pytest.approx(
        m["perf/tflops_all_chips"] / 4)
    assert 0 < m["perf/mfu"] < 1
    assert fc.step_metrics(0, 0, 0.0) == {}


def test_peak_comes_from_the_device_kind_table(monkeypatch):
    """One table keyed by device_kind; an unknown kind (CPU included) has
    no peak and emits no mfu key; POLYRL_PEAK_TFLOPS is not read."""
    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    assert flops_lib.peak_tflops("TPU v5 lite") == 197.0
    assert flops_lib.peak_tflops("cpu") is None
    assert flops_lib.peak_tflops("TPU v99") is None
    monkeypatch.setenv("POLYRL_PEAK_TFLOPS", "918")
    fc = flops_lib.FlopsCounter(cfg, peak_tflops=flops_lib.peak_tflops("cpu"))
    assert fc.peak_tflops is None
    m = fc.step_metrics(n_tokens=1000, mean_context_len=64, step_time_s=1.0)
    assert set(m) == {"perf/tflops_all_chips", "perf/tflops_per_chip"}


def test_profiler_step_gating(tmp_path):
    """Trainer traces exactly the configured steps (one trace dir appears)."""
    import jax

    from tests.test_checkpoint import _make_trainer

    trainer = _make_trainer(tmp_path / "ck", total_steps=2)
    trainer.cfg.profile_steps = (2,)
    trainer.cfg.profile_dir = str(tmp_path / "prof")
    trainer.fit()
    assert not trainer._tracing
    # jax profiler writes plugins/profile/<run> under the log dir
    found = []
    for root, _dirs, files in os.walk(tmp_path / "prof"):
        found += [f for f in files if f.endswith((".xplane.pb", ".trace.json.gz"))]
    assert found, "no profiler artifacts written"


def test_tracking_wandb_gated(tmp_path):
    # wandb is not installed in this image: backend degrades to no-op
    t = Tracking(backends=("jsonl", "wandb"), path=str(tmp_path / "m.jsonl"))
    assert t._wandb is None
    t.log({"a": 1.0}, step=1)
    t.close()
    assert (tmp_path / "m.jsonl").read_text().strip()


def test_moe_param_count_and_active_flops():
    """MoE configs: param_count covers router + ALL experts; per-token
    FLOPs cover only the routed top-k (MFU would otherwise be ~10x off on
    e.g. Qwen3-30B-A3B, which activates ~3B of 30B params)."""
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("qwen3-30b-a3b")
    total = flops_lib.param_count(cfg)
    assert 29e9 < total < 32e9, total  # "30B" family

    dense_equiv = flops_lib.flops_per_token(cfg, 1, training=False)
    # active matmul params ≈ 3B ("A3B"): fwd ≈ 2 * active
    active = dense_equiv / 2.0
    assert 2e9 < active < 4e9, active


def test_server_metrics_endpoint():
    """GET /metrics: Prometheus text exposition of serving telemetry."""
    import urllib.request

    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.server import RolloutServer

    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    engine = CBEngine(cfg, params, pad_token_id=0,
                      kv_cache_dtype=jnp.float32, max_slots=4, page_size=8,
                      max_seq_len=64, prompt_buckets=(8,), num_pages=64)
    server = RolloutServer(engine, host="127.0.0.1", port=0).start()
    try:
        with urllib.request.urlopen(
                f"http://{server.endpoint}/metrics", timeout=30) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "# TYPE polyrl_num_running_reqs gauge" in body, body
        assert "polyrl_weight_version" in body
    finally:
        server.stop()
