"""Weight-fabric fault tolerance (ARCHITECTURE.md "Weight-fabric fault
tolerance"): verified pushes (frame CRC trailers + control-channel
manifest verify), same-version partial re-pushes off the coverage ledger,
bandwidth-keyed deadlines with a jittered retry budget, laggard
escalation into the pool control plane, and the 2-fake-engine chaos fit
drill (corruption + control-channel kill + a stalled receiver)."""

import threading
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.rollout.faults import (TransferFaultConfig,
                                       TransferFaultInjector)
from polyrl_tpu.transfer import (
    ReceiverAgent,
    SenderAgent,
    TransferConfig,
    TransferInterface,
    build_layout,
    pack_params,
    unflatten_like,
    unpack_params,
)
from polyrl_tpu.transfer import tcp_engine as te
from polyrl_tpu.transfer.layout import alloc_buffer
from polyrl_tpu.transfer.tcp_engine import ReceiverSockets, Watermark


def small_params(seed=0):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 4)
    return {
        "embed": {"w": jax.random.normal(ks[0], (17, 8), jnp.float32)},
        "layers": {
            "0": {"wq": jax.random.normal(ks[1], (8, 8), jnp.bfloat16),
                  "wk": jax.random.normal(ks[2], (8, 4), jnp.bfloat16)},
        },
        "norm": jax.random.normal(ks[3], (8,), jnp.float32),
    }


def assert_tree_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def wait_for(cond, timeout=5.0, msg="condition"):
    """Poll a predicate: the receiver installs the instant IT verifies, so
    sender-side bookkeeping (the verify_result round-trip) may land a beat
    later than wait_for_version returns."""
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.02)


def fast_cfg(**kw):
    """Test-speed supervision knobs: tight bandwidth-keyed deadlines and a
    snappy backoff so fault drills resolve in hundreds of ms."""
    defaults = dict(min_bandwidth_mbps=1000.0, deadline_slack_s=2.0,
                    stream_slack_s=2.0, retry_budget=2,
                    backoff_base_s=0.05, backoff_max_s=0.2,
                    prepare_timeout_s=10.0)
    defaults.update(kw)
    return TransferConfig(**defaults)


def mk_pair(params, cfg=None, fault=None, num_streams=2,
            instance="inst-ft"):
    layout = build_layout(params)
    buf = alloc_buffer(layout)
    sender = SenderAgent(buf, manager_client=None, listen_host="127.0.0.1",
                         num_streams=num_streams, poll_s=0.05,
                         advertise_host="127.0.0.1",
                         cfg=cfg or fast_cfg(), fault=fault)
    sender.start()
    rx = ReceiverAgent(layout, instance, sender.endpoint,
                       num_streams=num_streams, listen_host="127.0.0.1",
                       advertise_host="127.0.0.1")
    rx.start()
    return layout, buf, sender, rx


# -- integrity: frame CRC + manifest verify + partial resume -----------------


def test_frame_corruption_detected_and_resumed(monkeypatch):
    """A corrupted wire frame is rejected by its CRC trailer, the round is
    NOT installed, the receiver answers verify_failed with the failed
    range, and the sender re-pushes ONLY that range (resumed_bytes <
    total) — the landed buffer ends bitwise-equal to the source."""
    monkeypatch.setattr(te, "STREAM_STRIPE", 4096)
    params = small_params(11)
    injector = TransferFaultInjector(TransferFaultConfig(
        enabled=True, corrupt_frames=1))
    layout, buf, sender, rx = mk_pair(params, fault=injector)
    try:
        time.sleep(0.3)  # registration
        with sender.buffer_write_lock():
            pack_params(params, layout, buf)
        v = sender.signal_update()
        got = rx.wait_for_version(v, timeout=30.0)
        assert got == v
        wait_for(lambda: sender.rounds_verified >= 1,
                 msg="sender round bookkeeping")
        assert injector.corruptions == 1
        assert rx.sockets.crc_failures == 1
        # rejected once, repaired via a PARTIAL re-push
        assert sender.verify_failures == 1
        assert rx.verify_failures == 1
        assert 0 < sender.resumed_bytes < layout.total_bytes
        assert rx.resumed_bytes == sender.resumed_bytes
        assert sender.rounds_verified == 1
        assert_tree_equal(params,
                          unflatten_like(params,
                                         unpack_params(rx.buffer, layout)))
        # counters surface for server_info / step records
        health = rx.health()
        assert health["transfer_crc_frame_failures"] == 1
        assert health["transfer_resumed_bytes"] > 0
        assert sender.counters()["transfer/verify_failures"] == 1.0
    finally:
        rx.stop()
        sender.stop()


def test_corrupted_rounds_never_install_version():
    """Persistent corruption: every attempt fails verify, so the version
    gate holds (receiver.version never advances), the retry budget
    exhausts, and the laggard callback fires."""
    params = small_params(12)
    injector = TransferFaultInjector(TransferFaultConfig(
        enabled=True, corrupt_frames=10_000))
    escalations = []
    cfg = fast_cfg(retry_budget=1)
    layout, buf, sender, rx = mk_pair(params, cfg=cfg, fault=injector)
    sender.laggard_cb = lambda inst, reason: escalations.append(
        (inst, reason))
    try:
        time.sleep(0.3)
        with sender.buffer_write_lock():
            pack_params(params, layout, buf)
        v = sender.signal_update()
        with pytest.raises(TimeoutError):
            rx.wait_for_version(v, timeout=3.0)
        assert rx.version == -1  # the corrupted rounds never installed
        deadline = time.monotonic() + 5.0
        while not escalations and time.monotonic() < deadline:
            time.sleep(0.05)
        assert escalations and escalations[0][0] == "inst-ft"
        assert sender.laggard_escalations == 1
        assert sender.verify_failures >= 2  # full push + resume, both bad
        assert sender.sync_health()["inst-ft"]["escalated"] is True
        # escalated at this version: the poll loop must stop re-pushing
        failures = sender.push_failures
        time.sleep(0.4)  # several poll_s ticks
        assert sender.push_failures == failures
    finally:
        rx.stop()
        sender.stop()


def test_control_channel_kill_mid_round_recovers():
    """Control-plane death right before the verify handshake: the attempt
    fails as a transport error, the receiver reconnects (capped+jittered
    backoff, counted), and the retry re-pushes the round to a verified
    bitwise-exact install."""
    params = small_params(13)
    injector = TransferFaultInjector(TransferFaultConfig(
        enabled=True, kill_control_rounds=1))
    layout, buf, sender, rx = mk_pair(params, fault=injector)
    try:
        time.sleep(0.3)
        with sender.buffer_write_lock():
            pack_params(params, layout, buf)
        v = sender.signal_update()
        assert rx.wait_for_version(v, timeout=30.0) == v
        wait_for(lambda: sender.rounds_verified >= 1,
                 msg="sender round bookkeeping")
        assert injector.control_kills == 1
        assert rx.control_reconnects >= 1
        assert sender.push_retries >= 1
        assert sender.rounds_verified == 1
        assert_tree_equal(params,
                          unflatten_like(params,
                                         unpack_params(rx.buffer, layout)))
    finally:
        rx.stop()
        sender.stop()


def test_stalled_receiver_escalates_after_budget():
    """A stream stalled past the bandwidth-keyed deadline fails each
    attempt by timeout; past the retry budget the instance is escalated
    to the laggard callback and blocklisted at this version — no more
    re-pushes every poll_s."""
    params = small_params(14)
    injector = TransferFaultInjector(TransferFaultConfig(
        enabled=True, stall_s=1.5, stall_streams=-1))
    escalated = threading.Event()
    calls = []

    def cb(inst, reason):
        calls.append((inst, reason))
        escalated.set()

    cfg = fast_cfg(deadline_slack_s=0.4, stream_slack_s=0.4,
                   retry_budget=1)
    layout, buf, sender, rx = mk_pair(params, cfg=cfg, fault=injector)
    sender.laggard_cb = cb
    try:
        time.sleep(0.3)
        with sender.buffer_write_lock():
            pack_params(params, layout, buf)
        sender.signal_update()
        assert escalated.wait(timeout=10.0)
        assert calls[0][0] == "inst-ft"
        assert injector.stalls >= 2          # every attempt stalled
        assert sender.push_failures == 2     # 1 + retry_budget attempts
        assert sender.laggard_escalations == 1
        assert rx.version == -1
        health = sender.sync_health()["inst-ft"]
        assert health["escalated"] and health["push_failures"] == 2
    finally:
        rx.stop()
        sender.stop()


def test_repush_after_escalation_cleared_by_new_registration():
    """A fresh registration clears the laggard blocklist: an operator
    restarting the receiver gets a fresh retry budget and catches up."""
    params = small_params(15)
    injector = TransferFaultInjector(TransferFaultConfig(
        enabled=True, stall_s=1.5, stall_streams=2))
    cfg = fast_cfg(deadline_slack_s=0.4, stream_slack_s=0.4,
                   retry_budget=1)
    layout, buf, sender, rx = mk_pair(params, cfg=cfg, fault=injector)
    try:
        time.sleep(0.3)
        with sender.buffer_write_lock():
            pack_params(params, layout, buf)
        v = sender.signal_update()
        deadline = time.monotonic() + 10.0
        while sender.laggard_escalations == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sender.laggard_escalations == 1
        # "restart" the receiver: stop + fresh agent -> fresh registration
        rx.stop()
        rx = ReceiverAgent(layout, "inst-ft", sender.endpoint,
                           num_streams=2, listen_host="127.0.0.1",
                           advertise_host="127.0.0.1")
        rx.start()
        # stall budget (2) is spent: the catch-up push lands clean
        assert rx.wait_for_version(v, timeout=30.0) == v
        wait_for(lambda: sender.rounds_verified >= 1,
                 msg="sender round bookkeeping")
        assert_tree_equal(params,
                          unflatten_like(params,
                                         unpack_params(rx.buffer, layout)))
    finally:
        rx.stop()
        sender.stop()


# -- watermark + coverage-ledger units (resume building blocks) --------------


def test_watermark_fail_and_timeout_paths():
    wm = Watermark(100)
    wm.advance(10)
    with pytest.raises(TimeoutError, match="stalled at 10/50"):
        wm.wait_until(50, timeout=0.05)
    wm.fail("pack exploded")
    with pytest.raises(ConnectionError, match="pack exploded"):
        wm.wait_until(50, timeout=5.0)
    # fail() beats a satisfied target too: waiters must observe the death
    wm2 = Watermark(100)
    wm2.fail("dead")
    with pytest.raises(ConnectionError):
        wm2.wait_until(1, timeout=5.0)
    # finish() satisfies any target on a healthy mark
    wm3 = Watermark(100)
    wm3.finish()
    wm3.wait_until(100, timeout=1.0)


def test_receiver_sockets_gap_and_digest_detection():
    buf = np.arange(1000, dtype=np.uint8)
    rs = ReceiverSockets(buf, num_streams=1, host="127.0.0.1")
    try:
        rs.arm(1)
        with rs._lock:
            rs._progress = {0: 100, 300: 150, 450: 50, 600: 400}
        # holes: [100,300) and [500,600)
        assert rs.gaps(1000) == [(100, 200), (500, 100)]
        good_crc = zlib.crc32(bytes(buf[0:100]))
        manifest = [
            (0, 100, good_crc),             # landed + digest ok
            (0, 100, good_crc ^ 1),         # landed, digest MISMATCH
            (100, 200, 0),                  # not landed at all
            (300, 250, zlib.crc32(bytes(buf[300:550]))),  # spans a hole
            (600, 400, zlib.crc32(bytes(buf[600:1000]))),  # merged ranges
        ]
        assert rs.verify_ranges(manifest) == [(0, 100), (100, 200),
                                              (300, 250)]
        # full coverage + clean digests -> nothing missing
        with rs._lock:
            rs._progress = {0: 1000}
        assert rs.gaps(1000) == []
        assert rs.verify_ranges([(0, 1000, zlib.crc32(bytes(buf)))]) == []
        # resume arming keeps coverage, clears only the re-pushed ranges
        rs.arm(2, reset=False, clear=[(0, 1000)])
        assert rs.gaps(1000) == [(0, 1000)]
        assert rs.resume_round
    finally:
        rs.close()


def test_reconnect_backoff_caps_and_jitters():
    """A dead sender endpoint must be retried at a bounded, jittered rate
    — not hammered bare at a fixed 0.2 s forever."""
    import socket as socketlib

    probe = socketlib.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()  # nothing listens here now
    layout = build_layout(small_params(16))
    rx = ReceiverAgent(layout, "inst-dead", f"127.0.0.1:{port}",
                       num_streams=1, listen_host="127.0.0.1",
                       advertise_host="127.0.0.1")
    rx.start()
    try:
        time.sleep(1.2)
        # geometric backoff from 0.2s with +-50% jitter: a handful of
        # attempts, never a tight loop, never silence
        assert 2 <= rx.control_reconnects <= 12
    finally:
        rx.stop()


def test_teardown_mid_push_releases_threads():
    """Interface close during a stalled push must return promptly: the
    injector stall is interrupted, executors shut down with
    cancel_futures, accept/event threads join (the conftest thread-leak
    guard is the second assert here)."""
    params = small_params(17)
    injector = TransferFaultInjector(TransferFaultConfig(
        enabled=True, stall_s=30.0, stall_streams=-1))
    iface = TransferInterface(params, manager_client=None, num_streams=2,
                              poll_s=0.05, advertise_host="127.0.0.1",
                              cfg=fast_cfg(retry_budget=5,
                                           backoff_max_s=5.0),
                              fault=injector)
    rx = ReceiverAgent(iface.layout, "inst-teardown",
                       iface.sender.endpoint, num_streams=2,
                       listen_host="127.0.0.1", advertise_host="127.0.0.1")
    rx.start()
    try:
        time.sleep(0.3)
        iface.update_weights_with_agent(params, streaming=False)
        time.sleep(0.4)  # the push round is now stalled mid-wire
        t0 = time.monotonic()
        iface.close()
        assert time.monotonic() - t0 < 8.0
    finally:
        rx.stop()


def test_transfer_config_section_overrides():
    from polyrl_tpu.config import load_config, to_dict

    cfg = load_config(overrides=[
        "transfer.min_bandwidth_mbps=12.5",
        "transfer.retry_budget=7",
        "transfer.verify=false",
        "transfer.fault_injection.enabled=true",
        "transfer.fault_injection.stall_s=0.5",
    ])
    assert cfg.transfer.min_bandwidth_mbps == 12.5
    assert cfg.transfer.retry_budget == 7
    assert cfg.transfer.verify is False
    assert cfg.transfer.fault_injection.enabled is True
    assert cfg.transfer.fault_injection.stall_s == 0.5
    d = to_dict(cfg)["transfer"]
    assert d["push_timeout_s"] == 600.0
    assert d["stream_push_timeout_s"] == 3600.0
    # bandwidth-keyed deadline math: bytes/bw + slack, capped by the old
    # flat timeout
    assert cfg.transfer.push_deadline_s(125 * 1e6, streamed=False) == \
        pytest.approx(10.0 + 30.0)
    assert cfg.transfer.push_deadline_s(10**12, streamed=True) == 3600.0


def test_trusting_path_still_installs_without_verify():
    """transfer.verify=false keeps the legacy transfer_done protocol."""
    params = small_params(18)
    layout, buf, sender, rx = mk_pair(params, cfg=fast_cfg(verify=False))
    try:
        time.sleep(0.3)
        with sender.buffer_write_lock():
            pack_params(params, layout, buf)
        v = sender.signal_update()
        assert rx.wait_for_version(v, timeout=30.0) == v
        wait_for(lambda: sender.rounds_verified >= 1,
                 msg="sender round bookkeeping")
        assert sender.rounds_verified == 1  # completion still counted
        assert rx.rounds_verified == 0      # no manifest handshake ran
        assert_tree_equal(params,
                          unflatten_like(params,
                                         unpack_params(rx.buffer, layout)))
    finally:
        rx.stop()
        sender.stop()


# -- acceptance: repaired push ≡ clean push on a real engine -----------------


def test_repaired_push_greedy_parity(monkeypatch):
    """Acceptance: a same-version partial re-push (post-verify_failed)
    transfers only the failed ranges, and greedy rollout outputs after the
    repaired push are IDENTICAL to a clean-push baseline — corrupt wire
    bytes can never leak into the installed tree."""
    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.cb_engine import STREAM_END, CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams
    from polyrl_tpu.rollout.server import RolloutServer

    monkeypatch.setattr(te, "STREAM_STRIPE", 16 * 1024)
    cfg = decoder.get_config("tiny")
    params1 = decoder.init_params(jax.random.PRNGKey(0), cfg)
    params2 = decoder.init_params(jax.random.PRNGKey(1), cfg)
    eng = CBEngine(cfg, params1, max_slots=4, page_size=8, max_seq_len=64,
                   prompt_buckets=(16,), num_pages=64)
    server = RolloutServer(eng, host="127.0.0.1", port=0)
    server.start()
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size, 12).tolist()
    sp = SamplingParams(temperature=0.0, max_new_tokens=8,
                        stop_token_ids=())

    def greedy(rid):
        q, abort = server.submit(rid, prompt, sp)
        toks, lps = [], []
        while True:
            item = q.get(timeout=120)
            if item is STREAM_END:
                break
            toks.extend(item["token_ids"])
            lps.extend(item["logprobs"])
        server._drop_abort(rid, abort)
        return toks, lps

    injector = TransferFaultInjector(TransferFaultConfig(
        enabled=True, corrupt_frames=1))
    iface = TransferInterface(params2, manager_client=None, num_streams=2,
                              poll_s=0.05, advertise_host="127.0.0.1",
                              cfg=fast_cfg(), fault=injector)
    rx = ReceiverAgent(iface.layout, server.endpoint,
                       iface.sender.endpoint, num_streams=2,
                       listen_host="127.0.0.1", advertise_host="127.0.0.1")
    server.receiver = rx
    rx.start()
    try:
        # clean-push baseline: params2 installed in-process
        eng.update_weights(params2, version=1)
        base_toks, base_lps = greedy("baseline")
        # back to params1, then repair-push params2 over the fabric
        eng.update_weights(params1, version=2)
        time.sleep(0.3)  # receiver registration
        v = iface.update_weights_with_agent(params2, streaming=True)
        ok, err = server.update_weights_from_agent(v)
        assert ok, err
        wait_for(lambda: iface.sender.rounds_verified >= 1,
                 msg="sender round bookkeeping")
        # the round WAS corrupted and WAS repaired partially
        assert injector.corruptions == 1
        assert rx.sockets.crc_failures == 1
        assert iface.sender.verify_failures >= 1
        assert 0 < iface.sender.resumed_bytes < iface.layout.total_bytes
        counters = iface.counters()
        assert counters["transfer/verify_failures"] >= 1.0
        assert counters["fault/transfer_corruptions"] == 1.0
        # identical greedy rollout: tokens AND logprobs bitwise
        got_toks, got_lps = greedy("repaired")
        assert got_toks == base_toks
        np.testing.assert_array_equal(np.asarray(got_lps),
                                      np.asarray(base_lps))
    finally:
        rx.stop()
        server.stop()
        iface.close()


# -- acceptance: 2-fake-engine chaos fit -------------------------------------


def test_push_chaos_fit_two_fake_engines(monkeypatch):
    """Acceptance drill: a fit over 2 fake engines with (a) injected frame
    corruption on one stream to engine A, (b) a mid-round control-channel
    kill to engine A, and (c) engine B's streams stalled past their
    bandwidth-keyed deadline from v2 on. The surviving engine's landed
    buffer must be bitwise-equal to the packed source, corrupted rounds
    must never install (version gate), the stalled engine must be
    drained + deregistered after its retry budget (laggard escalation),
    and training must complete with 0 dropped groups."""
    from polyrl_tpu.data.dataset import (PromptDataLoader,
                                         make_arithmetic_dataset)
    from polyrl_tpu.manager.client import (ManagerClient,
                                           spawn_rollout_manager)
    from polyrl_tpu.models import decoder
    from polyrl_tpu.rewards.manager import load_reward_manager
    from polyrl_tpu.rollout.pool import PoolConfig, PoolManager
    from polyrl_tpu.rollout.remote import RemoteRollout
    from polyrl_tpu.trainer.actor import ActorConfig, StreamActor
    from polyrl_tpu.trainer.stream_trainer import (StreamRLTrainer,
                                                   TrainerConfig)
    from polyrl_tpu.utils.tokenizer import ByteTokenizer
    from tests.fake_engine import FakeEngine

    monkeypatch.setattr(te, "STREAM_STRIPE", 16 * 1024)
    proc, port = spawn_rollout_manager(
        "127.0.0.1:0",
        extra_args=["--health-check-interval-s", "0.1",
                    "--stats-poll-interval-s", "0.1",
                    "--heartbeat-failures", "3",
                    "--generate-timeout-ms", "15000",
                    "--schedule-wait-timeout-ms", "10000"])
    mgr = ManagerClient(f"127.0.0.1:{port}")
    eng_a = FakeEngine(start_token=30, token_delay_s=0.005).start()
    eng_b = FakeEngine(start_token=30, token_delay_s=0.005).start()
    pool = PoolManager(mgr, PoolConfig(drain_grace_s=0.1))
    iface = None
    rxs = []
    try:
        mgr.wait_healthy()
        tok = ByteTokenizer()
        cfg = decoder.get_config("tiny", dtype=jnp.float32)
        params = decoder.init_params(jax.random.PRNGKey(0), cfg)
        injector = TransferFaultInjector(TransferFaultConfig(
            enabled=True,
            # (a) one corrupt frame to A, armed after its clean catch-up
            corrupt_frames=1, corrupt_instance=eng_a.endpoint,
            corrupt_after_attempts=1,
            # (b) one control-channel kill to A, later (post-repair)
            kill_control_rounds=1, kill_control_instance=eng_a.endpoint,
            kill_control_after_attempts=3,
            # (c) B stalls past its deadline on every attempt from v2 on
            stall_s=5.0, stall_streams=-1,
            stall_instance=eng_b.endpoint, stall_after_attempts=1))
        iface = TransferInterface(
            params, manager_client=mgr, num_streams=2, poll_s=0.1,
            advertise_host="127.0.0.1",
            cfg=fast_cfg(retry_budget=1), fault=injector)
        iface.set_laggard_callback(pool.escalate_laggard)
        pool.transfer_health_fn = iface.sync_health
        for eng in (eng_a, eng_b):
            out = mgr.register_rollout_instance(eng.endpoint)
            assert out["weight_sender_endpoint"] == iface.sender.endpoint
            rx = ReceiverAgent(iface.layout, eng.endpoint,
                               iface.sender.endpoint, num_streams=2,
                               listen_host="127.0.0.1",
                               advertise_host="127.0.0.1")
            rx.start()
            rxs.append(rx)
        rx_a, rx_b = rxs
        # with a weight sender registered, the bootstrap gate holds both
        # engines OUT of routing until their first push lands — wait for
        # healthy only; the fit's initial _push_weights activates them
        for eng in (eng_a, eng_b):
            pool.wait_for_member(eng.endpoint, active=False)

        remote = RemoteRollout(mgr, transfer=iface,
                               pad_token_id=tok.pad_token_id,
                               resume_budget=3, resume_wait_s=10.0,
                               pool=pool)
        tcfg = TrainerConfig(
            train_batch_size=4, rollout_n=2, ppo_mini_batch_size=8,
            micro_batch_size=4, min_stream_batch_size=4,
            max_prompt_length=16, max_response_length=8,
            adv_estimator="grpo", total_steps=4, temperature=1.0)
        actor = StreamActor(cfg, ActorConfig(lr=1e-4, remat=False), params)
        trainer = StreamRLTrainer(
            tcfg, actor, remote, tok,
            load_reward_manager("naive", tok, num_workers=1),
            PromptDataLoader(make_arithmetic_dataset(32), 4))
        history = trainer.fit()

        # training survived the whole drill: no data was lost
        assert len(history) == 4
        assert remote.dropped_groups == 0
        # every injected fault fired
        assert injector.corruptions == 1
        assert injector.control_kills == 1
        assert injector.stalls >= 2
        # (a) corruption: rejected by CRC + verify, repaired PARTIALLY
        assert rx_a.sockets.crc_failures >= 1
        assert iface.sender.verify_failures >= 1
        assert 0 < iface.sender.resumed_bytes < iface.layout.total_bytes
        # (b) control kill: A's receiver reconnected and the retry landed
        assert rx_a.control_reconnects >= 1
        # (c) the stalled engine was escalated: drained + deregistered
        assert iface.sender.laggard_escalations == 1
        assert pool.laggards == 1
        wait_for(lambda: eng_b.draining.is_set(), timeout=10.0,
                 msg="laggard drain")
        wait_for(lambda: pool.counters()["pool/active"] <= 1.0,
                 timeout=10.0, msg="laggard leaving the routing set")
        assert pool.counters(refresh=False)["pool/laggard_escalations"] \
            == 1.0
        # the version gate held: B never installed anything past v1
        assert rx_b.version <= 1
        # the SURVIVOR's landed buffer is bitwise-equal to the packed
        # source at the final version
        final_v = iface.sender.version
        rx_a.wait_for_version(final_v, timeout=30.0)
        assert np.array_equal(rx_a.buffer, iface.sender.buffer)
        # supervision telemetry rode the step records...
        last = history[-1]
        assert last["transfer/push_failures"] >= 2.0
        assert last["transfer/verify_failures"] >= 1.0
        assert last["fault/transfer_stalls"] >= 2.0
        assert last["transfer/retry_budget"] == 1.0
        # ...and the per-engine sync health rides the /statusz pool section
        # (the receiver can hold the version a moment before the sender's
        # thread has booked the push: on a loaded box the snapshot read
        # 4 for 5)
        def pushed():
            snap = trainer.statusz_snapshot()
            rows = {r["endpoint"]: r for r in snap["pool"]["engines"]}
            return rows[eng_a.endpoint]["transfer"]["pushed_version"]

        wait_for(lambda: pushed() == final_v, timeout=10.0,
                 msg="the survivor's push booked")
        health = iface.sync_health()
        assert health[eng_b.endpoint]["escalated"] is True
    finally:
        proc.kill()
        pool.close()
        for rx in rxs:
            rx.stop()
        if iface is not None:
            iface.close()
        eng_a.stop()
        eng_b.stop()
