"""C++ rollout-manager protocol tests against fake engines (SURVEY.md §4:
'a ~100-line fake SGLang suffices to test scheduling, eviction+continuation,
time-slicing, and weight-version orchestration without GPUs/TPUs')."""

import time

import pytest

from polyrl_tpu.manager.client import (GenerateProgress, GenerateResult,
                                       ManagerClient, spawn_rollout_manager)
from tests.fake_engine import FakeEngine


def _finals(stream):
    """Terminal results only (the batch stream also carries token-level
    GenerateProgress lines since the salvage protocol upgrade)."""
    return [r for r in stream if isinstance(r, GenerateResult)]


@pytest.fixture()
def manager():
    proc, port = spawn_rollout_manager(
        "127.0.0.1:0",
        extra_args=["--health-check-interval-s", "0.1",
                    "--stats-poll-interval-s", "0.2",
                    "--generate-timeout-ms", "10000"])
    client = ManagerClient(f"127.0.0.1:{port}")
    client.wait_healthy()
    yield client
    proc.kill()


def wait_active(client, n, deadline=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline:
        st = client.get_instances_status()
        healthy = [i for i in st["instances"] if i["healthy"]]
        if len(healthy) >= n:
            return st
        time.sleep(0.1)
    raise TimeoutError(f"never saw {n} healthy instances: {client.get_instances_status()}")


def test_health(manager):
    assert manager.health()


def test_register_and_generate(manager):
    eng = FakeEngine().start()
    try:
        manager.register_rollout_instance(eng.endpoint)
        wait_active(manager, 1)
        res = manager.generate("r1", [1, 2, 3], {"max_new_tokens": 4})
        assert res.success
        # fake engine emits start + len(input) + i
        assert res.output_token_ids == [1003, 1004, 1005, 1006]
        assert res.output_token_logprobs == [-0.5] * 4
        assert res.finish_reason == "length"
    finally:
        eng.stop()


def test_eviction_and_continuation(manager):
    """Instance dies after 2 tokens → manager evicts it and resumes the
    request token-exactly on the healthy instance."""
    dying = FakeEngine(die_after_tokens=2, start_token=1000).start()
    healthy = FakeEngine(start_token=1000).start()
    try:
        manager.register_rollout_instance(dying.endpoint)
        wait_active(manager, 1)
        # occupy: send the request while only the dying engine is registered
        manager.register_rollout_instance(healthy.endpoint)
        wait_active(manager, 2)
        res = None
        # retry until the dying instance is the one picked first
        for _ in range(6):
            res = manager.generate("r2", [5, 6], {"max_new_tokens": 6})
            if dying.generate_calls > 0:
                break
        assert res is not None and res.success
        assert len(res.output_token_ids) == 6
        assert len(res.output_token_logprobs) == 6
        if dying.generate_calls and dying.shutdown_called.is_set():
            # continuation path actually exercised: first 2 tokens from the
            # dying engine (prompt len 2), remaining 4 from the healthy one
            # with the extended prompt (len 4: 2 prompt + 2 generated)
            assert res.output_token_ids[:2] == [1002, 1003]
            assert res.output_token_ids[2:] == [1004, 1005, 1006, 1007]
            # evicted instance is gone from the registry
            st = manager.get_instances_status()
            eps = [i["endpoint"] for i in st["instances"]]
            assert dying.endpoint not in eps
    finally:
        dying.stop()
        healthy.stop()


def test_batch_generate_stream(manager):
    eng = FakeEngine().start()
    try:
        manager.register_rollout_instance(eng.endpoint)
        wait_active(manager, 1)
        reqs = [{"rid": f"b{i}", "input_ids": [1] * (i + 1),
                 "sampling_params": {"max_new_tokens": 3}} for i in range(4)]
        items = list(manager.batch_generate_stream(reqs, max_local_gen_s=30))
        results = [r for r in items if isinstance(r, GenerateResult)]
        assert len(results) == 4
        assert all(r.success for r in results)
        rids = sorted(r.rid for r in results)
        assert rids == ["b0", "b1", "b2", "b3"]
        for r in results:
            assert len(r.output_token_ids) == 3
        # token-level progress forwarding: every token also arrived as a
        # progress line BEFORE its terminal result (the salvage feed)
        prog: dict[str, list[int]] = {}
        for it in items:
            if isinstance(it, GenerateProgress):
                prog.setdefault(it.rid, []).extend(it.token_ids)
        for r in results:
            assert prog.get(r.rid) == r.output_token_ids
    finally:
        eng.stop()


def test_weight_version_orchestration(manager):
    """update_weight_version drains remotes; sender poll marks updating;
    update_weights pushes to the engine and re-activates."""
    eng = FakeEngine().start()
    try:
        manager.update_weight_senders(["127.0.0.1:19999"], groups_per_sender=2)
        out = manager.register_rollout_instance(eng.endpoint)
        assert out["weight_sender_endpoint"] == "127.0.0.1:19999"
        time.sleep(0.5)  # health check promotes (stays out of active: sender set)

        v = manager.update_weight_version()
        assert v == 1
        recv = manager.get_receive_instances()
        eps = [i["endpoint"] for i in recv["instances"]]
        assert eng.endpoint in eps
        assert recv["weight_version"] == 1
        # second poll: CAS prevents double-assignment
        recv2 = manager.get_receive_instances()
        assert [i for i in recv2["instances"]] == []

        res = manager.update_weights([eng.endpoint], weight_version=1)
        assert res["results"][0]["success"]
        assert eng.weight_updates == [1]
        st = manager.get_instances_status()
        inst = [i for i in st["instances"] if i["endpoint"] == eng.endpoint][0]
        assert inst["weight_version"] == 1
        assert not inst["updating_weight"]
        # now in the active pool → generate works
        res = manager.generate("r3", [1], {"max_new_tokens": 2})
        assert res.success
    finally:
        eng.stop()


def test_reconcile_is_idempotent_and_never_rewinds(manager):
    """POST /reconcile (supervisor replay): already-registered endpoints are
    kept (no pending reset, no double registration) and the weight version
    is a floor — a stale replay can raise it but never rewind it."""
    eng = FakeEngine().start()
    try:
        manager.register_rollout_instance(eng.endpoint)
        wait_active(manager, 1)
        assert manager.update_weight_version() == 1
        assert manager.update_weight_version() == 2
        # stale replay (version 1) must not rewind or duplicate
        out = manager.reconcile([eng.endpoint], [], [], 1, 1)
        assert out["kept"] == 1 and out["added_remote"] == 0
        assert out["weight_version"] == 2
        st = manager.get_instances_status()
        assert len(st["instances"]) == 1
        # the kept instance stays ACTIVE: served without a fresh health cycle
        res = manager.generate("rc1", [1], {"max_new_tokens": 2})
        assert res.success, res.error
        # a higher floor applies without draining the pool
        out2 = manager.reconcile([], [], [], 1, 10)
        assert out2["weight_version"] == 10
        res2 = manager.generate("rc2", [1], {"max_new_tokens": 2})
        assert res2.success, res2.error
        # new endpoints go through the normal register + health-check path
        eng2 = FakeEngine().start()
        try:
            out3 = manager.reconcile([eng2.endpoint], [], [], 1, 0)
            assert out3["added_remote"] == 1
            wait_active(manager, 2)
        finally:
            eng2.stop()
    finally:
        eng.stop()


def test_local_instance_time_slicing(manager):
    """Local instances leave the active pool after max_local_gen_s and get
    an abort; batch still completes on the remote instance."""
    slow_local = FakeEngine(token_delay_s=0.5, start_token=2000).start()
    fast_remote = FakeEngine(start_token=3000).start()
    try:
        manager.register_local_rollout_instances([slow_local.endpoint])
        manager.register_rollout_instance(fast_remote.endpoint)
        wait_active(manager, 2)
        reqs = [{"rid": f"t{i}", "input_ids": [1, 2],
                 "sampling_params": {"max_new_tokens": 4}} for i in range(2)]
        results = _finals(manager.batch_generate_stream(reqs,
                                                        max_local_gen_s=1.0))
        assert len(results) == 2
        assert all(r.success for r in results)
        # the local engine was told to abort
        assert slow_local.aborted.wait(timeout=5)
        # local engine no longer in active pool
        st = manager.get_instances_status()
        assert st["max_local_gen_s"] > 0
    finally:
        slow_local.stop()
        fast_remote.stop()


def test_update_metrics_balancer(manager):
    # trainer bubble < remote bubble → window shrinks
    out1 = manager.update_metrics(step_time_s=100.0, total_gen_time_s=40.0,
                                  trainer_bubble_s=10.0, throughput=1000.0,
                                  num_instances=2)
    assert out1["max_local_gen_s"] < 150.0
    # trainer bubble > remote bubble → window grows back
    out2 = manager.update_metrics(step_time_s=100.0, total_gen_time_s=95.0,
                                  trainer_bubble_s=50.0, throughput=1000.0,
                                  num_instances=2)
    assert out2["max_local_gen_s"] > out1["max_local_gen_s"]


def test_unhealthy_instance_not_scheduled(manager):
    eng = FakeEngine(healthy_after_s=3600).start()  # never healthy in test
    try:
        manager.register_rollout_instance(eng.endpoint)
        time.sleep(0.5)
        st = manager.get_instances_status()
        inst = [i for i in st["instances"] if i["endpoint"] == eng.endpoint]
        assert inst and not inst[0]["healthy"]
    finally:
        eng.stop()


def test_shutdown_instances(manager):
    eng = FakeEngine().start()
    try:
        manager.register_rollout_instance(eng.endpoint)
        wait_active(manager, 1)
        out = manager.shutdown_instances()
        assert out["shutdown_count"] == 1
        assert eng.shutdown_called.wait(timeout=5)
    finally:
        eng.stop()


def test_no_fabric_version_bump_keeps_remotes_serving(manager):
    """Regression (round-2 stranded-remote bug): with NO weight senders
    registered there is no re-admission path, so a bare version bump must
    NOT drain remotes from the active pool — the next batch must still be
    served. Reference semantics: drained instances always rejoin via the
    sender poll loop (sender_agent.py:324-340 → handlers.rs:681-795)."""
    eng = FakeEngine().start()
    try:
        manager.register_rollout_instance(eng.endpoint)
        wait_active(manager, 1)
        v1 = manager.update_weight_version()
        v2 = manager.update_weight_version()
        assert v2 == v1 + 1
        # the remote must still serve immediately (pre-fix: pool drained
        # forever, 120 s starvation then 'no instance available')
        t0 = time.monotonic()
        res = manager.generate("nf1", [1, 2], {"max_new_tokens": 3})
        assert res.success, res.error
        assert time.monotonic() - t0 < 10
        # and batch streaming works too
        reqs = [{"rid": f"nf-b{i}", "input_ids": [1],
                 "sampling_params": {"max_new_tokens": 2}} for i in range(3)]
        results = _finals(manager.batch_generate_stream(reqs,
                                                        max_local_gen_s=30))
        assert len(results) == 3 and all(r.success for r in results)
    finally:
        eng.stop()


def test_busy_pool_requeues_instead_of_failing():
    """A transiently busy pool (instance mid-weight-update) must requeue the
    request, not destroy it (reference blocks on instances_available_notify,
    state.rs:84-147). Uses a short schedule-wait timeout so the pre-fix
    behavior would fail fast with 'no instance available'."""
    proc, port = spawn_rollout_manager(
        "127.0.0.1:0",
        extra_args=["--health-check-interval-s", "0.1",
                    "--stats-poll-interval-s", "0.2",
                    "--schedule-wait-timeout-ms", "300"])
    client = ManagerClient(f"127.0.0.1:{port}")
    client.wait_healthy()
    eng = FakeEngine().start()
    try:
        client.update_weight_senders(["127.0.0.1:19999"])
        client.register_rollout_instance(eng.endpoint)
        time.sleep(0.5)  # healthy, but NOT active (sender set, stale weights)
        client.update_weight_version()
        recv = client.get_receive_instances()  # claim like a sender would
        assert [i["endpoint"] for i in recv["instances"]] == [eng.endpoint]

        import threading
        result = {}

        def gen():
            result["res"] = client.generate("bz1", [1], {"max_new_tokens": 2})

        t = threading.Thread(target=gen, daemon=True)
        t.start()
        # request must outlive several schedule-wait timeouts while the
        # instance is updating (pre-fix: fails after one 300 ms timeout)
        time.sleep(1.5)
        assert "res" not in result
        # transfer completes → instance re-enters pool → request served
        client.update_weights([eng.endpoint], weight_version=1)
        t.join(timeout=10)
        assert result["res"].success, result["res"].error
    finally:
        proc.kill()
        eng.stop()


def test_empty_pool_still_fails_fast():
    """Counterpart to requeueing: a pool with NO healthy/pending instance at
    all must fail the request after the schedule timeout, not hang."""
    proc, port = spawn_rollout_manager(
        "127.0.0.1:0",
        extra_args=["--schedule-wait-timeout-ms", "300"])
    client = ManagerClient(f"127.0.0.1:{port}")
    client.wait_healthy()
    try:
        t0 = time.monotonic()
        res = client.generate("ep1", [1], {"max_new_tokens": 2})
        assert not res.success
        assert time.monotonic() - t0 < 5
    finally:
        proc.kill()


def test_bounded_generate_pool_completes_large_batch():
    """generate_workers=2 with an 8-request batch: requests queue through the
    bounded pool (no thread-per-request) and all still complete."""
    proc, port = spawn_rollout_manager(
        "127.0.0.1:0",
        extra_args=["--health-check-interval-s", "0.1",
                    "--stats-poll-interval-s", "0.2",
                    "--generate-workers", "2",
                    "--http-workers", "4"])
    client = ManagerClient(f"127.0.0.1:{port}")
    client.wait_healthy()
    eng = FakeEngine().start()
    try:
        client.register_rollout_instance(eng.endpoint)
        wait_active(client, 1)
        reqs = [{"rid": f"bp{i}", "input_ids": [1, 2],
                 "sampling_params": {"max_new_tokens": 3}} for i in range(8)]
        results = _finals(client.batch_generate_stream(reqs,
                                                       max_local_gen_s=30))
        assert len(results) == 8
        assert all(r.success for r in results)
    finally:
        proc.kill()
        eng.stop()


def test_manager_metrics_endpoint(manager):
    """GET /metrics: Prometheus exposition of pool state (instances,
    weight version, per-instance queue depths)."""
    import urllib.request

    eng = FakeEngine().start()
    try:
        manager.register_rollout_instance(eng.endpoint)
        wait_active(manager, 1)
        with urllib.request.urlopen(
                f"{manager.endpoint}/metrics", timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "polyrl_mgr_instances 1" in body, body
        assert "polyrl_mgr_instances_healthy 1" in body, body
        assert f'polyrl_mgr_instance_running_reqs{{endpoint="{eng.endpoint}"}}' in body
        assert "# TYPE polyrl_mgr_weight_version counter" in body
    finally:
        eng.stop()


def test_sender_ip_acl_allows_loopback():
    """allowed_sender_ips covering the caller: registration + sender update
    succeed (reference enforces the CIDR allowlist on both,
    utils.rs:303-339)."""
    proc, port = spawn_rollout_manager(
        "127.0.0.1:0",
        extra_args=["--health-check-interval-s", "0.1",
                    "--allowed-sender-ips", "10.0.0.0/8,127.0.0.0/8"])
    client = ManagerClient(f"127.0.0.1:{port}")
    eng = FakeEngine().start()
    try:
        client.wait_healthy()
        client.update_weight_senders(["127.0.0.1:9999"], groups_per_sender=2)
        client.register_rollout_instance(eng.endpoint)
        wait_active(client, 1)
        st = client.get_instances_status()
        assert st["instances"][0]["weight_sender"] == "127.0.0.1:9999"
    finally:
        proc.kill()
        eng.stop()


def test_sender_ip_acl_rejects_unlisted():
    """Caller outside every CIDR: 403 on registration and on
    PUT /update_weight_senders; data-plane routes (health/status) stay
    open. Also covers the bare-IP (/32) spelling."""
    import urllib.error

    proc, port = spawn_rollout_manager(
        "127.0.0.1:0",
        extra_args=["--health-check-interval-s", "0.1",
                    "--allowed-sender-ips", "10.0.0.0/8,192.168.77.5"])
    client = ManagerClient(f"127.0.0.1:{port}")
    try:
        client.wait_healthy()  # /health is not ACL'd
        with pytest.raises(urllib.error.HTTPError) as ei:
            client.register_rollout_instance("127.0.0.1:1234")
        assert ei.value.code == 403
        with pytest.raises(urllib.error.HTTPError) as ei:
            client.register_local_rollout_instances(["127.0.0.1:1234"])
        assert ei.value.code == 403
        with pytest.raises(urllib.error.HTTPError) as ei:
            client.update_weight_senders(["127.0.0.1:9999"])
        assert ei.value.code == 403
        assert client.get_instances_status()["instances"] == []
    finally:
        proc.kill()


def test_sender_ip_acl_bad_cidr_fails_startup():
    """A malformed CIDR must fail at startup, not at first enforcement."""
    with pytest.raises(RuntimeError):
        spawn_rollout_manager(
            "127.0.0.1:0",
            extra_args=["--allowed-sender-ips", "not-an-ip/8"])


def test_one_build_of_the_manager_at_a_time(monkeypatch):
    """The binary is git-ignored, so in a fresh checkout the first tests
    of every xdist worker all build it: ``build_manager`` runs ``make``
    under an exclusive lock, or a worker starts the binary while another's
    link still writes it (ETXTBSY: what failed ``test_chip_smoke``'s
    rehearsal in the driver's run of PR 45's tree)."""
    import fcntl
    import os

    from polyrl_tpu.manager import client

    ran = []

    def make(cmd, **kw):
        other = os.open(client._CPP_DIR, os.O_RDONLY)
        try:
            with pytest.raises(BlockingIOError):
                fcntl.flock(other, fcntl.LOCK_EX | fcntl.LOCK_NB)
        finally:
            os.close(other)
        ran.append(cmd)

    monkeypatch.setattr(client.subprocess, "run", make)
    assert client.build_manager() == client._BINARY
    assert ran == [["make", "-C", client._CPP_DIR]]
