"""Weight-transfer fabric tests (SURVEY §4: 'the weight fabric runs on
localhost sockets by design — exercised with two processes and a small
tensor dict'; here sender/receiver run as threads in one process, the wire
is real TCP)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.manager.client import ManagerClient, spawn_rollout_manager
from polyrl_tpu.transfer import (
    ReceiverAgent,
    SenderAgent,
    TcpTransferEngine,
    TransferInterface,
    build_layout,
    pack_params,
    unflatten_like,
    unpack_params,
)
from polyrl_tpu.transfer.layout import ParamLayout, alloc_buffer
from polyrl_tpu.transfer.tcp_engine import ReceiverSockets, split_ranges
from tests.fake_engine import FakeEngine


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


# -- layout -----------------------------------------------------------------


def test_layout_roundtrip():
    params = small_params()
    layout = build_layout(params)
    assert layout.total_bytes % 64 == 0
    buf = alloc_buffer(layout)
    pack_params(params, layout, buf)
    named = unpack_params(buf, layout)
    rebuilt = unflatten_like(params, named)
    assert_tree_equal(params, rebuilt)
    # serialization roundtrip
    l2 = ParamLayout.from_json(layout.to_json())
    assert l2 == layout


def test_layout_names_stable():
    layout = build_layout(small_params())
    names = [e.name for e in layout.entries]
    assert "embed.w" in names and "layers.0.wq" in names and "norm" in names


def test_split_ranges():
    assert split_ranges(10, 3) == [(0, 4), (4, 3), (7, 3)]
    assert split_ranges(2, 8) == [(0, 1), (1, 1)]  # only non-empty ranges
    total = sum(ln for _, ln in split_ranges(1 << 20, 7))
    assert total == 1 << 20


# -- raw TCP engine ---------------------------------------------------------


def test_tcp_engine_transfer():
    rng = np.random.default_rng(0)
    src = rng.integers(0, 256, 1 << 20, dtype=np.uint8)
    dst = np.zeros_like(src)
    rx = ReceiverSockets(dst, num_streams=4, host="127.0.0.1")
    try:
        rx.arm(1)
        eng = TcpTransferEngine(num_streams=4)
        batch = eng.transfer_submit_write("127.0.0.1", rx.ports, src, round_id=1)
        batch.result(timeout=30.0)
        rx.wait(timeout=30.0)
        np.testing.assert_array_equal(src, dst)
        # second round over the same persistent listeners
        src2 = rng.integers(0, 256, 1 << 20, dtype=np.uint8)
        rx.arm(2)
        eng.transfer_submit_write("127.0.0.1", rx.ports, src2, round_id=2)
        rx.wait(timeout=30.0)
        np.testing.assert_array_equal(src2, dst)
    finally:
        rx.close()


# -- sender/receiver agents (no manager) ------------------------------------


def test_sender_stop_wakes_its_accept_thread():
    """stop() must not wait out the join timeout: closing a listening
    socket does not wake a thread blocked in accept(), shutting it down
    does (every agent teardown used to cost a flat 5 s)."""
    sender = SenderAgent(np.zeros(64, np.uint8), advertise_host="127.0.0.1")
    sender.start()
    time.sleep(0.1)  # let the accept thread block
    threads = list(sender._threads)
    t0 = time.monotonic()
    sender.stop()
    assert time.monotonic() - t0 < 2.0
    assert not any(t.is_alive() for t in threads)


def test_agents_direct_push():
    params = small_params(1)
    layout = build_layout(params)
    buf = alloc_buffer(layout)
    sender = SenderAgent(buf, manager_client=None, listen_host="127.0.0.1",
                         num_streams=2, poll_s=0.1, advertise_host="127.0.0.1")
    sender.start()
    rx = ReceiverAgent(layout, "inst-1", sender.endpoint, num_streams=2,
                       listen_host="127.0.0.1", advertise_host="127.0.0.1")
    rx.start()
    try:
        with sender.buffer_write_lock():
            pack_params(params, layout, buf)
        v = sender.signal_update()
        rx.wait_for_version(v, timeout=30.0)
        got = unflatten_like(params, unpack_params(rx.buffer, rx.layout))
        assert_tree_equal(params, got)

        # second push with new weights reuses the same sockets
        params2 = small_params(2)
        with sender.buffer_write_lock():
            pack_params(params2, layout, buf)
        v2 = sender.signal_update()
        rx.wait_for_version(v2, timeout=30.0)
        got2 = unflatten_like(params2, unpack_params(rx.buffer, rx.layout))
        assert_tree_equal(params2, got2)
    finally:
        rx.stop()
        sender.stop()


def test_receiver_buffer_size_mismatch_rejected():
    layout = build_layout(small_params())
    buf = alloc_buffer(layout)
    sender = SenderAgent(buf, manager_client=None, listen_host="127.0.0.1",
                         num_streams=1, poll_s=0.1, advertise_host="127.0.0.1")
    sender.start()
    bad_layout = build_layout({"x": jnp.zeros((3,), jnp.float32)})
    rx = ReceiverAgent(bad_layout, "bad", sender.endpoint, num_streams=1,
                       listen_host="127.0.0.1", advertise_host="127.0.0.1")
    rx.start()
    try:
        time.sleep(0.5)
        assert "bad" not in sender._regs
    finally:
        rx.stop()
        sender.stop()


# -- full orchestration through the C++ manager -----------------------------


@pytest.fixture()
def manager():
    proc, port = spawn_rollout_manager(
        "127.0.0.1:0",
        extra_args=["--health-check-interval-s", "0.1",
                    "--stats-poll-interval-s", "0.2"])
    client = ManagerClient(f"127.0.0.1:{port}")
    client.wait_healthy()
    yield client
    proc.kill()


def test_push_failure_aborts_and_retries(manager):
    """If the receiver isn't registered when the manager hands the instance
    to the sender, the sender aborts the CAS (POST /abort_weight_update) so
    the instance is retried on a later poll — not drained forever."""
    params = small_params(4)
    iface = TransferInterface(params, manager_client=manager,
                              num_streams=2, poll_s=0.1,
                              advertise_host="127.0.0.1")
    iface.sender.reg_wait_s = 0.3
    eng = FakeEngine().start()
    rx = None
    try:
        out = manager.register_rollout_instance(eng.endpoint)
        time.sleep(0.5)  # health check promotes
        v = iface.update_weights_with_agent(params)  # no receiver yet -> fails
        time.sleep(1.0)  # at least one failed push round (reg_wait 0.3s)
        # without /abort_weight_update the CAS would stay set and the
        # instance would never be returned by get_receive_instances again —
        # the retry below would time out. The abort makes retries possible:
        rx = ReceiverAgent(iface.layout, eng.endpoint,
                           out["weight_sender_endpoint"], num_streams=2,
                           listen_host="127.0.0.1", advertise_host="127.0.0.1")
        rx.start()
        rx.wait_for_version(v, timeout=30.0)
        got = unflatten_like(params, unpack_params(rx.buffer, rx.layout))
        assert_tree_equal(params, got)
    finally:
        if rx is not None:
            rx.stop()
        eng.stop()
        iface.close()


def test_end_to_end_weight_sync(manager):
    """SURVEY §3.3 end to end: trainer packs -> version bump drains pool ->
    sender polls /get_receive_instances -> TCP push -> manager
    /update_weights -> instance notified -> rejoins active pool."""
    params = small_params(3)
    iface = TransferInterface(params, manager_client=manager,
                              num_streams=2, poll_s=0.1,
                              advertise_host="127.0.0.1")
    eng = FakeEngine().start()
    rx = None
    try:
        out = manager.register_rollout_instance(eng.endpoint)
        assert out["weight_sender_endpoint"] == iface.sender.endpoint
        # the rollout server would spawn its receiver on registration:
        rx = ReceiverAgent(iface.layout, eng.endpoint,
                           out["weight_sender_endpoint"], num_streams=2,
                           listen_host="127.0.0.1", advertise_host="127.0.0.1")
        rx.start()
        time.sleep(0.5)  # health check promotes the instance

        v = iface.update_weights_with_agent(params)
        rx.wait_for_version(v, timeout=30.0)
        got = unflatten_like(params, unpack_params(rx.buffer, rx.layout))
        assert_tree_equal(params, got)

        # manager notified the instance and re-activated it
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0:
            if eng.weight_updates == [v]:
                break
            time.sleep(0.1)
        assert eng.weight_updates == [v]
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0:
            st = manager.get_instances_status()
            inst = [i for i in st["instances"] if i["endpoint"] == eng.endpoint]
            if inst and inst[0]["weight_version"] == v and not inst[0]["updating_weight"]:
                break
            time.sleep(0.1)
        else:
            raise AssertionError(f"instance never re-activated: {st}")
        res = manager.generate("wr1", [1, 2], {"max_new_tokens": 2})
        assert res.success
    finally:
        if rx is not None:
            rx.stop()
        eng.stop()
        iface.close()


# -- multi-NIC sender groups (transfer/nic.py + SenderGroup) -----------------


def test_nic_cidr_filter_and_pick():
    from polyrl_tpu.transfer import filter_ips_by_cidr, pick_sender_ips
    from polyrl_tpu.transfer.nic import get_node_ips

    ips = ["10.128.0.5", "10.129.1.7", "192.168.3.2", "127.0.0.1"]
    assert filter_ips_by_cidr(ips, "") == ips                      # open
    assert filter_ips_by_cidr(ips, "0.0.0.0/0") == ips
    assert filter_ips_by_cidr(ips, "10.0.0.0/8") == ["10.128.0.5",
                                                     "10.129.1.7"]
    assert filter_ips_by_cidr(
        ips, "10.129.0.0/16, 192.168.0.0/16") == ["10.129.1.7",
                                                  "192.168.3.2"]
    # fewer NICs than groups wraps around (reference fsdp_interface.py:108)
    assert pick_sender_ips(3, "10.129.0.0/16", ips=ips) == ["10.129.1.7"] * 3
    # more NICs truncates
    assert pick_sender_ips(1, "10.0.0.0/8", ips=ips) == ["10.128.0.5"]
    with pytest.raises(RuntimeError):
        pick_sender_ips(2, "172.16.0.0/12", ips=ips)
    # real enumeration returns at least the fallback IP
    assert len(get_node_ips(include_loopback=True)) >= 1


def test_sender_group_partitioned_push():
    """Two sender agents (one per 'NIC' — both loopback here) each serving
    their own receivers from ONE shared packed buffer; both partitions get
    every update and the pack guard excludes all in-flight rounds."""
    from polyrl_tpu.transfer import SenderGroup

    params = small_params(3)
    layout = build_layout(params)
    buf = alloc_buffer(layout)
    group = SenderGroup(buf, ["127.0.0.1", "127.0.0.1"],
                        manager_client=None, num_streams=2, poll_s=0.1,
                        listen_host="127.0.0.1")
    group.start()
    assert len(set(group.endpoints)) == 2  # distinct control ports
    rxs = [ReceiverAgent(layout, f"inst-g{i}", ep, num_streams=2,
                         listen_host="127.0.0.1", advertise_host="127.0.0.1")
           for i, ep in enumerate(group.endpoints)]
    for rx in rxs:
        rx.start()
    try:
        with group.buffer_write_lock():
            pack_params(params, layout, group.buffer)
        v = group.signal_update()
        for rx in rxs:
            rx.wait_for_version(v, timeout=30.0)
            got = unflatten_like(params, unpack_params(rx.buffer, rx.layout))
            assert_tree_equal(params, got)

        # second round through swap_buffer (double-buffer path)
        params2 = small_params(4)
        back = alloc_buffer(layout)
        pack_params(params2, layout, back)
        old = group.swap_buffer(back, v + 1)
        assert old is buf
        for rx in rxs:
            rx.wait_for_version(v + 1, timeout=30.0)
            got = unflatten_like(params2, unpack_params(rx.buffer, rx.layout))
            assert_tree_equal(params2, got)
    finally:
        for rx in rxs:
            rx.stop()
        group.stop()


def test_transfer_interface_sender_groups_with_manager(manager):
    """TransferInterface(sender_groups=2) registers BOTH sender endpoints
    with the manager, which partitions registered instances across them."""
    params = small_params(5)
    iface = TransferInterface(params, manager_client=manager,
                              num_streams=2, sender_groups=2,
                              sender_nic_cidr="127.0.0.0/8")
    try:
        assert len(iface.sender.endpoints) == 2
        st = manager.get_instances_status()
        assert st is not None  # manager accepted the PUT (no exception)
    finally:
        iface.close()


# -- streaming (in-round pack || wire || install overlap) --------------------


def test_covered_entries_prefix_logic():
    from polyrl_tpu.transfer.layout import covered_entries

    params = small_params(0)
    layout = build_layout(params)
    total = layout.total_bytes
    # nothing landed
    assert covered_entries(layout, []) == []
    # everything landed in one range
    assert [e.name for e in covered_entries(layout, [(0, total)])] == [
        e.name for e in layout.entries]
    # partial prefix: only entries fully under the watermark (order kept)
    second = layout.entries[1]
    cov = [(0, second.offset + second.nbytes - 1)]  # 1 byte short
    names = [e.name for e in covered_entries(layout, cov)]
    assert names == [layout.entries[0].name]
    # spanning a stream-range boundary: both halves must land
    mid = layout.entries[2].offset + 3
    assert [e.name for e in covered_entries(
        layout, [(0, mid), (mid, 0)])][:2] == [
        layout.entries[0].name, layout.entries[1].name]
    full = [(0, mid), (mid, total - mid)]
    assert len(covered_entries(layout, full)) == len(layout.entries)
    # start_idx resumes after already-emitted entries
    assert covered_entries(layout, full, start_idx=2) == list(
        layout.entries[2:])


def test_pack_params_streaming_matches_pack():
    from polyrl_tpu.transfer.layout import pack_params_streaming

    params = small_params(3)
    layout = build_layout(params)
    ref = alloc_buffer(layout)
    pack_params(params, layout, ref)
    buf = alloc_buffer(layout)
    marks = []
    # tiny group size forces many groups -> monotonic watermark per group
    pack_params_streaming(params, layout, buf, marks.append, group_bytes=64)
    np.testing.assert_array_equal(buf, ref)
    assert marks == sorted(marks) and marks[-1] == layout.total_bytes
    assert len(marks) > 2


def test_streamed_interleave_keeps_all_streams_busy(monkeypatch):
    """Advisor r4: contiguous per-stream ranges serialized the streamed
    round's wire behind pack order (stream k idle until the watermark
    crossed its start offset). With round-robin stripes, EVERY stream must
    land bytes while the pack is only half done — and the multi-frame
    protocol must still reassemble the buffer exactly."""
    from polyrl_tpu.transfer import tcp_engine as te

    monkeypatch.setattr(te, "STREAM_STRIPE", 1024)
    total = 16 * 1024
    src = np.frombuffer(np.random.default_rng(0).bytes(total),
                        np.uint8).copy()
    dst = np.zeros(total, np.uint8)
    rs = te.ReceiverSockets(dst, 2, host="127.0.0.1")
    eng = te.TcpTransferEngine(num_streams=2)
    try:
        rs.arm(7)
        wm = te.Watermark(total)
        batch = eng.transfer_submit_write("127.0.0.1", rs.ports, src,
                                          round_id=7, watermark=wm)
        wm.advance(total // 2)  # pack "stalled" halfway
        deadline = time.monotonic() + 10
        s0 = s1 = 0
        while time.monotonic() < deadline:
            cov = dict(rs.coverage())
            s0 = sum(g for off, g in cov.items() if (off // 1024) % 2 == 0)
            s1 = sum(g for off, g in cov.items() if (off // 1024) % 2 == 1)
            if s0 > 0 and s1 > 0:
                break
            time.sleep(0.01)
        assert s0 > 0 and s1 > 0, \
            f"wire serialized behind pack order: {dict(rs.coverage())}"
        wm.finish()
        batch.result(timeout=10)
        rs.wait(timeout=10)
        np.testing.assert_array_equal(dst, src)
    finally:
        rs.close()
        eng.shutdown()


def test_streaming_push_with_incremental_install():
    """signal_update_streaming: the pack trails behind gated sender streams
    and the receiver emits tensors in layout order as their bytes land;
    values must equal a serial pack+push."""
    from polyrl_tpu.transfer.layout import pack_params_streaming
    from polyrl_tpu.transfer.tcp_engine import Watermark

    params = small_params(5)
    layout = build_layout(params)
    buf = alloc_buffer(layout)
    sender = SenderAgent(buf, manager_client=None, listen_host="127.0.0.1",
                         num_streams=2, poll_s=0.05, advertise_host="127.0.0.1")
    sender.start()
    rx = ReceiverAgent(layout, "inst-s", sender.endpoint, num_streams=2,
                       listen_host="127.0.0.1", advertise_host="127.0.0.1")
    rx.start()
    emitted: list[tuple[str, np.ndarray]] = []
    try:
        wm = Watermark(layout.total_bytes)
        v = sender.signal_update_streaming(wm)

        def slow_progress(n):
            time.sleep(0.02)  # pack slower than the wire: streams must gate
            wm.advance(n)

        packer = threading.Thread(
            target=pack_params_streaming,
            args=(params, layout, buf, slow_progress),
            kwargs={"group_bytes": 64}, daemon=True)
        packer.start()
        rx.wait_for_version(
            v, timeout=30.0,
            on_tensor=lambda e, raw: emitted.append((e.name, raw.copy())))
        packer.join(timeout=10.0)
        wm.finish()
        names = [n for n, _ in emitted]
        assert names == [e.name for e in layout.entries]  # order + complete
        got = unflatten_like(params, unpack_params(rx.buffer, rx.layout))
        assert_tree_equal(params, got)
        by = layout.by_name()
        for name, raw in emitted:
            e = by[name]
            np.testing.assert_array_equal(
                raw, np.asarray(rx.buffer[e.offset:e.offset + e.nbytes]))
    finally:
        rx.stop()
        sender.stop()


def test_streaming_interface_update():
    """TransferInterface streaming mode end-to-end (no manager)."""
    from polyrl_tpu.transfer.interface import TransferInterface

    params = small_params(7)
    iface = TransferInterface(params, manager_client=None, num_streams=2,
                              poll_s=0.05, advertise_host="127.0.0.1")
    rx = ReceiverAgent(iface.layout, "inst-i", iface.sender.endpoint,
                       num_streams=2, listen_host="127.0.0.1",
                       advertise_host="127.0.0.1")
    rx.start()
    try:
        v = iface.update_weights_with_agent(params, streaming=True)
        rx.wait_for_version(v, timeout=30.0)
        got = unflatten_like(params, unpack_params(rx.buffer, rx.layout))
        assert_tree_equal(params, got)
        # a second streaming round reuses the same buffer safely
        params2 = small_params(8)
        v2 = iface.update_weights_with_agent(params2, streaming=True)
        rx.wait_for_version(v2, timeout=30.0)
        got2 = unflatten_like(params2, unpack_params(rx.buffer, rx.layout))
        assert_tree_equal(params2, got2)
    finally:
        rx.stop()
        iface.close()


def test_async_interface_update_and_fence():
    """update_weights_async (the pipelined trainer's push path): returns
    immediately with the bumped version while the pack/wire round rides the
    ``weight-push`` background thread; wait_pushed() fences, the receiver
    lands the exact bytes, and a pack failure surfaces ON THE FENCE, not
    silently on the background thread."""
    from polyrl_tpu.transfer.interface import TransferInterface

    params = jax.tree_util.tree_map(np.asarray, small_params(31))
    iface = TransferInterface(params, manager_client=None, num_streams=2,
                              poll_s=0.05, advertise_host="127.0.0.1")
    rx = ReceiverAgent(iface.layout, "inst-async", iface.sender.endpoint,
                       num_streams=2, listen_host="127.0.0.1",
                       advertise_host="127.0.0.1")
    rx.start()
    try:
        v = iface.update_weights_async(params)
        iface.wait_pushed(timeout=30.0)
        rx.wait_for_version(v, timeout=30.0)
        got = unflatten_like(params, unpack_params(rx.buffer, rx.layout))
        assert_tree_equal(params, got)
        # back-to-back async rounds fence on each other
        params2 = jax.tree_util.tree_map(np.asarray, small_params(32))
        v2 = iface.update_weights_async(params2)
        assert v2 == v + 1
        iface.wait_pushed(timeout=30.0)
        rx.wait_for_version(v2, timeout=30.0)
        got2 = unflatten_like(params2, unpack_params(rx.buffer, rx.layout))
        assert_tree_equal(params2, got2)
        # a poisoned pack (wrong tree) fails the NEXT fence loudly
        iface.update_weights_async({"not": np.zeros(3, np.float32)})
        with pytest.raises(RuntimeError, match="async weight push failed"):
            iface.wait_pushed(timeout=30.0)
    finally:
        rx.stop()
        iface.close()


def test_back_to_back_streaming_installs_are_never_torn():
    """A second push arriving while an incremental installer is still
    emitting must never produce a mixed-version tree: the tail re-checks
    the armed round under the install lock and, when superseded, waits for
    the newer round and re-emits everything from its completed buffer."""
    from polyrl_tpu.transfer.interface import TransferInterface

    p1 = small_params(21)
    p2 = small_params(22)
    iface = TransferInterface(p1, manager_client=None, num_streams=2,
                              poll_s=0.02, advertise_host="127.0.0.1")
    rx = ReceiverAgent(iface.layout, "inst-bb", iface.sender.endpoint,
                       num_streams=2, listen_host="127.0.0.1",
                       advertise_host="127.0.0.1")
    rx.start()
    emitted: dict[str, np.ndarray] = {}

    def slow_install(e, raw):
        time.sleep(0.01)  # slow device_put: the v2 push overtakes the tail
        emitted[e.name] = np.asarray(raw).copy()

    try:
        v1 = iface.update_weights_with_agent(p1, streaming=True)
        waiter = threading.Thread(
            target=rx.wait_for_version, args=(v1,),
            kwargs={"timeout": 30.0, "on_tensor": slow_install}, daemon=True)
        waiter.start()
        v2 = iface.update_weights_with_agent(p2, streaming=True)
        waiter.join(timeout=30.0)
        assert not waiter.is_alive()
        rx.wait_for_version(v2, timeout=30.0)
        assert set(emitted) == {e.name for e in iface.layout.entries}
        # every emitted tensor must match ONE consistent version end-to-end

        def tree_bytes(params):
            buf = alloc_buffer(iface.layout)
            pack_params(params, iface.layout, buf)
            return {e.name: np.asarray(
                buf[e.offset:e.offset + e.nbytes]) for e in iface.layout.entries}

        t1, t2 = tree_bytes(p1), tree_bytes(p2)
        match1 = all(np.array_equal(emitted[n], t1[n]) for n in emitted)
        match2 = all(np.array_equal(emitted[n], t2[n]) for n in emitted)
        assert match1 or match2, "installer emitted a torn mixed-version tree"
    finally:
        rx.stop()
        iface.close()


def test_streaming_push_fans_out_to_multiple_receivers():
    """One streamed round, two registered receivers: both instances' stream
    sets trail the SAME pack watermark concurrently and both land the full
    buffer (the sender pushes per-instance in parallel threads)."""
    from polyrl_tpu.transfer.layout import pack_params_streaming
    from polyrl_tpu.transfer.tcp_engine import Watermark

    params = small_params(31)
    layout = build_layout(params)
    buf = alloc_buffer(layout)
    sender = SenderAgent(buf, manager_client=None, listen_host="127.0.0.1",
                         num_streams=2, poll_s=0.05, advertise_host="127.0.0.1")
    sender.start()
    rxs = [ReceiverAgent(layout, f"inst-m{i}", sender.endpoint, num_streams=2,
                         listen_host="127.0.0.1", advertise_host="127.0.0.1")
           for i in range(2)]
    for rx in rxs:
        rx.start()
    try:
        time.sleep(0.3)  # both registrations land
        wm = Watermark(layout.total_bytes)
        v = sender.signal_update_streaming(wm)

        def slow_progress(n):
            time.sleep(0.02)  # pack slower than the wire: BOTH instances'
            wm.advance(n)     # gated streams must trail the same watermark

        packer = threading.Thread(
            target=pack_params_streaming,
            args=(params, layout, buf, slow_progress),
            kwargs={"group_bytes": 64}, daemon=True)
        packer.start()
        for rx in rxs:
            rx.wait_for_version(v, timeout=30.0)
        packer.join(timeout=10.0)
        assert not packer.is_alive()
        wm.finish()
        for rx in rxs:
            rx.wait_for_version(v, timeout=30.0)
            got = unflatten_like(params, unpack_params(rx.buffer, rx.layout))
            assert_tree_equal(params, got)
    finally:
        for rx in rxs:
            rx.stop()
        sender.stop()


class _TicketLock:
    """A lock that serves its waiters in the order they asked (``with``
    only). ``threading.Lock`` hands itself to whichever contender the
    scheduler runs first, and a thread that releases it and asks again at
    once wins that race whenever the cores are busy."""

    def __init__(self):
        self._cv = threading.Condition()
        self._next = self._serving = 0

    def __enter__(self):
        with self._cv:
            mine, self._next = self._next, self._next + 1
            self._cv.wait_for(lambda: self._serving == mine)

    def __exit__(self, *exc):
        with self._cv:
            self._serving += 1
            self._cv.notify_all()


def test_completion_tail_survives_same_version_repush():
    """Regression (advisor r5): a SAME-version re-push arming mid-tail must
    not let the tail emit buffer bytes the retry's streams are overwriting.
    The old tail checked sockets._round only on its first iteration and its
    supersede guard compared versions, so a retry round (same version, new
    round id) could land garbage under tensors still being emitted. The
    fixed tail re-checks the round under the lock every iteration and gates
    emission on the new round's landed coverage."""
    params = small_params(7)
    layout = build_layout(params)
    rx = ReceiverAgent(layout, "inst-tail", "127.0.0.1:9",
                       num_streams=1, listen_host="127.0.0.1",
                       advertise_host="127.0.0.1")
    # NOT started: the test drives receiver state directly, playing the
    # control-channel roles (prepare/transfer_done) itself. The install
    # lock is made fair: what is tested is the tail's re-check once a
    # re-push HAS armed between two emissions, not whether the scheduler
    # lets the re-push's thread win the lock from a tail that drops it and
    # takes it again within microseconds (on loaded cores it never does,
    # and the tail ends before the re-push arms)
    rx._install_lock = _TicketLock()
    total = layout.total_bytes
    pattern_a, pattern_b = 0xA5, 0x5A
    rx.buffer[:] = pattern_a
    # a completed round 1: full coverage, version 1 installed
    rx.sockets.arm(1)
    with rx.sockets._lock:
        rx.sockets._progress = {0: total}
    with rx._version_cv:
        rx._armed_version = 1
        rx.version = 1

    emitted: list[tuple[str, bytes]] = []
    first_emit = threading.Event()

    def on_tensor(e, raw):
        emitted.append((e.name, bytes(raw)))
        first_emit.set()
        time.sleep(0.05)  # open a window for the re-push to arm mid-tail

    def repush():
        first_emit.wait(timeout=5.0)
        # the prepare handler's exact sequence: take the install lock,
        # re-arm the SAME version under a new round id (coverage resets)
        with rx._install_lock:
            with rx._version_cv:
                rx._armed_version = 1
            rx.sockets.arm(2)
        rx.buffer[:] = 0  # garbage: round-2 bytes start landing
        time.sleep(0.25)  # tail must stall here, not emit zeros
        rx.buffer[:] = pattern_b
        with rx.sockets._lock:
            rx.sockets._progress = {0: total}  # round 2 fully landed
        with rx._version_cv:  # transfer_done for the re-push
            rx.version = 1
            rx._version_cv.notify_all()

    t = threading.Thread(target=repush, daemon=True)
    t.start()
    try:
        final = rx.wait_for_version(1, timeout=10.0, on_tensor=on_tensor)
        t.join(timeout=5.0)
        assert final == 1
        names = [n for n, _ in emitted]
        # every entry installed at least once AFTER the re-push restart
        assert names[-len(layout.entries):] == [e.name for e in layout.entries]
        for name, raw in emitted:
            vals = set(raw)
            assert vals <= {pattern_a} or vals <= {pattern_b}, (
                f"{name} emitted torn/garbage bytes: {sorted(vals)[:5]}")
        # the final install is the re-push's bytes
        for name, raw in emitted[-len(layout.entries):]:
            assert set(raw) <= {pattern_b}, name
    finally:
        rx.stop()
