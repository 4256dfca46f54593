"""``xspans.py``: the wire-format decoder against ``ProfileData`` on the
trace recorded on a TPU v5e (``make_tiny_trace.py``) and against a small
hand-encoded file; scope time, TraceMe selection by thread and window, and
``None`` where a scope, a span or a counter is absent; the readers built on
it; and the scoped trace recorded on a TPU (``make_tiny_scoped_trace.py``:
which field carries the scope path there)."""

import os
import struct

import pytest

from benchmark.lib import counters, costs, costs_kernels, harness, tracered
from benchmark.lib import xspans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = os.path.join(DATA, "tiny_tpu.xplane.pb")
SCOPED = os.path.join(DATA, "tiny_scoped_tpu.xplane.pb")


# -- a hand-encoded xplane ------------------------------------------------------


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _f(field: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(field << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _entry(field: int, key: int, msg: bytes) -> bytes:
    return _f(field, _f(1, key) + _f(2, msg))


def _event(meta_id: int, offset_ps: int, dur_ps: int) -> bytes:
    # a statistic on the event itself, as the TPU's have: stepped over
    return _f(4, _f(1, meta_id) + _f(2, offset_ps) + _f(3, dur_ps)
              + _f(4, _f(1, 9) + _f(2, 1.0)))


def _line(line_id: int, name: str, t0_ns: int, events: bytes) -> bytes:
    return _f(3, _f(1, line_id) + _f(2, name) + _f(3, t0_ns) + events)


def _space() -> bytes:
    stat_meta = (_entry(5, 1, _f(1, 1) + _f(2, "tf_op"))
                 + _entry(5, 2, _f(1, 2) + _f(2, "jit(step)/while/body/mlp/dot:"))
                 + _entry(5, 9, _f(1, 9) + _f(2, "Time Scale Multiplier")))
    metas = (
        # scope path as a string, and as a reference to a statistic's name
        _entry(4, 1, _f(1, 1) + _f(2, "%custom-call.7 = bf16[4] custom-call()")
               + _f(5, _f(1, 1) + _f(5, "jit(step)/while/body/attn_core/"
                                     "paged_attention:")))
        + _entry(4, 2, _f(1, 2) + _f(2, "%fusion.3") + _f(5, _f(1, 1) + _f(7, 2)))
        + _entry(4, 3, _f(1, 3) + _f(2, "%copy.1"))      # no scope
        # a loop's own event spans its body and is never counted
        + _entry(4, 6, _f(1, 6) + _f(2, "%while.6 = (s32[]) while((s32[]) %t)")
                 + _f(5, _f(1, 1) + _f(5, "jit(step)/while/body/mlp/while:")))
        + _entry(4, 4, _f(1, 4) + _f(2, "jit_step(123)"))
        + _entry(4, 5, _f(1, 5) + _f(2, "jit_prefill_one(9)")))
    ops = (_event(6, 100_000, 100_000)
           + _event(1, 100_000, 50_000) + _event(2, 150_000, 30_000)
           + _event(3, 180_000, 10_000) + _event(2, 400_000, 40_000))
    mods = _event(4, 100_000, 100_000) + _event(5, 400_000, 40_000)
    device = _f(1, _f(2, "/device:TPU:0") + stat_meta + metas
                + _line(1, "XLA Modules", 1_000, mods)
                + _line(2, "XLA Ops", 1_000, ops)
                + _line(3, "Async XLA Ops", 1_000, _event(3, 0, 5)))
    host_metas = (_entry(4, 1, _f(1, 1) + _f(2, "bench/window"))
                  + _entry(4, 2, _f(1, 2) + _f(2, "engine/sample_fetch"))
                  + _entry(4, 3, _f(1, 3) + _f(2, "engine/fetch"))
                  + _entry(4, 4, _f(1, 4) + _f(2, "$threading.py:300 wait"))
                  + _entry(4, 5, _f(1, 5) + _f(2, "engine/build step (False, 8, None)")))
    host = _f(1, _f(2, "/host:CPU") + host_metas
              + _line(7, "main/7", 0, _event(1, 1_000_000, 1_000_000))
              + _line(8, "python3", 0,
                      _event(2, 1_100_000, 200_000) + _event(4, 0, 9_000_000)
                      + _event(5, 1_500_000, 100_000))
              + _line(9, "python3", 0, _event(3, 900_000, 300_000)))
    return device + host + _f(1, _f(2, "/host:metadata"))


def test_decode_reads_scope_paths_threads_and_the_window():
    tr = xspans.decode(_space())
    assert tr["window"] == (1000.0, 2000.0)
    dev = tr["device"]["/device:TPU:0"]
    assert [(n.split("(")[0], s, d) for n, s, d in dev["modules"]] == [
        ("jit_step", 1100.0, 100.0), ("jit_prefill_one", 1400.0, 40.0)]
    assert [(p, s, d) for _n, p, s, d in dev["ops"]] == [
        ("jit(step)/while/body/mlp/while", 1100.0, 100.0),
        ("jit(step)/while/body/attn_core/paged_attention", 1100.0, 50.0),
        ("jit(step)/while/body/mlp/dot", 1150.0, 30.0),
        ("", 1180.0, 10.0),
        ("jit(step)/while/body/mlp/dot", 1400.0, 40.0)]
    # two threads of one name stay apart; Python frames are left out
    assert sorted(tr["host"].values()) == [
        [("engine/fetch", 900.0, 300.0)],
        [("engine/sample_fetch", 1100.0, 200.0),
         ("engine/build step (False, 8, None)", 1500.0, 100.0)]]
    assert xspans.program_names(tr) == {"jit_step", "jit_prefill_one"}


def test_scope_seconds_counts_a_scope_inside_the_named_programs_only():
    tr = xspans.decode(_space())
    # attn_core: one 50 ns operation in the one jit_step program
    assert xspans.scope_seconds(tr, "attn_core", "jit_step") == (
        pytest.approx(50e-9), 1)
    # mlp: 30 ns inside jit_step; the 40 ns belong to jit_prefill_one
    assert xspans.scope_seconds(tr, "mlp", "jit_step") == (
        pytest.approx(30e-9), 1)
    assert xspans.scope_seconds(tr, "mlp", "jit_prefill") == (
        pytest.approx(40e-9), 1)
    # absent scope, absent program, absent trace, CPU trace: None
    assert xspans.scope_seconds(tr, "attn_out", "jit_step") is None
    assert xspans.scope_seconds(tr, "mlp", "jit_spec_step") is None
    assert xspans.scope_seconds(None, "mlp", "jit_step") is None
    assert xspans.scope_seconds({"window": None, "device": {}, "host": {}},
                                "mlp", "jit_step") is None
    # a program that straddles the window's edge is not a whole step
    tr["window"] = (1150.0, 2000.0)
    assert xspans.scope_seconds(tr, "attn_core", "jit_step") is None


@pytest.mark.parametrize("path,scope,inside", [
    ("jit(step)/jit(main)/while/body/attn_core/dot_general", "attn_core", True),
    ("attn_core/slice", "attn_core", True),
    ("jit(actor_update)/transpose(jvp(attn_core))/mul", "attn_core", True),
    ("jit(step)/attn_core", "attn_core", True),
    ("jit(step)/attn_core_glue/add", "attn_core", False),
    ("jit(step)/my_attn_core/add", "attn_core", False),
    ("jit(step)/mlp/add", "attn_core", False),
    ("", "mlp", False),
])
def test_a_scope_is_a_whole_component_of_the_path(path, scope, inside):
    assert xspans._in_scope(path, scope) is inside


def test_host_spans_select_by_prefix_and_thread_and_clip_to_the_window():
    tr = xspans.decode(_space())
    eng = xspans.host_spans(tr, "engine/")
    by_first = {rows[0][0]: rows for rows in eng.values()}
    # engine/fetch began before the window: clipped to its start
    assert by_first["engine/fetch"] == [("engine/fetch", 1000.0, 1200.0)]
    assert [n for n, _a, _b in by_first["engine/sample_fetch"]] == [
        "engine/sample_fetch", "engine/build step (False, 8, None)"]
    assert xspans.host_spans(tr, "server/") == {}
    assert xspans.host_spans(None, "engine/") == {}
    tr["window"] = (1300.0, 1400.0)   # nothing of the fetch is left
    assert "engine/fetch" not in {
        r[0][0] for r in xspans.host_spans(tr, "engine/").values()}


@pytest.mark.skipif(not os.path.exists(TINY), reason="no recorded trace")
def test_decode_agrees_with_profiledata_on_a_recorded_tpu_trace():
    mine = xspans.decode(open(TINY, "rb").read())
    theirs = tracered.load(TINY)
    dev = theirs["device"]["/device:TPU:0"]
    mods = mine["device"]["/device:TPU:0"]["modules"]
    assert [n for n, _s, _d in mods] == [n for n, _s, _d in dev["XLA Modules"]]
    for (_n, s, d), (_m, s2, d2) in zip(mods, dev["XLA Modules"]):
        assert abs(s - s2) <= 1.0 and abs(d - d2) <= 1.0   # ns; theirs whole
    ops = mine["device"]["/device:TPU:0"]["ops"]
    assert [n for n, *_ in ops] == [n for n, _s, _d in dev["XLA Ops"]]
    window = [(s, s + d) for evs in theirs["host"].values()
              for n, s, d in evs if n == "bench/window"]
    assert mine["window"] == pytest.approx(window[0])
    # the metadata's ``tf_op`` carries the operation's name path
    assert {p for _n, p, _s, _d in ops if p} == {"jit(<lambda>)/dot_general"}
    assert xspans.scope_seconds(mine, "dot_general", "jit__lambda") \
        == (pytest.approx(2 * 11.84e-6, rel=0.01), 2)


@pytest.mark.skipif(not os.path.exists(SCOPED), reason="no recorded trace")
def test_scopes_and_annotations_of_a_recorded_tpu_trace():
    """What the chip's profiler really writes: scope paths through a scan
    and around a named Pallas kernel, and the threads' annotations."""
    tr = xspans.load(SCOPED)
    assert {"jit_step", "jit_prefill_one"} <= xspans.program_names(tr)
    core = xspans.scope_seconds(tr, "attn_core", "jit_step")
    mlp = xspans.scope_seconds(tr, "mlp", "jit_step")
    assert core is not None and mlp is not None
    # (the device's clock runs a fraction of a millisecond ahead of the
    # host's in this file: the first of the three steps starts "before"
    # the window that its call lies in, and is not counted)
    assert core[1] == mlp[1] >= 2 and 0 < core[0] < mlp[0]
    assert xspans.scope_seconds(tr, "attn_core", "jit_prefill_one") is None
    assert xspans.scope_seconds(tr, "mlp", "jit_prefill_one")[1] == 1
    plane = tr["device"][sorted(tr["device"])[0]]
    assert any("paged_attention" in n and xspans._in_scope(p, "attn_core")
               for n, p, _s, _d in plane["ops"])
    spans = xspans.host_spans(tr, "engine/")
    names = {frozenset(n for n, _a, _b in rows) for rows in spans.values()}
    assert frozenset(("engine/fetch",)) in names          # its own thread
    assert frozenset(("engine/decode_dispatch_device", "engine/sample_fetch",
                      "engine/idle")) in names


# -- the readers ------------------------------------------------------------------


def _obs(**over):
    config = {"hidden_size": 8, "num_attention_heads": 2,
              "num_key_value_heads": 1, "head_dim": 4,
              "num_hidden_layers": 3, "intermediate_size": 16,
              "vocab_size": 32}
    obs = {"config": {"config": config}, "peaks": {"bytes": 1e9},
           "mix": {"engine": {"steps_per_dispatch": 2}},
           "window": (0.0, 10.0), "trace": {"window_s": 4.0},
           "kv_tokens_at_end": 1000.0, "tokens_in_window": 100.0,
           "server_info": []}
    obs.update(over)
    return obs


def test_counter_readers_take_first_to_last_sample_and_skip_old_engines():
    samples = [
        {"occupancy": 0.3},                                  # an older engine
        {"device_busy_s": 1.0, "device_busy_at_s": 101.0,
         "decode_steps_done": 80, "decode_dispatches": 12, "loop_host_s": 0.5,
         "stream_lag_s": 0.10, "stream_chunks": 100},
        {"device_busy_s": 9.9},                              # a torn sample
        {"device_busy_s": 19.0, "device_busy_at_s": 119.5,
         "decode_steps_done": 880, "decode_dispatches": 112,
         "loop_host_s": 0.9, "stream_lag_s": 0.55, "stream_chunks": 1000},
    ]
    obs = _obs(server_info=samples)
    read = harness.load_reader
    assert read("busy_ms_per_step")(obs) == pytest.approx(1e3 * 18.0 / 800)
    assert read("engine_device_busy")(obs) == pytest.approx(100 * 18.0 / 18.5)
    assert read("loop_host_ms")(obs) == pytest.approx(1e3 * 0.4 / 100)
    assert read("stream_lag_ms")(obs) == pytest.approx(1e3 * 0.45 / 900)
    old = _obs(server_info=[{"occupancy": 0.3}, {"occupancy": 0.3}])
    for name in ("busy_ms_per_step", "engine_device_busy", "loop_host_ms",
                 "stream_lag_ms"):
        assert read(name)(old) is None
        assert read(name)(_obs(server_info=samples[:2])) is None  # one sample
    still = _obs(server_info=[samples[1], samples[1]])             # no step
    assert counters.delta_ratio(still, "device_busy_s",
                                "decode_steps_done") is None


def test_trace_readers_on_a_decoded_trace(monkeypatch):
    tr = xspans.decode(_space())
    monkeypatch.setattr(xspans, "load", lambda path=None: tr)
    read = harness.load_reader
    obs = _obs()
    # 50 ns under attn_core in one program of 2 fused steps
    assert read("attn_core_ms")(obs) == pytest.approx(1e3 * 50e-9 / 2)
    kv_mid = 1000.0 - 100.0 * (1.0 - 0.4 / 2.0)
    per_token = costs.kv_bytes_per_token(obs["config"]["config"])
    assert costs_kernels.attn_core_decode_bytes(
        obs["config"]["config"], kv_mid) == kv_mid * per_token == 920 * 48
    assert read("attn_core_roofline")(obs) == pytest.approx(
        100.0 * (920 * 48 / 1e9) / 25e-9)
    # no thread holds engine/decode_dispatch_device: no loop thread to read
    assert read("loop_wait_share")(obs) is None
    tr["host"]["python3#8"] = [
        ("engine/decode_dispatch_device", 1000.0, 50.0),
        ("engine/sample_fetch", 1100.0, 200.0),
        ("engine/idle", 1250.0, 150.0),       # overlaps: the union counts
        ("engine/emit", 1500.0, 100.0)]
    assert read("loop_wait_share")(obs) == pytest.approx(30.0)
    # a rehearsal (no peaks, no reduced trace) and a parent without scopes
    assert read("attn_core_roofline")(_obs(peaks=None)) is None
    assert read("attn_core_roofline")(_obs(trace=None)) is None
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    for name in ("attn_core_ms", "attn_core_roofline", "loop_wait_share"):
        assert read(name)(obs) is None
