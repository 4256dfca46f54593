"""``lib/account.py``: the partition's arithmetic on a made trace
(``decode_step_ms`` + ``step_outside_ms`` + idle = ``step_period_ms``; the
innermost declared scope wins; a loop's own event is no operation; an
instant counts once); the engine's stamps out of a hand-encoded file's
OWN event statistics, which ``xspans.decode`` steps over; the nine readers
this account brings, on the made trace, on the trace recorded on a TPU
with the new names (``make_tiny_account_trace.py``) and None from one
without them (``make_tiny_scoped_trace.py``'s: a program from before the
declaration and the stamps).

Run by hand: ``python -m pytest benchmark/tests/test_account.py -q``."""

import os
import struct

import pytest

from benchmark.lib import account, harness, xspans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "tiny_scoped_tpu.xplane.pb")
ACCOUNT = os.path.join(DATA, "tiny_account_tpu.xplane.pb")
LEAVES = ("attn_core", "mlp_dense", "glue", "head")
NEW = ("step_period_ms", "step_outside_ms", "step_unscoped_ms",
       "busy_ms_per_step.traced", "mlp_ms", "mlp_dense_ms", "attn_qkv_ms",
       "attn_out_ms", "moe_shared_ms")
P = "jit(step)/jit(main)/while/body/"


def _made() -> dict:
    """Two whole ``jit_step`` programs of 2 steps each (1000-1400 and
    1500-1900 ns), one the window cuts, ``jit_upload`` busy 60 ns between
    them, an operation under no module, 40 ns idle between them, 30 ns
    idle inside the first program."""
    ops = [
        ("%while.1 = while(...)", "", 1000.0, 400.0),            # container
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", P + "attn_core/add",
         1000.0, 100.0),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a)",
         P + "mlp/mlp_dense/dot", 1100.0, 150.0),
        ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)", P + "mlp/glue/add",
         1250.0, 50.0),
        # overlaps the one before by 20 ns: those count once, for it
        ("%copy.4 = f32[8]{0} copy(f32[8]{0} %a)", "", 1280.0, 90.0),
        # 1370-1400: no operation
        ("%sort.5 = f32[8]{0} sort(f32[8]{0} %a)", "jit(upload)/sort",
         1410.0, 60.0),
        ("%copy.6 = f32[8]{0} copy(f32[8]{0} %a)", "", 1480.0, 10.0),
        ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %a)", P + "head/dot",
         1500.0, 400.0),
        ("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %a)", P + "head/dot",
         1950.0, 100.0),
    ]
    modules = [("jit_step(1)", 1000.0, 400.0), ("jit_upload(2)", 1405.0, 70.0),
               ("jit_step(1)", 1500.0, 400.0), ("jit_step(1)", 1950.0, 400.0)]
    return {"window": (900.0, 2000.0), "host": {},
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


def test_the_partition_adds_up_and_the_innermost_scope_wins():
    acc = account.partition(_made(), 2, LEAVES)
    assert acc["steps"] == 4 and acc["span"] == (1000.0, 1900.0)
    assert acc["stretch"] == 900.0 and acc["programs"] == 800.0
    assert acc["outside"] == 70.0 and acc["idle"] == 30.0
    # decode_step_ms + step_outside_ms + idle = step_period_ms, a step
    assert acc["programs"] + acc["outside"] + acc["idle"] == acc["stretch"]
    assert acc["by_module"] == {"jit_step": 770.0, "jit_upload": 60.0,
                                account.NO_MODULE: 10.0}
    # mlp/mlp_dense -> mlp_dense, mlp/glue -> glue; the copy under no
    # scope keeps the 70 ns that no operation before it covers; the
    # container's 400 ns are nobody's
    assert acc["by_scope"] == {"attn_core": 100.0, "mlp_dense": 150.0,
                               "glue": 50.0, account.NONE: 70.0,
                               "head": 400.0}
    assert acc["gaps"] == 30.0
    assert sum(acc["by_scope"].values()) + acc["gaps"] == acc["programs"]
    assert list(acc["none_ops"].values()) == [70.0]
    assert next(iter(acc["none_ops"])).startswith("copy")
    assert account.ms_a_step(acc, acc["stretch"]) == 900.0 / 1e6 / 4
    lines = account.table(acc)
    assert acc["first"] == acc["last"] == 400.0 and acc["programs_n"] == 2
    assert "period 0.000" in lines[0] and "jit_upload" in lines[1]
    assert "the first program's step 0.000, the last's 0.000" in lines[0]
    assert "mlp_dense" in lines[2] and "copy" in lines[3]
    # a program from before the declaration: no partition by scope
    bare = account.partition(_made(), 2, None)
    assert bare["by_scope"] == {} and bare["outside"] == 70.0
    assert len(account.table(bare)) == 2


def test_a_stump_at_an_end_of_the_window_is_no_program():
    """Where the device's session starts or stops INSIDE the window (Phi's
    cell, PR 53: a first event of 264 ms and a last of 145 among programs
    of 399), the program it cuts is an event inside the window with part
    of its time: the stretch leaves it out and says how many."""
    tr = _made()
    plane = tr["device"]["/device:TPU:0"]
    plane["modules"] += [("jit_step(1)", 905.0, 90.0),    # cut at its start
                         ("jit_step(1)", 1950.0, 45.0)]   # cut at its end
    plane["modules"].remove(("jit_step(1)", 1950.0, 400.0))
    assert len(xspans.whole_programs(tr, "jit_step")) == 4
    acc = account.partition(tr, 2, LEAVES)
    assert acc["cut"] == 2 and acc["programs_n"] == 2 and acc["steps"] == 4
    assert acc["span"] == (1000.0, 1900.0) and acc["programs"] == 800.0
    assert "2 more `jit_step` events" in account.table(acc)[0]
    assert account.partition(_made(), 2, LEAVES)["cut"] == 0
    # one program alone is nobody's stump
    assert account.whole_programs(
        {**tr, "window": (1400.0, 1950.0)})[1] == 0


def test_there_is_no_account_without_a_window_or_a_whole_program():
    assert account.partition(None, 2, LEAVES) is None
    tr = _made()
    assert account.partition({**tr, "window": None}, 2, LEAVES) is None
    assert account.partition({**tr, "window": (1001.0, 1450.0)}, 2,
                             LEAVES) is None


def test_innermost_reads_through_a_gradients_wrappers():
    leaves = ("mlp_dense", "glue", "attn_core")
    assert account.innermost("a/mlp/mlp_dense/dot", leaves) == "mlp_dense"
    assert account.innermost("a/transpose(jvp(mlp_dense))/dot",
                             leaves) == "mlp_dense"
    assert account.innermost("a/glue/attn_core/x", leaves) == "attn_core"
    assert account.innermost("a/mlp/dot", leaves) == account.NONE
    assert account.innermost("", leaves) == account.NONE


# -- the stamps: an event's own statistics ----------------------------------------


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


def _landed(offset_ps: int, steps: int, busy: float, at: float) -> bytes:
    own = (_f(4, _f(1, 1) + _f(4, steps)) + _f(4, _f(1, 2) + _f(2, busy))
           + _f(4, _f(1, 3) + _f(2, at)) + _f(4, _f(1, 4) + _f(3, 1)))
    return _f(4, _f(1, 7) + _f(2, offset_ps) + _f(3, 1000) + own)


def _space() -> bytes:
    stat_meta = b"".join(
        _entry(5, i, _f(1, i) + _f(2, name)) for i, name in enumerate(
            ("decode_steps_done", "device_busy_s", "device_busy_at_s",
             "dispatches"), start=1))
    event_meta = (_entry(4, 7, _f(1, 7) + _f(2, "engine/landed"))
                  + _entry(4, 8, _f(1, 8) + _f(2, "engine/fetch"))
                  + _entry(4, 9, _f(1, 9) + _f(2, "bench/window")))
    fetcher = _f(3, _f(1, 2) + _f(2, "fetcher") + _f(3, 1000)
                 + _landed(200_000, 8, 0.25, 50.25)
                 + _f(4, _f(1, 8) + _f(2, 250_000) + _f(3, 50_000))
                 + _landed(600_000, 16, 0.75, 50.75)
                 + _landed(900_000, 32, 1.25, 51.25))
    main = _f(3, _f(1, 1) + _f(2, "main") + _f(3, 1000)
              + _f(4, _f(1, 9) + _f(2, 0) + _f(3, 2_000_000)))
    host = _f(2, "/host:CPU") + fetcher + main + event_meta + stat_meta
    return _f(1, host)


def test_stamps_are_read_from_the_events_own_statistics():
    buf = _space()
    found = account.decode_stamps(buf)
    assert [t for t, _st in found] == [1200.0, 1600.0, 1900.0]
    assert found[0][1] == {"decode_steps_done": 8, "device_busy_s": 0.25,
                           "device_busy_at_s": 50.25, "dispatches": 1}
    # the accepted decoder keeps the name and the times, as before
    host = xspans.decode(buf)["host"]["fetcher"]
    assert [n for n, _s, _d in host].count("engine/landed") == 3
    # between the first and the last stamp INSIDE the stretch
    acc = {"span": (1100.0, 1700.0)}
    got = account.busy_between_stamps(acc, found)
    assert got == {"steps": 8, "landings": 2, "busy_s": 0.5,
                   "trace_s": pytest.approx(4e-7)}
    assert account.busy_between_stamps({"span": (1100.0, 1300.0)},
                                       found) is None      # one stamp
    assert account.busy_between_stamps(None, found) is None
    assert account.busy_between_stamps(acc, []) is None
    same = [(1200.0, found[0][1]), (1600.0, found[0][1])]
    assert account.busy_between_stamps(acc, same) is None  # no step between


# -- the readers ------------------------------------------------------------------


def _obs(k: int = 2) -> dict:
    return {"mix": {"engine": {"steps_per_dispatch": k}}, "peaks": None}


def _read(monkeypatch, trace, stamps=(), leaves=LEAVES) -> dict:
    monkeypatch.setattr(xspans, "load", lambda path=None: trace)
    monkeypatch.setattr(account, "stamps", lambda path=None: list(stamps))
    monkeypatch.setattr(account, "leaf_scopes", lambda: leaves)
    return {name: harness.load_reader(name)(_obs()) for name in NEW}


def test_the_readers_on_the_made_trace(monkeypatch):
    stamps = [(1150.0, {"decode_steps_done": 10, "device_busy_s": 1.0}),
              (1850.0, {"decode_steps_done": 14, "device_busy_s": 1.5})]
    got = _read(monkeypatch, _made(), stamps)
    assert got["step_period_ms"] == pytest.approx(900.0 / 4 / 1e6)
    assert got["step_outside_ms"] == pytest.approx(70.0 / 4 / 1e6)
    assert got["step_unscoped_ms"] == pytest.approx(70.0 / 4 / 1e6)
    assert got["busy_ms_per_step.traced"] == pytest.approx(125.0)
    # plain scope readers: every operation whose path holds the scope,
    # whole durations, over the whole programs' steps
    assert got["mlp_ms"] == pytest.approx(200.0 / 4 / 1e6)
    assert got["mlp_dense_ms"] == pytest.approx(150.0 / 4 / 1e6)
    assert got["attn_qkv_ms"] is None and got["moe_shared_ms"] is None
    decode_step_ms = harness.load_reader("decode_step_ms")(_obs())
    assert decode_step_ms + got["step_outside_ms"] + 30.0 / 4 / 1e6 \
        == pytest.approx(got["step_period_ms"])
    # the parent: no declaration, no stamps
    got = _read(monkeypatch, dict(_made()), (), None)
    assert got["step_unscoped_ms"] is None
    assert got["busy_ms_per_step.traced"] is None
    assert got["step_period_ms"] == pytest.approx(900.0 / 4 / 1e6)
    assert all(v is None for v in _read(monkeypatch, None).values())


def test_the_readers_on_a_trace_from_before_the_new_names(monkeypatch):
    """``make_tiny_scoped_trace.py``'s file: ``jit_step`` programs with
    ``attn_core`` and ``mlp``, no ``mlp_dense``, no stamps."""
    trace = xspans.decode(open(SCOPED, "rb").read())
    assert account.decode_stamps(open(SCOPED, "rb").read()) == []
    got = _read(monkeypatch, trace, (), None)
    assert got["step_period_ms"] > 0 and got["step_outside_ms"] >= 0
    assert got["mlp_ms"] > 0
    for name in ("step_unscoped_ms", "busy_ms_per_step.traced",
                 "mlp_dense_ms", "attn_qkv_ms", "attn_out_ms",
                 "moe_shared_ms"):
        assert got[name] is None, name


@pytest.mark.skipif(not os.path.exists(ACCOUNT),
                    reason="recorded on a TPU by make_tiny_account_trace.py")
def test_the_readers_on_the_trace_recorded_on_a_tpu(monkeypatch, capfd):
    from polyrl_tpu.models.scopes import LEAF_SCOPES

    buf = open(ACCOUNT, "rb").read()
    trace = xspans.decode(buf)
    found = account.decode_stamps(buf)
    assert len(found) == 6
    assert [st["decode_steps_done"] for _t, st in found] == [
        2, 4, 6, 8, 10, 12]
    assert all(st["dispatches"] == 1 for _t, st in found)
    busy = [st["device_busy_s"] for _t, st in found]
    assert busy == sorted(busy) and busy[0] > 0
    acc = account.partition(trace, 2, LEAF_SCOPES)
    # the first of the six programs began before the window did on the
    # device's clock (the two clocks part by a fraction of a millisecond)
    assert acc["steps"] == 10 and acc["programs_n"] == 5
    assert acc["programs"] + acc["outside"] + acc["idle"] \
        == pytest.approx(acc["stretch"], rel=1e-12)
    assert sum(acc["by_scope"].values()) + acc["gaps"] \
        == pytest.approx(acc["programs"], rel=1e-9)
    assert set(acc["by_module"]) >= {"jit_step", "jit_upload"}
    assert acc["outside"] >= acc["by_module"]["jit_upload"] > 0
    # (``attn_core``'s tanh fused into a neighbour: a fusion carries its
    # root's scope; the cumulative sum is XLA's ``reduce-window``)
    assert acc["by_scope"]["mlp_dense"] > 0
    assert acc["by_scope"][account.NONE] > 0
    assert any(k.startswith("reduce-window") for k in acc["none_ops"])
    assert account.busy_between_stamps(acc, found)["steps"] == 6
    monkeypatch.setattr(xspans, "load", lambda path=None: trace)
    monkeypatch.setattr(account, "stamps", lambda path=None: found)
    obs = {**_obs(), "peaks": {"any": 1}}
    got = {name: harness.load_reader(name)(obs) for name in NEW}
    said = capfd.readouterr().err
    assert said.count("account: ") == 4 and "jit_upload" in said
    assert "busy_ms_per_step.traced: " in said
    assert got["mlp_ms"] >= got["mlp_dense_ms"] > 0
    assert got["step_unscoped_ms"] > 0 and got["step_outside_ms"] > 0
    assert got["busy_ms_per_step.traced"] > 0
    assert got["attn_qkv_ms"] is None
