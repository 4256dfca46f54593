"""The readers of the host half of a dispatch (PR 37): the counter readers
on two hand-made ``server_info`` samples and ``None`` without their keys
(the parent's engine), the two log2-histogram readers on hand-made bucket
lists, and the two trace readers on a hand-made decoded trace and on the
traces recorded on a TPU (``make_tiny_trace.py``: no decode program, so
``None``; ``make_tiny_scoped_trace.py``: ``jit_step`` programs beside an
``engine/fetch`` thread)."""

import json
import os

import pytest

from benchmark.lib import harness, landings, loghist, xspans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = os.path.join(DATA, "tiny_tpu.xplane.pb")
SCOPED = os.path.join(DATA, "tiny_scoped_tpu.xplane.pb")
NEW = ("loop_emit_ms", "loop_accounting_ms", "loop_dispatch_ms",
       "loop_other_ms", "emit_wait_ms", "rows_per_step", "engine_tok_s",
       "stream_lag_p99_ms", "landing_gap_max_ms", "fetch_lag_ms",
       "idle_unlanded_share", "setup_build_s", "loop_wait_ms",
       "lines_per_burst")
COUNTER = NEW[:9] + NEW[11:]
read = harness.load_reader


def _samples():
    """Two samples 10 s of landings apart: 100 dispatches of 8 steps over
    64 rows; the loop hosted 3.5 s of them."""
    a = {"decode_dispatches": 20, "decode_steps_done": 150,
         "row_steps_done": 9_600, "device_busy_at_s": 500.0,
         "loop_host_s": 1.0, "loop_wall_s": 1.0, "phase_emit_s": 0.5, "phase_accounting_s": 0.2,
         "phase_spill_sweep_s": 0.0, "phase_decode_dispatch_s": 0.2,
         "phase_prefill_dispatch_s": 0.05, "phase_collect_wave_s": 0.02,
         "phase_restore_s": 0.0, "phase_other_s": 0.03,
         "phase_sample_fetch_s": 7.0, "phase_idle_s": 0.5,
         "emit_wait_s": 0.1, "dispatches_emitted": 25,
         "stream_chunks": 110, "stream_lines": 880,
         "stream_lag_hist": [[-48, 100], [-40, 10]],
         "landing_gap_hist": [[-24, 18], [16, 1]], "stalls": 1,
         "build_s": 41.5, "programs_built": 11}
    b = dict(a, decode_dispatches=120, decode_steps_done=950,
             row_steps_done=9_600 + 800 * 64, device_busy_at_s=510.0,
             loop_host_s=4.5, phase_emit_s=2.5, phase_accounting_s=0.9,
             phase_spill_sweep_s=0.1, phase_decode_dispatch_s=0.7,
             phase_prefill_dispatch_s=0.05, phase_collect_wave_s=0.12,
             phase_restore_s=0.0, phase_other_s=0.13,
             phase_sample_fetch_s=13.0, phase_idle_s=1.0, loop_wall_s=11.0,
             stream_chunks=6_110, stream_lines=880 + 6_000 * 8,
             emit_wait_s=0.35, dispatches_emitted=125,
             # 6,000 bursts more: 5,900 in bucket -48, 89 in -40, 11 in -16
             stream_lag_hist=[[-48, 6000], [-40, 99], [-16, 11]],
             landing_gap_hist=[[-24, 60], [-23, 57], [16, 1]])
    return [a, {"occupancy": 1.0}, b]


def _obs(samples):
    """As ``run.py`` hands it over on the chip: a rehearsal has no peaks,
    and its readers say nothing."""
    return {"server_info": samples, "peaks": {"hbm_bytes_s": 819e9},
            "end_to_end": {"rollout_tok_s": 5125.0}}


def test_counter_readers_on_two_samples(capfd):
    obs = _obs(_samples())
    got = {name: read(name)(obs) for name in COUNTER}
    assert got["loop_emit_ms"] == pytest.approx(20.0)
    assert got["loop_accounting_ms"] == pytest.approx(8.0)
    assert got["loop_dispatch_ms"] == pytest.approx(5.0)
    assert got["loop_other_ms"] == pytest.approx(2.0)
    # the four partition loop_host_ms
    assert sum(got[n] for n in NEW[:4]) == pytest.approx(
        read("loop_host_ms")(obs)) == pytest.approx(35.0)
    # ... and with the two waits the loop's wall: 35 + 65 of 100 ms
    assert got["loop_wait_ms"] == pytest.approx(65.0)
    assert got["lines_per_burst"] == pytest.approx(8.0)
    assert got["emit_wait_ms"] == pytest.approx(2.5)
    assert got["rows_per_step"] == pytest.approx(64.0)
    assert got["engine_tok_s"] == pytest.approx(5120.0)
    # 6,000 bursts: the 99th percentile has 60 beyond it and lies in
    # bucket -40 (ranks 5,901-5,989), whose middle is 2**(-39.5/8) s
    assert got["stream_lag_p99_ms"] == pytest.approx(1e3 * 2 ** (-39.5 / 8))
    # the bucket that held the stall gained nothing in the window
    assert got["landing_gap_max_ms"] == pytest.approx(1e3 * 2 ** (-22 / 8))
    assert got["setup_build_s"] == 41.5
    said = capfd.readouterr().err
    assert "engine_tok_s 5120.00" in said and "5125.00" in said
    assert "(-0.098%)" in said and "same two landings" not in said
    assert "loop_other_ms: collect_wave 1.000, restore 0.000, other 1.000" \
        in said
    assert "loop_wait_ms: sample_fetch 60.000, idle 5.000, 65.0% of the " \
        "loop's wall" in said
    assert "percentile 99 of 6000 bursts" in said
    assert "99 gaps, stalls 0 in the window, 1 since the start" in said


def test_engine_tok_s_says_the_clients_rate_between_the_same_landings(capfd):
    """The harness's client shares the engine's clock: 64 streams of 8
    tokens every 0.1 s, 0.05 s behind the landings, read the engine's rate
    between the two landings the samples hold; a stream that never started
    is passed over."""
    from types import SimpleNamespace as Req

    arrivals = [(499.85 + 0.1 * i, 8) for i in range(104)]
    obs = _obs(_samples())
    obs["requests"] = [Req(arrivals=arrivals)] * 64 + [Req(arrivals=[])]
    assert read("engine_tok_s")(obs) == pytest.approx(5120.0)
    said = capfd.readouterr().err
    assert "over 10.00 s" in said
    assert "the client between the same two landings 5120.00 (+0.000%)" \
        in said


def test_counter_readers_read_none_on_an_engine_without_their_keys():
    parent = [{"decode_dispatches": 20, "decode_steps_done": 150,
               "loop_host_s": 1.0, "device_busy_at_s": 500.0,
               "stream_lag_s": 0.1, "stream_chunks": 10},
              {"decode_dispatches": 120, "decode_steps_done": 950,
               "loop_host_s": 5.0, "device_busy_at_s": 510.0,
               "stream_lag_s": 0.5, "stream_chunks": 90}]
    for samples in (parent, [], _samples()[:2]):     # too few samples too
        for name in COUNTER:
            if name == "setup_build_s" and samples and "build_s" in samples[0]:
                continue                 # one sample is all it needs
            assert read(name)(_obs(samples)) is None, name
    assert read("loop_host_ms")(_obs(parent)) == pytest.approx(40.0)


def test_the_tail_is_the_highest_percentile_with_ten_samples_beyond_it():
    assert loghist.tail({0: 10}) is None
    assert loghist.tail({}) is None
    # 200 samples: percentile 95 has ten beyond it; rank 190 is bucket 8's
    pct, value, n = loghist.tail({0: 150, 8: 45, 16: 5})
    assert (pct, n) == (95.0, 200) and value == pytest.approx(2 ** (8.5 / 8))
    pct, _value, n = loghist.tail({0: 10_000_000})
    assert (pct, n) == (99.0, 10_000_000)
    assert loghist.upper_edge(-24) == pytest.approx(2 ** (-23 / 8))
    # a bucket an older sample lacks, and one that stood still
    obs = {"server_info": [{"h": [[1, 5], [2, 7]]}, {"h": [[1, 5], [2, 9],
                                                           [3, 1]]}]}
    assert loghist.gained(obs, "h") == {2: 2, 3: 1}
    assert loghist.gained(obs, "other") is None


def _trace(k=1.0):
    """Three decode programs of 100 us (times ``k``); the first lands 20 us
    after its end while the next already runs, the second 250 us after its
    end with the device idle for 200 of them, the third not within the
    trace."""
    mods = [("jit_step(1)", 1_000.0, 100.0), ("jit_step(1)", 1_100.0, 100.0),
            ("jit_prefill_one(2)", 1_150.0, 10.0),
            ("jit_step(1)", 1_400.0, 100.0)]
    host = {"python3#3": [("engine/fetch", 1_050.0, 70.0),
                          ("engine/fetch", 1_130.0, 320.0)],
            "python3#2": [("engine/idle", 950.0, 50.0),
                          ("engine/emit", 1_125.0, 5.0)]}
    return {"window": (900.0 * k, 1_900.0 * k),
            "device": {"/device:TPU:0": {"ops": [], "modules": [
                (n, s * k, d * k) for n, s, d in mods]}},
            "host": {t: [(n, s * k, d * k) for n, s, d in rows]
                     for t, rows in host.items()}}


def test_trace_readers_on_a_decoded_trace(monkeypatch, capfd):
    tr = _trace()
    monkeypatch.setattr(xspans, "load", lambda path=None: tr)
    assert landings.fetch_ends(tr) == [1_120.0, 1_450.0]
    # lags 20 and 250 ns; the third program has no landing: left out
    assert read("fetch_lag_ms")({}) == pytest.approx(135.0 / 1e6)
    # idle: 900-1000, 1200-1400, 1500-1900; unlanded: 1100-1120 (busy),
    # 1200-1450 (idle for 200), 1500-1900 (to the window's end, idle)
    # the stamps: 12 s of landings, 11.5 of them with work outstanding
    on_chip = {"peaks": {"hbm_bytes_s": 819e9}, "server_info": [
        {"device_busy_s": 4.0, "device_busy_at_s": 100.0},
        {"device_busy_s": 15.5, "device_busy_at_s": 112.0}]}
    assert read("idle_unlanded_share")(on_chip) == pytest.approx(
        100.0 * (200.0 + 400.0) / 1_000.0)
    capfd.readouterr()
    # the same trace a million times as long says its seconds; of the idle
    # rest, 0.9-1.0 s, the loop thread idled through half
    whole = _trace(1e6)
    monkeypatch.setattr(xspans, "load", lambda path=None: whole)
    assert read("idle_unlanded_share")(on_chip) == pytest.approx(60.0)
    assert capfd.readouterr().err.rstrip().endswith(
        "the device idle 0.700 s of the traced 1.000, 0.600 s with a "
        "finished program not landed; the engine's stamps: 0.500 s with "
        "nothing outstanding in the window; in the rest: engine/idle 5.00%")
    monkeypatch.setattr(xspans, "load", lambda path=None: tr)
    # operations, where the plane has them, say when the device runs
    tr["device"]["/device:TPU:0"]["ops"] = [
        ("%fusion", "", 1_000.0, 200.0), ("%fusion", "", 1_300.0, 200.0)]
    assert read("idle_unlanded_share")(on_chip) == pytest.approx(
        100.0 * (100.0 + 400.0) / 1_000.0)
    assert "idle 0.000 s of the traced 0.000" in capfd.readouterr().err
    assert read("idle_unlanded_share")({}) == pytest.approx(50.0)
    assert capfd.readouterr().err == ""         # a rehearsal says nothing
    tr["host"].pop("python3#3")                     # no fetcher's span
    assert read("fetch_lag_ms")({}) is None
    tr["device"]["/device:TPU:0"]["modules"] = [
        ("jit_prefill_one(2)", 1_150.0, 10.0)]      # no decode program
    assert read("fetch_lag_ms")({}) is None
    assert read("idle_unlanded_share")({}) is None
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    assert read("fetch_lag_ms")({}) is None
    assert read("idle_unlanded_share")({}) is None


@pytest.mark.skipif(not os.path.exists(SCOPED), reason="no recorded trace")
def test_trace_readers_on_the_traces_recorded_on_a_tpu(monkeypatch):
    tiny, scoped = xspans.load(TINY), xspans.load(SCOPED)
    monkeypatch.setattr(xspans, "load", lambda path=None: tiny)
    assert read("fetch_lag_ms")({}) is None         # no jit_step there
    assert read("idle_unlanded_share")({}) is None
    monkeypatch.setattr(xspans, "load", lambda path=None: scoped)
    lag = read("fetch_lag_ms")({})
    share = read("idle_unlanded_share")({})
    # its fetcher thread loops over a 1 ms annotation beside the steps
    assert lag is not None and 0.0 <= lag < 5.0
    assert share is not None and 0.0 <= share <= 100.0


def test_every_new_metric_is_declared_for_every_cell_with_a_reader():
    bench = harness.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == cells
        assert callable(read(name))
    assert by_name["setup_build_s"]["moves"] == "setup_s"
    assert {by_name[n]["moves"] for n in NEW if n != "setup_build_s"} \
        == {"rollout_tok_s"}
    json.dumps(bench)
