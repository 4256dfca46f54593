"""KV memory plane (ARCHITECTURE.md "KV memory plane"): the per-page
ledger reconciles EXACTLY against the allocator free list + prefix-cache
residency at quiescence under completion/abort/salvage/flush churn,
residency tiers go hot->cold on the dispatch clock, the ``memory``
statusz section rides both planes, the flight recorder bundles
memory.json on a cold-frac anomaly, and ``kv_ledger=False`` leaves the
engine's output bitwise identical."""

import json
import os
import queue
import threading
import urllib.request

import jax
import pytest

from polyrl_tpu.models import decoder
from polyrl_tpu.obs import statusz
from polyrl_tpu.rollout.cb_engine import STREAM_END, CBEngine
from polyrl_tpu.rollout.sampling import SamplingParams


@pytest.fixture(scope="module")
def tiny():
    cfg = decoder.get_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mk_engine(tiny, **kw):
    cfg, params = tiny
    defaults = dict(max_slots=4, page_size=8, max_seq_len=128,
                    prompt_buckets=(16, 32), num_pages=64)
    defaults.update(kw)
    return CBEngine(cfg, params, **defaults)


def _drain(q):
    toks, reason = [], ""
    while True:
        item = q.get(timeout=60)
        if item is STREAM_END:
            return toks, reason
        toks.extend(item["token_ids"])
        if item["finished"]:
            reason = item["finish_reason"]


class _AbortAtFirstChunk(queue.Queue):
    """A request's stream that sets its abort event as the engine puts the
    first chunk, on the engine's own thread: by then at most
    pipeline_depth + 1 dispatches (136 tokens) are out, so a 400-token
    request cannot have finished, however late a loaded box schedules the
    thread that reads the stream."""

    def __init__(self):
        super().__init__()
        self.abort = threading.Event()

    def put(self, item, block=True, timeout=None):
        super().put(item, block, timeout)
        self.abort.set()


def _abort_mid_generation(eng, rid, prompt):
    """Submit a 400-token request, abort it at its first chunk, drain it."""
    q = _AbortAtFirstChunk()
    eng.submit(rid, prompt,
               SamplingParams(temperature=0.0, max_new_tokens=400),
               out=q, abort=q.abort)
    return _drain(q)


def _quiesce(eng):
    """Wait for the loop thread to settle: no active slots, no pending."""
    import time
    t0 = time.monotonic()
    while time.monotonic() - t0 < 30:
        if not eng._active.any() and not eng._pending \
                and eng._queue.empty():
            # one more beat so in-flight finalizes land
            time.sleep(0.2)
            if not eng._active.any():
                return
        time.sleep(0.05)
    raise AssertionError("engine did not quiesce")


# -- reconciliation ----------------------------------------------------------


def test_ledger_reconciles_exactly_under_churn(tiny):
    """attributed_frac == 1.0 EXACTLY at quiescence: every page the
    allocator or cache holds is attributed after completion churn
    (finalize + publish), salvage-abort churn, and a full cache flush."""
    # salvage_partials=True, prefix cache on; a pool that the aborted
    # request's 400 tokens would fit (see _AbortAtFirstChunk)
    eng = _mk_engine(tiny, max_seq_len=512, num_pages=128)
    eng.start()
    try:
        sp = SamplingParams(temperature=0.0, max_new_tokens=8)
        # completion churn: full-page prompts publish into the cache
        for i in range(3):
            toks, _ = _drain(eng.submit(f"fin{i}", [i + 1] * 16, sp))
            assert len(toks) == 8
        # salvage churn: abort mid-generation (salvage_partials finalizes
        # the slot through the salvage path, publishing decoded pages)
        # (13 tokens: a row holds the pages it has written, so its last
        # page is part full whenever it stops, and salvage frees that one)
        _abort_mid_generation(eng, "kill-me", [7, 9, 11, 13] * 3 + [5])
        _quiesce(eng)

        # mid-run quiescent reconcile: cache still resident
        snap = eng.kv_memory_snapshot()
        rec = snap["reconcile"]
        assert rec["attributed_frac"] == 1.0
        assert rec["ledger_free"] == rec["pool_free"] \
            == eng.allocator.free_count
        assert rec["ledger_cache"] == rec["cache_pages"] \
            == eng.prefix_cache.num_entries
        assert rec["cache_pages"] > 0, "publish churn must leave residency"

        # flush churn: everything returns to the free list
        eng.flush_prefix_cache()
        _quiesce(eng)
        snap = eng.kv_memory_snapshot()
        rec = snap["reconcile"]
        assert rec["attributed_frac"] == 1.0
        assert rec["ledger_free"] == eng.num_pages - 1  # page 0 reserved
        assert rec["ledger_cache"] == rec["cache_pages"] == 0

        # free-cause taxonomy saw each churn class
        by_cause = snap["churn"]["freed_by_cause"]
        assert by_cause["finalize"] > 0
        assert by_cause["salvage"] > 0
        assert by_cause["flush"] > 0
        # conservation: every alloc was eventually freed
        assert snap["churn"]["page_allocs"] == snap["churn"]["page_frees"]
        # lifetime/idle histograms observed the frees
        assert snap["hists"]["page_lifetime_dispatches"]["count"] > 0
    finally:
        eng.stop()


def test_plain_abort_cause_reconciles(tiny):
    """salvage_partials=False: the fast-abort path frees with the
    ``abort`` cause and still reconciles exactly."""
    eng = _mk_engine(tiny, salvage_partials=False, max_seq_len=512,
                     num_pages=128)
    eng.start()
    try:
        _abort_mid_generation(eng, "abort-me", [5, 6, 7])
        _quiesce(eng)
        snap = eng.kv_memory_snapshot()
        assert snap["churn"]["freed_by_cause"]["abort"] > 0
        assert snap["reconcile"]["attributed_frac"] == 1.0
    finally:
        eng.stop()


# -- server_info / fleet export ----------------------------------------------


def test_memory_fields_ride_server_info(tiny):
    """The flat memory-plane fields (and the cause-split cache eviction
    counters) ride /get_server_info, so the manager's stats poller can
    forward kv_cold_page_frac / hbm_headroom_gb per instance."""
    from polyrl_tpu.rollout.server import RolloutServer

    eng = _mk_engine(tiny)
    srv = RolloutServer(eng, host="127.0.0.1", port=0)
    eng.generate([[3] * 16], SamplingParams(temperature=0.0,
                                            max_new_tokens=4))
    eng.flush_prefix_cache()
    info = srv.server_info()
    assert {"kv_hot_page_frac", "kv_warm_page_frac", "kv_cold_page_frac",
            "kv_cold_bytes", "memory/attributed_frac",
            "memory/page_allocs", "memory/page_frees",
            "memory/page_publishes"} <= set(info)
    assert info["memory/attributed_frac"] == 1.0
    assert info["memory/freed_finalize"] > 0
    # prefix-cache evictions split by cause (flush churn above)
    assert {"prefix_cache/evict_capacity", "prefix_cache/evict_flush",
            "prefix_cache/evict_preref_ttl"} <= set(info)
    assert info["prefix_cache/evict_flush"] > 0
    eng.stop()


def test_fleet_gauges_and_memory_section():
    """Pool aggregation: worst-case semantics (max cold frac, min HBM
    headroom) with per-field presence guards — an engine predating the
    ledger is skipped, never counted as 0."""
    from polyrl_tpu.rollout.pool import PoolConfig, PoolManager

    insts = [
        {"endpoint": "a:1", "healthy": True, "occupancy": 0.5,
         "kv_cold_page_frac": 0.25, "hbm_headroom_gb": 4.0},
        {"endpoint": "b:2", "healthy": True, "occupancy": 0.5,
         "kv_cold_page_frac": 0.75},          # no HBM stats (CPU engine)
        {"endpoint": "c:3", "healthy": True, "occupancy": 0.5},  # pre-ledger
    ]
    g = PoolManager._fleet_engine_gauges(insts)
    assert g["engine/kv_cold_page_frac"] == 0.75   # worst (max), c skipped
    assert g["engine/hbm_headroom_gb"] == 4.0      # tightest (min), only a
    # engines with the ledger off fleet-wide -> no gauge at all, not 0.0
    g0 = PoolManager._fleet_engine_gauges(
        [{"endpoint": "c:3", "healthy": True, "occupancy": 0.5}])
    assert "engine/kv_cold_page_frac" not in g0
    assert "engine/hbm_headroom_gb" not in g0

    pm = PoolManager(manager=None, cfg=PoolConfig(sweep_interval_s=0))
    try:
        pm._last_status = {"instances": insts}
        mem = pm.memory_section()
        assert mem["fleet"]["engines_reporting"] == 2
        assert mem["fleet"]["kv_cold_page_frac_max"] == 0.75
        assert mem["fleet"]["hbm_headroom_gb_min"] == 4.0
        assert [e["endpoint"] for e in mem["engines"]] == ["a:1", "b:2"]
        # nothing reporting -> empty section (statusz serves {}, the
        # recorder skips memory.json)
        pm._last_status = {"instances": [insts[2]]}
        assert pm.memory_section() == {}
    finally:
        pm.close()


# -- residency tiers ---------------------------------------------------------


def test_published_pages_go_cold_within_budget(tiny):
    """CPU e2e: a finished request's published pages decay hot->cold
    within kv_cold_after_dispatches idle dispatches of unrelated traffic,
    and the fraction surfaces as the fleet's engine/kv_cold_page_frac."""
    from polyrl_tpu.rollout.pool import PoolManager
    from polyrl_tpu.rollout.server import RolloutServer

    cold_after = 8
    eng = _mk_engine(tiny, kv_cold_after_dispatches=cold_after,
                     steps_per_dispatch=2)
    srv = RolloutServer(eng, host="127.0.0.1", port=0)
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)
    # publish a distinctive prefix into the cache, then leave it idle
    eng.generate([[101] * 16], sp)
    assert eng.prefix_cache.num_entries > 0
    birth_tick = eng.kvledger.dispatch
    info = srv.server_info()
    assert info["kv_cold_page_frac"] == 0.0, "fresh pages must not be cold"

    # unrelated traffic (distinct prompts -> no hit on the idle pages)
    # until the dispatch clock has advanced past the cold budget
    i = 0
    while eng.kvledger.dispatch - birth_tick <= cold_after:
        eng.generate([[7 + i, 9 + i, 11 + i, 13 + i]], sp)
        i += 1
        assert i < 64, "dispatch clock is not advancing"

    info = srv.server_info()
    assert info["kv_cold_page_frac"] > 0.0, (
        f"idle published pages still not cold "
        f"{eng.kvledger.dispatch - birth_tick} dispatches after birth")
    assert info["kv_cold_bytes"] > 0.0
    snap = eng.kv_memory_snapshot()
    assert snap["tiers"]["cold"] > 0
    assert snap["tiers"]["cold_after_dispatches"] == cold_after
    # and the step-record gauge the trainer/recorder watches carries it
    g = PoolManager._fleet_engine_gauges(
        [{"healthy": True, "occupancy": 0.0, **info}])
    assert g["engine/kv_cold_page_frac"] == info["kv_cold_page_frac"]
    eng.stop()


# -- statusz v6 --------------------------------------------------------------


def _get_json(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=10.0) as r:
        return json.loads(r.read())


def test_statusz_v6_memory_section_both_planes(tiny):
    """Both planes serve the v6 ``memory`` section: the rollout plane's
    carries the live ledger snapshot, the trainer plane's the fleet view
    (ALWAYS present — {} when nothing reports)."""
    from polyrl_tpu.rollout.server import RolloutServer

    assert statusz.SCHEMA == "polyrl/statusz/v8"
    assert "memory" in statusz.REQUIRED_SECTIONS

    # trainer plane: fleet view via build_snapshot's memory kwarg
    fleet = {"fleet": {"engines_reporting": 1,
                       "kv_cold_page_frac_max": 0.5}}
    srv = statusz.StatuszServer(
        lambda: statusz.build_snapshot("trainer", step=3, memory=fleet),
        host="127.0.0.1").start()
    try:
        snap = _get_json(f"http://{srv.endpoint}/statusz")
        assert snap["schema"] == "polyrl/statusz/v8"
        assert snap["memory"] == fleet
    finally:
        srv.stop()
    # ...and the section is ALWAYS present, {} when nothing reports
    assert statusz.build_snapshot("trainer", step=3)["memory"] == {}

    # rollout plane: the live ledger behind the real route
    eng = _mk_engine(tiny)
    server = RolloutServer(eng, host="127.0.0.1", port=0).start()
    try:
        eng.generate([[5] * 16], SamplingParams(temperature=0.0,
                                                max_new_tokens=4))
        snap = _get_json(f"http://127.0.0.1:{server.port}/statusz")
        assert snap["schema"] == "polyrl/statusz/v8"
        mem = snap["memory"]
        # the four attributable roles cover every page but reserved page 0
        assert sum(mem["roles"].values()) == eng.num_pages - 1
        assert mem["reconcile"]["attributed_frac"] == 1.0
        assert {"hot", "warm", "cold"} <= set(mem["tiers"])
        assert mem["churn"]["page_allocs"] > 0
        # HBM truth is optional (absent on the CPU backend) but the
        # accounted-bytes denominator is always there
        assert mem["accounted_bytes"] > 0
    finally:
        server.stop()


def test_kv_report_renders_ledger_and_fleet(tiny, capsys):
    """tools/kv_report.py renders both section shapes without choking."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    try:
        import kv_report
    finally:
        sys.path.pop(0)

    eng = _mk_engine(tiny)
    eng.generate([[5] * 16], SamplingParams(temperature=0.0,
                                            max_new_tokens=4))
    out = kv_report.render(eng.kv_memory_snapshot(), {"source": "test"})
    assert "reconciliation: attributed_frac = 1" in out
    assert "residency tiers" in out
    eng.stop()
    out = kv_report.render(
        {"fleet": {"engines_reporting": 2, "kv_cold_page_frac_max": 0.5},
         "engines": [{"endpoint": "a:1", "kv_cold_page_frac": 0.5}]},
        {"source": "test"})
    assert "cold frac max = 0.5" in out
    assert kv_report.render({}, {"source": "t"}).count("empty") == 1


# -- flight recorder ---------------------------------------------------------


def test_recorder_bundles_memory_json_on_cold_anomaly(tmp_path):
    """A cold-frac spike trips the recorder exactly once, and the bundle
    carries the fleet memory view as memory.json."""
    from polyrl_tpu.obs.recorder import DEFAULT_WATCH, FlightRecorder

    assert DEFAULT_WATCH["engine/kv_cold_page_frac"] == "high"
    assert DEFAULT_WATCH["engine/hbm_headroom_gb"] == "low"

    rec = FlightRecorder(str(tmp_path), warmup=3, z_threshold=4.0)
    fleet = {"fleet": {"engines_reporting": 1,
                       "kv_cold_page_frac_max": 0.9},
             "engines": [{"endpoint": "a:1", "kv_cold_page_frac": 0.9}]}
    rec.memory_fn = lambda: fleet
    for s in range(6):
        assert rec.record_step(s, {"engine/kv_cold_page_frac": 0.05}) is None
    path = rec.record_step(7, {"engine/kv_cold_page_frac": 0.9})
    assert path is not None, "cold-frac spike must dump a bundle"
    with open(os.path.join(path, "memory.json")) as f:
        assert json.load(f) == fleet
    # exactly one bundle for the induced anomaly
    bundles = os.listdir(os.path.join(str(tmp_path), "postmortem"))
    assert len(bundles) == 1
    # memprof.pprof is never written on the CPU backend
    assert "memprof.pprof" not in os.listdir(path)


def test_recorder_skips_empty_memory_view(tmp_path):
    """memory_fn returning {} (ledger off fleet-wide) must not leave an
    empty memory.json in the bundle."""
    from polyrl_tpu.obs.recorder import FlightRecorder

    rec = FlightRecorder(str(tmp_path), warmup=3, z_threshold=4.0)
    rec.memory_fn = dict  # always {}
    for s in range(6):
        rec.record_step(s, {"engine/kv_cold_page_frac": 0.05})
    path = rec.record_step(7, {"engine/kv_cold_page_frac": 0.9})
    assert path is not None
    assert "memory.json" not in os.listdir(path)


# -- ledger off --------------------------------------------------------------


def test_ledger_off_is_bitwise_identical(tiny):
    """rollout.kv_ledger=false: pure bookkeeping removal — sampled output
    (RNG-sensitive) is bitwise identical with the ledger on or off."""
    sp = SamplingParams(temperature=0.8, top_p=0.9, max_new_tokens=12)
    prompts = [[5, 3, 9] * 4, [11, 4] * 8, [42] * 16]
    on = _mk_engine(tiny, kv_ledger=True, seed=7)
    out_on = on.generate(prompts, sp)
    on.stop()
    off = _mk_engine(tiny, kv_ledger=False, seed=7)
    out_off = off.generate(prompts, sp)
    assert off.kvledger is None
    assert off.kv_memory_info() == {}
    assert off.kv_memory_snapshot() == {}
    off.stop()
    for a, b in zip(out_on, out_off):
        assert a["token_ids"] == b["token_ids"]
        assert a["logprobs"] == b["logprobs"]  # exact, not approx
        assert a["finish_reason"] == b["finish_reason"]
