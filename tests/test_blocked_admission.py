"""Blocked admission (ARCHITECTURE.md "Fetcher-thread pipeline"): a request
that waits for pages or a slot takes what the fetcher has landed and stays
in ``_pending``; it never puts a barrier on the run-ahead pipeline. While
one waits the loop keeps the device one program ahead (by what the device
has finished, not by what has reached the host); with nobody waiting the
throttle is ``pipeline_depth``. Timing moves, results do not.

The engines here are the tiny preset with 2 slots for 4 requests: prompts
of 8 tokens (no full page, so nothing is published), answers long enough
that nobody ends unless a case wants it, and a pool that holds what two
rows write. (Until PR 34 it was the pool that admitted 2 of 4, each
reserving prompt + answer at admission; pages now follow what a row has
written, ``tests/test_page_growth.py``, so what a request waits for here
is a slot, except in (f), where it is pages a long row has written.)"""

import threading
import time

import jax
import numpy as np
import pytest

from polyrl_tpu.models import decoder
from polyrl_tpu.rollout import cb_engine
from polyrl_tpu.rollout.cb_engine import CBEngine, STREAM_END
from polyrl_tpu.rollout.sampling import SamplingParams


@pytest.fixture(scope="module")
def tiny():
    cfg = decoder.get_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mk_engine(tiny, **kw):
    cfg, params = tiny
    # 63 pages to hand out; a request of 8 + 240 tokens writes 31
    defaults = dict(max_slots=2, page_size=8, max_seq_len=256,
                    prompt_buckets=(16,), num_pages=64)
    defaults.update(kw)
    return CBEngine(cfg, params, **defaults)


def _prompts(cfg, n=4, length=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, length).tolist()
            for _ in range(n)]


def _greedy(budget):
    return SamplingParams(temperature=0.0, max_new_tokens=budget,
                          stop_token_ids=())


LONG = _greedy(240)


def _wait(cond, timeout=60.0, what="condition"):
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.005)


def _read(q, n, timeout=60.0):
    """The next ``n`` tokens of a stream (fewer if it ends first)."""
    toks = []
    while len(toks) < n:
        item = q.get(timeout=timeout)
        if item is STREAM_END:
            break
        toks.extend(item["token_ids"])
    return toks


def _end_of(q, timeout=60.0):
    """Drain a stream to its end; the finish reason of its terminal line."""
    reason = ""
    while True:
        item = q.get(timeout=timeout)
        if item is STREAM_END:
            return reason
        if item["finished"]:
            reason = item["finish_reason"]


def _old_drain(self, attempt):
    """What the three admission sites did before: land fetch batch after
    fetch batch, waiting for each, until the attempt succeeds or the
    run-ahead pipeline is EMPTY."""
    got = attempt()
    while not got and self._outstanding():
        self._drain_emit_q(keep=self._outstanding() - 1)
        got = attempt()
    return got


def _iterate_with_two_waiting(eng, cfg, n_iters=20):
    """Drive the loop by hand (no threads: a landing happens only where the
    loop asks for one, so the counts are exact): 4 long requests, 2 slots.
    Returns the counters after the first iteration and after ``n_iters``
    more."""
    for i, p in enumerate(_prompts(cfg)):
        eng.submit(f"r{i}", p, LONG)
    eng._loop_iter()
    first = eng.profiler.counters()
    for _ in range(n_iters):
        eng._loop_iter()
    return first, eng.profiler.counters()


# -- (a) the pipeline keeps its run-ahead while requests wait -----------------


@pytest.mark.parametrize("spec_tokens", [0, 2], ids=["plain", "spec"])
def test_waiting_requests_leave_the_pipeline_its_run_ahead(tiny, spec_tokens):
    cfg, _ = tiny
    eng = _mk_engine(tiny, spec_tokens=spec_tokens)
    try:
        first, last = _iterate_with_two_waiting(eng, cfg)
        assert len(eng._pending) == 2 and int(eng._active.sum()) == 2
        assert eng._admission_waiting
        # the first decode dispatch follows the prefill, the rest follow
        # each other: the device never ran dry
        assert first["decode_dispatches"] == 1
        assert last["decode_dispatches"] == 21
        assert last["decode_dispatches_cold"] == \
            first["decode_dispatches_cold"] == 0
        # one deferral an iteration, and none of them waited
        assert first["admission_deferrals"] == 1
        assert last["admission_deferrals"] == 21
        assert eng._outstanding() == eng.pipeline_depth
    finally:
        eng.stop()


# -- (b) the old loop is what (a) catches -------------------------------------


def test_draining_admission_runs_every_dispatch_cold(tiny, monkeypatch):
    cfg, _ = tiny
    monkeypatch.setattr(CBEngine, "_retry_landed", _old_drain)
    eng = _mk_engine(tiny)
    try:
        first, last = _iterate_with_two_waiting(eng, cfg)
        assert len(eng._pending) == 2
        assert last["decode_dispatches"] == 21
        # every iteration's admission emptied the pipeline before the
        # dispatch that followed it
        assert last["decode_dispatches_cold"] \
            - first["decode_dispatches_cold"] == 20
    finally:
        eng.stop()


# -- (c) how far the loop runs ahead, with and without a queue ----------------


def test_a_freed_slot_is_refilled_within_two_dispatches(tiny):
    """A (41 tokens) ends in its 5th decode dispatch; C, waiting for a
    slot, is prefilled no later than 2 decode dispatches after A's last
    output landed, and D goes on waiting."""
    cfg, _ = tiny
    eng = _mk_engine(tiny)
    events: list[str] = []
    enqueue, finalize = eng._enqueue_output, eng._finalize

    def rec_enqueue(entry, **kw):
        events.append(entry[0])
        return enqueue(entry, **kw)

    def rec_finalize(slot, **kw):
        events.append("finalize")
        return finalize(slot, **kw)

    eng._enqueue_output, eng._finalize = rec_enqueue, rec_finalize
    try:
        budgets = [_greedy(41), LONG, LONG, LONG]
        outs = [eng.submit(f"r{i}", p, sp)
                for i, (p, sp) in enumerate(zip(_prompts(cfg), budgets))]
        eng.start()
        assert _end_of(outs[0]) == "length"
        assert _read(outs[2], 1), "C must be admitted once A's slot returns"
        seen = list(events)
        fin = seen.index("finalize")
        assert seen[:fin].count("step") >= 5
        refill = next(i for i in range(fin, len(seen))
                      if seen[i].startswith("prefill"))
        assert seen[fin:refill].count("step") <= 2, seen
        assert len(eng._pending) == 1  # D
    finally:
        eng.stop()


def test_run_ahead_is_bounded_by_the_device_only_while_a_request_waits(
        tiny, monkeypatch):
    """The device's progress is what holds the loop while a request waits:
    with the device (as the loop sees it) finishing nothing, one decode
    dispatch goes out behind the prefill and no more, though every output
    lands; with nobody waiting, the same blind loop dispatches on."""
    cfg, _ = tiny
    done = threading.Event()
    real = cb_engine._finished_on_device
    monkeypatch.setattr(cb_engine, "_finished_on_device",
                        lambda payload: done.is_set() and real(payload))

    eng = _mk_engine(tiny)
    try:
        outs = [eng.submit(f"r{i}", p, LONG)
                for i, p in enumerate(_prompts(cfg))]
        eng.start()
        # the prefill's output lands and is emitted by the waiting loop
        assert _read(outs[0], 1) and _read(outs[1], 1)
        time.sleep(0.3)
        c = eng.profiler.counters()
        assert c["decode_dispatches"] == 1
        assert len(eng._pending) == 2
        done.set()
        _wait(lambda: eng.profiler.counters()["decode_dispatches"] >= 6,
              what="dispatches once the device finishes programs")
    finally:
        eng.stop()

    done.clear()
    eng = _mk_engine(tiny, max_slots=4, num_pages=256)  # room for all four
    try:
        for i, p in enumerate(_prompts(cfg)):
            eng.submit(f"r{i}", p, LONG)
        eng.start()
        _wait(lambda: eng.profiler.counters()["decode_dispatches"] >= 6,
              what="dispatches with nobody waiting")
        assert eng.profiler.counters()["admission_deferrals"] == 0
        assert not eng._admission_waiting
    finally:
        eng.stop()


def test_with_nobody_waiting_the_throttle_is_pipeline_depth(tiny):
    cfg, _ = tiny
    eng = _mk_engine(tiny, max_slots=4, num_pages=256, pipeline_depth=3)
    try:
        for i, p in enumerate(_prompts(cfg)):
            eng.submit(f"r{i}", p, LONG)
        for _ in range(10):
            eng._loop_iter()
            assert eng._outstanding() <= 3
        assert eng._outstanding() == 3 and not eng._pending
        c = eng.profiler.counters()
        assert c["admission_deferrals"] == 0
        assert c["decode_dispatches"] == 10
        assert c["decode_dispatches_cold"] == 0
    finally:
        eng.stop()


# -- (d) timing moves, results do not -----------------------------------------


def test_streams_equal_an_engine_with_room_for_all(tiny):
    cfg, _ = tiny
    prompts = _prompts(cfg, seed=1)
    sp = _greedy(40)  # 6 pages each

    def run(max_slots):
        eng = _mk_engine(tiny, max_slots=max_slots)
        try:
            return eng.generate(prompts, sp, timeout=120.0), \
                eng.profiler.counters()
        finally:
            eng.stop()

    tight, c_tight = run(2)   # two at a time
    roomy, c_roomy = run(4)
    assert c_tight["slot_yields"] == c_roomy["slot_yields"] == 0
    assert c_tight["admission_deferrals"] > 0 == \
        c_roomy["admission_deferrals"]
    for a, b in zip(tight, roomy):
        assert a["finish_reason"] == b["finish_reason"] == "length"
        assert a["token_ids"] == b["token_ids"]
        np.testing.assert_allclose(a["logprobs"], b["logprobs"], atol=5e-4)


# -- (e) an abort while waiting -----------------------------------------------


def test_abort_of_a_waiting_request_is_honoured_at_once(tiny):
    """D waits behind C, which waits for a slot: the scan never reaches D,
    and D's abort is still honoured on the next iteration (one decode
    dispatch); A and B decode on; then the head's."""
    cfg, _ = tiny
    eng = _mk_engine(tiny)
    at_abort: list[int] = []
    emit_abort = eng._emit_abort

    def rec_abort(req, **kw):
        at_abort.append(eng.profiler.counters()["decode_dispatches"])
        return emit_abort(req, **kw)

    eng._emit_abort = rec_abort
    try:
        evs = [threading.Event() for _ in range(4)]
        outs = [eng.submit(f"r{i}", p, LONG, abort=evs[i])
                for i, p in enumerate(_prompts(cfg))]
        eng.start()
        assert _read(outs[0], 1) and _read(outs[1], 1)
        _wait(lambda: len(eng._pending) == 2, what="two requests waiting")
        for who in (3, 2):
            before = eng.profiler.counters()["decode_dispatches"]
            evs[who].set()
            assert _end_of(outs[who], timeout=30.0) == "abort"
            assert at_abort[-1] - before <= 2
        assert len(eng._pending) == 0
        # the survivors never stopped
        assert len(_read(outs[0], 16)) >= 16
        assert len(_read(outs[1], 16)) >= 16
        assert int(eng._active.sum()) == 2
    finally:
        eng.stop()


# -- (f) the spill tier's restore under pool pressure -------------------------


def _pump(eng, queues, max_iters=2000):
    """Drive the loop by hand until every stream has ended; the tokens of
    each."""
    toks = [[] for _ in queues]
    ended = [False] * len(queues)
    for _ in range(max_iters):
        eng._loop_iter()
        for i, q in enumerate(queues):
            while not ended[i] and not q.empty():
                item = q.get_nowait()
                if item is STREAM_END:
                    ended[i] = True
                else:
                    toks[i].extend(item["token_ids"])
        if all(ended):
            return toks
    raise AssertionError("streams did not end")


def test_restore_under_pressure_truncates_or_restores_and_never_drains(tiny):
    """A prefix hit on spilled pages while a long request holds the pool
    (16 of 18 pages, written or in flight): the restore finds no pages, the
    hit truncates and the request waits; when the holder ends the chain is
    restored and attached. No admission step calls the blocking drain, and
    the tokens are those of an engine that never spilled."""
    cfg, _ = tiny
    [p] = _prompts(cfg, 1, length=32, seed=2)
    [h] = _prompts(cfg, 1, length=32, seed=3)
    short, holder = _greedy(8), _greedy(90)  # 5 pages; 16 pages

    def run(num_pages, spill):
        eng = CBEngine(cfg, tiny[1], max_slots=2, page_size=8,
                       max_seq_len=128, prompt_buckets=(32,),
                       num_pages=num_pages, kv_spill=spill,
                       kv_cold_after_dispatches=2)
        drains_in_scan: list[int] = []
        scanning = [False]
        collect, drain = eng._collect_wave, eng._drain_emit_q

        def rec_collect():
            scanning[0] = True
            try:
                return collect()
            finally:
                scanning[0] = False

        def rec_drain(keep=0):
            if scanning[0]:
                drains_in_scan.append(keep)
            return drain(keep)

        eng._collect_wave, eng._drain_emit_q = rec_collect, rec_drain
        try:
            # by hand, no threads: a landing happens only where the loop
            # asks for one, so the holder's run-ahead is what the loop's
            # throttle lets it be and the pool's state is exact
            [first] = _pump(eng, [eng.submit("first", p, short)])
            if spill:
                n = len(eng.prefix_cache.spill_candidates())
                assert n == 3 and eng._spill_pages(n, cold_only=False) == n
            qh = eng.submit("holder", h, holder)
            while eng.allocator.free_count > num_pages - 1 - 16:
                eng._loop_iter()   # the holder's pages, as it runs ahead
            assert eng._active.any()
            qp = eng.submit("resume", p, short)
            both = _pump(eng, [qh, qp])
            return first, both, drains_in_scan, eng
        finally:
            eng.stop()

    # 18 pages to hand out: the holder takes 16, the restore needs 3
    first, (held, resumed), drains, eng = run(19, True)
    ref_first, (ref_held, ref_resumed), _d, _e = run(128, False)
    assert drains == [], "admission put a barrier on the pipeline"
    c = eng.profiler.counters()
    assert c["admission_deferrals"] > 0 == c["slot_yields"]
    assert eng.kvledger.pages_restored >= 3
    assert first == ref_first
    assert held == ref_held and len(held) == 90
    assert resumed == ref_resumed == first


# -- the per-layer metric that reads the counter ------------------------------


@pytest.mark.parametrize("samples,want", [
    # an engine that drains before every dispatch; one that never does;
    # one cold dispatch in a window of 100
    ([{"decode_dispatches": 10, "decode_dispatches_cold": 10},
      {"decode_dispatches": 190, "decode_dispatches_cold": 190}], 100.0),
    ([{"decode_dispatches": 10, "decode_dispatches_cold": 1},
      {"decode_dispatches": 190, "decode_dispatches_cold": 1}], 0.0),
    ([{"occupancy": 1.0},
      {"decode_dispatches": 10, "decode_dispatches_cold": 1},
      {"decode_dispatches": 110, "decode_dispatches_cold": 2}], 1.0),
    # a parent's engine has no such counter; nothing dispatched
    ([{"decode_dispatches": 10}, {"decode_dispatches": 190}], None),
    ([{"decode_dispatches": 10, "decode_dispatches_cold": 1},
      {"decode_dispatches": 10, "decode_dispatches_cold": 1}], None),
])
def test_cold_dispatch_share_of_a_server_info_pair(samples, want):
    from benchmark.lib import harness

    got = harness.load_reader("cold_dispatch_share")({"server_info": samples})
    assert got == (want if want is None else pytest.approx(want))
