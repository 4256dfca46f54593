"""The offered load is the mix's: ``steady_plan`` against the function it
replaced (which copied the engine's reservation rule), the pattern's end
of set-up against sequences of ``server_info``, the pattern itself over a
fake plane, and ``kv_pool_written``. CPU only, no jax."""

import os
import threading
import time

import pytest

from benchmark.lib import costs, harness, stats, traffic, xspans
from benchmark.lib.traffic import quantile, rng_for, size_set

steady_decode = harness.load_named("patterns", "steady_decode")


def parent_steady_plan(mix, seed, vocab, free_pages, page_size, max_requests):
    """``traffic.steady_plan`` as it stood at 575a2de, verbatim: the
    largest n whose prompts plus budgets fit ``free_pages``."""
    budget = quantile(mix["answer_tokens"], 0.5)

    def pages(lengths):
        return sum(-(-(n + budget) // page_size) for n in lengths)

    n = max_requests
    while n > 1 and pages(size_set(mix["prompt_tokens"], n)) > free_pages:
        n -= 1
    lengths = size_set(mix["prompt_tokens"], n)
    rng = rng_for(seed)
    return [{"prompt": rng.integers(1, vocab, size=lengths[k]).tolist(),
             "budget": budget, "rank": int(k)}
            for k in rng_for(0, 1).permutation(n)]


def load_config(name):
    return harness.load_config(os.path.join(harness.BENCH_DIR, "configs",
                                            f"{name}.json"))


def cell_sizes(config, mix):
    """(vocabulary, pages the engine may hand out) as the plane works
    them out for a cell of this configuration."""
    per_page = (costs.kv_bytes_per_token(config["config"])
                * mix["engine"]["page_size"])
    return (int(config["config"]["vocab_size"]),
            int(config["serve"]["kv_pool_bytes"] // per_page))


@pytest.mark.parametrize("config_name, mix_name, offered", [
    ("qwen2.5-7b", "rollout-long", 18), ("qwen3-30b-a3b", "rollout-wide", 64)])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_the_two_standing_mixes_offer_what_the_parent_computed(
        config_name, mix_name, offered, seed):
    mix = traffic.load_mix(mix_name)
    vocab, free_pages = cell_sizes(load_config(config_name), mix)
    e = mix["engine"]
    parent = parent_steady_plan(mix, seed, vocab, free_pages,
                                e["page_size"], e["max_slots"])
    assert len(parent) == offered == mix["offered_requests"]
    # the same lengths, the same order, the same ids: both cells measure
    # what they measured
    assert traffic.steady_plan(mix, seed, vocab) == parent


@pytest.mark.parametrize("mix_name, slots_then", [("rollout-long", 8),
                                                  ("rollout-wide", 4)])
def test_the_rehearsals_offer_what_the_parent_computed(mix_name, slots_then):
    tiny = load_config("rehearsal")
    mix = harness.rehearsal(dict(tiny, reference="dense_gqa"),
                            traffic.load_mix(mix_name))[1]
    vocab, free_pages = cell_sizes(tiny, mix)
    e = mix["engine"]
    assert traffic.steady_plan(mix, 3, vocab) == parent_steady_plan(
        mix, 3, vocab, free_pages, e["page_size"], e["max_slots"])
    assert mix["offered_requests"] == slots_then


def test_rollout_short_offers_64_fixed_lengths():
    mix = traffic.load_mix("rollout-short")
    vocab, free_pages = cell_sizes(load_config("qwen2.5-7b"), mix)
    a = traffic.steady_plan(mix, 11, vocab)
    b = traffic.steady_plan(mix, 2**31 + 99, vocab)
    lengths = [len(p["prompt"]) for p in a]
    assert len(a) == 64 == mix["engine"]["max_slots"]
    assert lengths == [len(p["prompt"]) for p in b]
    assert [p["rank"] for p in a] == [p["rank"] for p in b]
    assert a != b
    assert (min(lengths), max(lengths), sum(lengths)) == (129, 955, 18221)
    assert stats.median(lengths) == pytest.approx(213.5)
    assert {p["budget"] for p in a} == {4096}
    assert max(lengths) + 4096 <= mix["engine"]["max_seq_len"]
    assert max(lengths) <= mix["engine"]["prompt_buckets"][-1]
    # more is offered than an engine that reserves prompt + budget in whole
    # pages can take: what it takes is its own number and is pinned nowhere
    page = mix["engine"]["page_size"]
    assert sum(-(-(n + 4096) // page) for n in lengths) > free_pages


# -- the end of set-up -------------------------------------------------------


def info(running, queued):
    return {"num_running_reqs": running, "num_queued_reqs": queued}


def test_set_up_ends_at_once_when_every_request_has_started():
    adm = steady_decode.Admission(18, settle_s=3.0)
    assert not adm.settled(0.0, 0, None)
    assert not adm.settled(1.0, 17, info(17, 0))     # one still prefilling
    assert adm.settled(1.1, 18, None)                # no server_info needed
    assert adm.settled(1.1, 18, info(18, 0))


def test_set_up_ends_when_the_engine_takes_no_more():
    adm = steady_decode.Admission(64, settle_s=3.0)
    seq = [(0.0, 0, info(0, 0)),        # nothing has reached the engine
           (1.0, 0, info(0, 64)),       # all queued, none started
           (2.0, 10, info(10, 53)),     # one is being prefilled: 63 of 64
           (3.0, 30, info(31, 33)),     # the 31st runs, its token not seen
           (4.0, 31, info(31, 33)),     # accounted for: the clock starts
           (6.9, 31, info(31, 33))]
    assert [adm.settled(*s) for s in seq] == [False] * 6
    assert adm.settled(7.0, 31, info(31, 33))
    assert adm.settled(9.0, 31, info(31, 33))


def test_a_running_count_that_moves_restarts_the_settle_time():
    adm = steady_decode.Admission(64, settle_s=3.0)
    assert not adm.settled(0.0, 30, info(30, 34))
    assert not adm.settled(2.9, 30, info(30, 34))
    assert not adm.settled(3.5, 31, info(31, 33))    # one more went in
    assert not adm.settled(6.4, 31, info(31, 33))
    assert not adm.settled(6.45, 31, None)           # the server was silent
    assert not adm.settled(9.0, 31, info(31, 33))    # so the clock restarted
    assert adm.settled(12.0, 31, info(31, 33))


def test_correctness_samples_come_from_admitted_requests_with_enough_tokens():
    class R:
        def __init__(self, rid, rank):
            self.rid, self.rank = rid, rank
    reqs = [R(f"r{k}", k) for k in (5, 1, 9, 3, 7)]
    seen = {"r5": 600, "r1": 600, "r9": 100, "r3": 600, "r7": 512}
    got = steady_decode.pick_by_length(reqs, seen, 512, [0.0, 0.5, 1.0])
    assert [r.rank for r in got] == [1, 5, 7]   # r9 has too few tokens
    assert steady_decode.pick_by_length(reqs, seen, 1000, [0.0, 1.0]) == []


def test_kv_pool_written_is_contexts_over_pool_tokens():
    read = harness.load_reader("kv_pool_written")
    assert read({"kv_tokens_at_end": 91_000, "kv_pool_tokens": 2193 * 64}) \
        == pytest.approx(100 * 91_000 / 140_352)
    assert read({"kv_tokens_at_end": 0, "kv_pool_tokens": 140_352}) is None
    assert read({}) is None


# -- the pattern over a fake plane -------------------------------------------


class FakePlane:
    """An engine that admits the first ``holds`` requests in the order
    they reach it and streams ``per_line`` tokens to each every ``period``
    seconds; the rest wait. ``late`` admits one more that many seconds
    into the window; ``ends`` finishes the first request that many seconds
    into it."""

    class Req:
        def __init__(self, rid, rank, budget, prompt_len, t_submit):
            self.rid, self.rank, self.budget = rid, rank, budget
            self.prompt_len = prompt_len
            self.t_done = None
            self.n_seen, self.error = 0, ""
            self.tokens, self.logprobs, self.arrivals = [], [], []

    def __init__(self, mix, holds, late=None, ends=None):
        self.mix, self.seed, self.endpoint = mix, 5, "127.0.0.1:1"
        self.config = {"config": {"vocab_size": 512}}
        self.holds, self.late, self.ends = holds, late, ends
        self.phases, self.info_samples, self.clients = {}, [], []
        self.period, self.per_line = 0.02, 8
        self.batches = []           # the rids of each POST, in order
        self._arrived = []          # requests in the order they came
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._running = 0
        self._t_window = float("inf")
        self._engine = threading.Thread(target=self._decode, daemon=True)

    def num_pages(self):
        return 1001

    def mark(self, phase):
        self.phases[phase] = time.monotonic()

    def server_info(self):
        return info(self._running, len(self._arrived) - self._running)

    def stream(self, client, reqs, prompts):
        with self._lock:
            self.batches.append([r.rid for r in reqs])
            self._arrived += reqs
            if not self._engine.is_alive() and not self._stop.is_set():
                self._engine.start()
        self._stop.wait()

    def _decode(self):
        while not self._stop.wait(self.period):
            now = time.monotonic()
            n = self.holds + (self.late is not None
                              and now - self._t_window >= self.late)
            live = [r for r in self._arrived[:n] if r.t_done is None]
            for r in live:
                r.n_seen += self.per_line
                r.tokens += [1] * self.per_line
                r.logprobs += [-1.0] * self.per_line
                r.arrivals.append((now, self.per_line))
            if self.ends is not None and now - self._t_window >= self.ends:
                first = self._arrived[0]
                first.t_done = first.t_done or now
            self._running = len(live)

    def window(self, seconds, trace, counter, settle):
        t0 = self._t_window = time.monotonic()
        for _ in range(4):
            time.sleep(seconds / 4)
            self.info_samples.append((time.monotonic(), self.server_info()))
        t1 = time.monotonic()
        settle(t1)
        self._stop.set()
        return t0, t1, None, {}


def fake_mix(offered):
    mix = traffic.load_mix("rollout-short")
    mix = harness.rehearsal({"config": {}, "reference": "dense_gqa"}, mix)[1]
    return dict(mix, offered_requests=offered, settle_seconds=0.3,
                offer_spacing_seconds=0.0, warm_tokens=16,
                correct_positions=16)


def run_pattern(plane, seconds=0.6):
    try:
        return steady_decode.run(plane, seconds, False, None)
    finally:
        plane._stop.set()
        for t in plane.clients:
            t.join(timeout=5.0)


def test_the_pattern_times_what_the_engine_admitted_and_fails_nothing():
    plane = FakePlane(fake_mix(8), holds=5)
    out = run_pattern(plane)
    assert (out["attempted"], out["failed"], out["failures"]) == (8, 0, [])
    c = out["checks"]
    assert (c["offered"], c["admitted"], c["queued"]) == (8, 5, 3)
    assert len(plane.batches) == 1 and len(plane.batches[0]) == 8
    # 5 streams of 8 tokens every 20 ms; the 3 that wait count for nothing
    assert out["end_to_end"]["rollout_tok_s"] == pytest.approx(2000, rel=0.1)
    obs = out["observed"]
    started = [r for r in obs["requests"] if r.n_seen]
    assert len(started) == 5 and len(out["samples"]) == 2
    # contexts: prompts plus what each had generated when the window closed
    assert obs["kv_tokens_at_end"] >= sum(
        r.prompt_len + 16 + 8 * 25 for r in started)
    assert obs["kv_tokens_at_end"] <= sum(
        r.prompt_len + r.n_seen for r in started)
    assert obs["kv_pool_tokens"] == 1000 * plane.mix["engine"]["page_size"]
    # set-up lasted the settle time past the last admission, no longer
    assert plane.phases["warm"] - plane.phases["prefilled"] < 0.2


def test_every_request_admitted_ends_set_up_without_a_settle_time():
    plane = FakePlane(dict(fake_mix(6), settle_seconds=60.0), holds=6)
    out = run_pattern(plane, seconds=0.3)
    assert out["failed"] == 0 and out["checks"]["admitted"] == 6


@pytest.mark.parametrize("fault, why", [
    ({"late": 0.2}, "admitted inside the window"),
    ({"ends": 0.2}, "finished inside the window")])
def test_a_window_that_is_not_decode_only_fails_the_run(fault, why):
    plane = FakePlane(fake_mix(8), holds=5, **fault)
    out = run_pattern(plane)
    assert out["failed"] == 1 and out["failures"] == [why]
    out["checks"].update(reference={"ok": True}, kernels_ok=True,
                         engine_recoveries=0)
    assert harness.verdict(out, rehearse=True) is False


def test_spaced_offers_reach_the_engine_in_the_plan_s_order():
    plane = FakePlane(dict(fake_mix(8), offer_spacing_seconds=0.02), holds=5)
    out = run_pattern(plane, seconds=0.3)
    # a batch a request, in the order of the plan, so that the engine
    # admits the plan's first five whatever the manager's workers race
    assert plane.batches == [[f"long{i}"] for i in range(8)]
    plan = traffic.steady_plan(plane.mix, plane.seed, 512)
    assert out["checks"]["admitted_ranks"] == sorted(
        p["rank"] for p in plan[:5])
    assert out["failed"] == 0


def test_decode_step_ms_counts_whole_programs_only(monkeypatch):
    """The profiler's session starts and stops inside a program: the two
    it cuts are on the trace with the part of their time it saw."""
    modules = ([("jit_step(1)", -30.0, 970.0)]          # cut at the start
               + [("jit_step(1)", 940.0 + 1000.0 * i, 1000.0)
                  for i in range(8)]
               + [("jit_step(1)", 8940.0, 400.0),       # cut at the end
                  ("jit_prefill(2)", 2000.0, 10.0)])
    trace = {"window": (0.0, 9300.0), "host": {},
             "device": {"/device:TPU:0": {"ops": [], "modules": modules}}}
    monkeypatch.setattr(xspans, "load", lambda path=None: trace)
    obs = {"mix": {"engine": {"steps_per_dispatch": 8}}}
    read = harness.load_reader("decode_step_ms")
    assert read(obs) == pytest.approx(1e3 * 1000e-9 / 8)
    # counted with the cut ones it read (970 + 8000 + 400) / 10 programs
    trace["device"]["/device:TPU:0"]["modules"] = modules[-1:]
    assert read(obs) is None
