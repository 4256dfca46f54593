"""The life of a KV page (ARCHITECTURE.md "The life of a page"): admission
takes the PROMPT's pages, a row takes more as it writes its way into them
(``CBEngine._grow_rows``, before every decode dispatch, for everything the
dispatches in flight and that one can write), and when the pool has no more
the youngest row gives up slot and pages and comes back as a continuation
of itself (``_yield_row``): same rid, same stream, nothing streamed twice.

The engines are the tiny presets in float32 with pages of 8 tokens; pools
are sized so that the case at hand does or does not run out."""

import queue
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import decoder
from polyrl_tpu.rollout.cb_engine import CBEngine, STREAM_END
from polyrl_tpu.rollout.sampling import SamplingParams

PS = 8


@pytest.fixture(scope="module")
def dense():
    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def hybrid():
    cfg = decoder.get_config("hybrid-tiny", dtype=jnp.float32)
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def latent():
    """Latent attention in every layer: all pages, no K/V pair, no state
    (``tests/test_latent_moe.py``)."""
    cfg = decoder.get_config("mla-moe-tiny", dtype=jnp.float32)
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def cca():
    """Compressed convolutional attention in every layer: a K/V pair in
    pages AND convolution tails in the slot (``tests/test_cca.py``)."""
    cfg = decoder.get_config("cca-tiny", dtype=jnp.float32)
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def sambay():
    """The SambaY family: Mamba states and window rings in the slot, ONE
    paged K/V layer that the cross layers read (``tests/test_sambay.py``)."""
    cfg = decoder.get_config("sambay-tiny", dtype=jnp.float32)
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def mixed():
    """Laguna's family: full GQA layers in pages beside window layers in
    rings of the slot's, a head count and a rope a kind
    (``tests/test_mixed_gqa.py``)."""
    cfg = decoder.get_config("mixed-tiny", dtype=jnp.float32)
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def looped():
    """A looped model: one stack of ``gqa`` layers run three times a token,
    a page holding every pass's keys (``tests/test_looped.py``)."""
    cfg = decoder.get_config("ouro-tiny", dtype=jnp.float32)
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def sala():
    """MiniCPM-SALA's family: block-sparse layers whose pages carry pooled
    keys beside the K/V pair, among linear-attention layers with a float32
    state in the slot (``tests/test_sala.py``); the page is its block."""
    cfg = decoder.get_config("minicpm-sala-tiny", dtype=jnp.float32)
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


def _engine(model, **kw):
    cfg, params = model
    opts = dict(max_slots=4, page_size=PS, max_seq_len=128,
                prompt_buckets=(16, 32), num_pages=64,
                kv_cache_dtype=jnp.float32)
    opts.update(kw)
    return CBEngine(cfg, params, **opts)


def _prompts(n, length, seed=0, vocab=500):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, length).tolist() for _ in range(n)]


def _greedy(budget):
    return SamplingParams(temperature=0.0, max_new_tokens=budget,
                          stop_token_ids=())


def _drain(q, timeout=120.0):
    """A stream to its end: tokens, log-probs, every line's ``finished``,
    and how many terminal markers came."""
    toks, lps, fins, ends = [], [], [], 0
    while True:
        item = q.get(timeout=timeout)
        if item is STREAM_END:
            ends += 1
            try:  # nothing may follow the end
                extra = q.get(timeout=0.05)
                raise AssertionError(f"after STREAM_END: {extra!r}")
            except queue.Empty:
                return toks, lps, fins, ends
        toks += item["token_ids"]
        lps += item["logprobs"]
        fins.append(bool(item["finished"]))


def _wait(cond, what, timeout=60.0):
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.005)


def _books_balance(eng, start_free):
    """After ``stop()``: the ledger owns every page exactly once (all free,
    none double-booked) and the allocator is back where it began."""
    assert eng.allocator.free_count == start_free
    assert sorted(eng.allocator._free) == list(range(1, eng.num_pages))
    roles = eng.kvledger.role_counts()
    assert roles["free"] == start_free
    assert roles["active_decode"] == roles["prefix_cache_published"] == 0
    assert eng.kvledger.attributed_frac(
        eng.allocator.free_count, eng._cache_pages()) == 1.0
    assert eng.deck.attributed_frac() == 1.0


# -- admission takes the prompt's pages ----------------------------------------


@pytest.mark.parametrize("n_prompt,want", [(7, 1), (8, 2), (12, 2), (16, 3)])
def test_admission_takes_the_prompts_pages_not_the_answers(dense, n_prompt,
                                                           want):
    eng = _engine(dense)
    try:
        eng.submit("r", _prompts(1, n_prompt)[0], _greedy(100))
        eng._drain_queue()
        with eng._pool_lock:
            eng._admit()
        # ceil((prompt + 1) / page): the first decode step's write included
        assert eng.allocator.free_count == 63 - want
        assert int(np.count_nonzero(eng._page_table[0])) == want
        assert int(eng._budgets[0]) == 100  # the budget caps the tokens still
    finally:
        eng.stop()
    _books_balance(eng, 63)


def test_a_request_larger_than_the_whole_pool_is_refused_at_once(dense):
    eng = _engine(dense, num_pages=9)  # 8 pages: 64 tokens
    try:
        big, fits = _prompts(2, 12)
        q_big = eng.submit("big", big, _greedy(60))    # 72 tokens: never
        q_fit = eng.submit("fit", fits, _greedy(50))   # 62 tokens
        eng.start()
        item = q_big.get(timeout=60)
        assert item["finish_reason"] == "error" and "unsupported" in \
            item["error"]
        toks, *_ = _drain(q_fit)
        assert len(toks) == 50
    finally:
        eng.stop()
    _books_balance(eng, 8)


# -- growth covers every position the device writes ----------------------------


def _spy_writes(eng):
    """Record, at every decode dispatch, the host's page table as the
    dispatch is handed it and the device's lengths before and after: the
    positions the dispatch wrote for a row are ``[before, after)``."""
    seen = []
    get_step, get_spec = eng._get_step, eng._get_spec_step

    def spy(fn, i_table, i_seq, i_out_seq):
        def call(*args):
            table = np.asarray(args[i_table])
            before = np.asarray(args[i_seq]).copy()
            out = fn(*args)
            seen.append((table, before, np.asarray(out[i_out_seq]).copy()))
            return out
        return call

    # step: args (params, kp, vp, rng, page_table, seq_lens, ...), out[6]
    eng._get_step = lambda *a, **k: spy(get_step(*a, **k), 4, 5, 6)
    # spec: args (params, kp, vp, rng, tok_buf, page_table, seq_lens, ...),
    # out[8]; a round writes spec_tokens + 1 positions from its length
    eng._get_spec_step = lambda *a, **k: spy(get_spec(*a, **k), 5, 6, 8)
    return seen


@pytest.mark.parametrize("kind", ["plain", "grouped", "spec"])
def test_pages_cover_every_position_written_with_the_pipeline_full(dense,
                                                                   kind):
    opts = dict(pipeline_depth=4, steps_per_dispatch=3)
    if kind == "spec":
        opts.update(spec_tokens=2, spec_rounds=2)
    eng = _engine(dense, **opts)
    seen = _spy_writes(eng)
    extra = eng.spec_tokens  # a verify writes its drafts past the accepted
    try:
        if kind == "grouped":
            [p] = _prompts(1, 20)
            outs = [eng.submit(f"g{i}", p, _greedy(40), group_id="g",
                               group_size=3) for i in range(3)]
        else:
            outs = [eng.submit(f"r{i}", p, _greedy(40))
                    for i, p in enumerate(_prompts(3, 12))]
        # by hand: nothing lands but where the loop asks, so the pipeline
        # stands full (4 outstanding) at every dispatch after the fourth
        for _ in range(40):
            eng._loop_iter()
            assert eng._outstanding() <= 4
        full = 0
        for table, before, after in seen:
            for row in np.flatnonzero(after[:-1] > before[:-1]):
                last = int(after[row]) - 1 + extra
                last = min(last, 12 + 40 - 2 if kind != "grouped"
                           else 20 + 40 - 2)
                held = int(np.count_nonzero(table[row]))
                assert held * PS > last, (row, before[row], after[row], held)
            full += 1
        assert full >= 10
        if kind == "grouped":
            assert eng.grouped_decode_dispatches > 0
        c = eng.profiler.counters()
        assert c["slot_yields"] == 0
    finally:
        eng.stop()
    for q in outs:
        _drain(q)
    _books_balance(eng, 63)


def test_the_table_goes_up_seldom_while_the_pool_is_roomy(dense):
    """When one row needs a page every row is topped up ahead by its even
    share of half the free pages, so in a roomy pool the table is uploaded
    a few times, not with every dispatch, and what a row holds beyond what
    it has dispatched never passes that share (and its budget); a pool with
    nothing to spare still serves the bare need (the cases below)."""
    eng = _engine(dense, pipeline_depth=2, steps_per_dispatch=2)
    uploads, spare = [], []
    real = eng._page_table_dev
    eng._page_table_dev = lambda: (uploads.append(
        eng.profiler.counters()["decode_dispatches"]), real())[1]
    try:
        outs = [eng.submit(f"r{i}", p, _greedy(100))
                for i, p in enumerate(_prompts(3, 9))]
        for _ in range(40):
            eng._loop_iter()
            rows = np.flatnonzero(eng._active)
            reach = eng._seq_lens[rows] + eng._inflight_tok[rows]
            held = np.count_nonzero(eng._page_table[rows], axis=1)
            spare.append(int((held - -(-reach // PS)).max()))
        assert eng.profiler.counters()["decode_dispatches"] == 40
        during = [u for u in uploads if u > 0]
        assert 1 <= len(during) <= 4, uploads
        # 57 pages free at the first growth: a share of 57 // 6 = 9 pages
        assert 0 <= min(spare) and max(spare) <= 9 + 1
        # 9 + 100 tokens: 14 pages a row at the very most
        assert eng.kvledger.page_allocs <= 3 * 14
    finally:
        eng.stop()
    for q in outs:
        _drain(q)
    _books_balance(eng, 63)


def test_a_row_that_starts_alone_leaves_the_waiting_their_share(dense):
    """One request is taken in and decodes alone while five more of its
    batch stand in the engine's queue (they arrived during its prefill):
    the pages it takes ahead are its share among the six, not half the
    pool, and the six then run to their budgets without a yield."""
    eng = _engine(dense, max_slots=6, num_pages=50, max_seq_len=256,
                  pipeline_depth=2, steps_per_dispatch=8)
    try:
        first, *rest = _prompts(6, 15)      # a token short of two pages
        outs = [eng.submit("r0", first, _greedy(200))]
        real = eng._prefill_request

        def while_it_prefills(*args, **kw):
            outs.extend(eng.submit(f"r{i + 1}", p, _greedy(40))
                        for i, p in enumerate(rest))
            eng._prefill_request = real
            return real(*args, **kw)

        eng._prefill_request = while_it_prefills
        eng._loop_iter()        # its prefill, then its first dispatch
        assert eng._queue.qsize() == 5 and eng._active.sum() == 1
        # 47 pages free: a share of 47 // 12 = 3 pages ahead of the bare
        # need of one; alone it took 47 // 2, and the five that need 25
        # more between them found 24
        assert np.count_nonzero(eng._page_table[0]) <= 2 + 1 + 3 + 1
        eng.start()
        for q, n in zip(outs, [200] + [40] * 5):
            toks, _lps, fins, ends = _drain(q)
            assert len(toks) == n and ends == 1 and fins[-1]
        assert eng.profiler.counters()["slot_yields"] == 0
    finally:
        eng.stop()
    _books_balance(eng, 49)


def test_no_row_is_given_pages_past_its_budget(dense):
    eng = _engine(dense, pipeline_depth=8, steps_per_dispatch=8)
    try:
        [p] = _prompts(1, 12)
        out = eng.generate([p], _greedy(10), timeout=120.0)[0]
        assert len(out["token_ids"]) == 10
        # 12 + 10 tokens: positions 0..20 are written, 3 pages; the
        # run-ahead of 8 x 8 tokens would have asked for 11
        assert eng.kvledger.page_allocs == 3
    finally:
        eng.stop()
    _books_balance(eng, 63)


# -- when the pool runs out, the youngest row yields ---------------------------


def _run(model, num_pages, budget=60, n=4, length=12, **kw):
    eng = _engine(model, num_pages=num_pages, **kw)
    try:
        outs = [eng.submit(f"r{i}", p, _greedy(budget))
                for i, p in enumerate(_prompts(n, length))]
        eng.start()
        streams = [_drain(q) for q in outs]
        return streams, eng.profiler.counters(), eng
    finally:
        eng.stop()


@pytest.mark.parametrize("kind", ["cached", "recomputed", "spec",
                                  "latent-cached", "latent-recomputed",
                                  "looped-cached", "looped-recomputed"])
def test_a_pool_too_small_makes_the_youngest_yield_and_come_back(
        request, dense, kind):
    """The dense model, (``latent-``) the model whose cache is a latent
    pool in every layer and (``looped-``) the one whose page holds three
    passes' keys: a page is a page whatever it holds."""
    family, _, how = kind.rpartition("-")
    opts = {"cached": {}, "recomputed": {"enable_prefix_cache": False},
            "spec": {"spec_tokens": 2}}[how]
    model = request.getfixturevalue(family) if family else dense
    # 4 rows x (12 + 60 tokens) write 36 pages; 19 are there
    tight, c_tight, eng = _run(model, 20, **opts)
    roomy, c_roomy, _ = _run(model, 64, **opts)
    assert c_tight["slot_yields"] > 0 == c_roomy["slot_yields"]
    # how many pages rows grew into is no counter: the tables above say it
    assert "pages_grown" not in c_roomy
    for (toks, lps, fins, ends), (r_toks, r_lps, *_r) in zip(tight, roomy):
        # exactly its budget, each token once with its log-prob, one
        # terminal line and one end, none in between
        assert len(toks) == len(lps) == 60
        assert fins == [False] * 59 + [True] and ends == 1
        assert toks == r_toks
        np.testing.assert_allclose(lps, r_lps, atol=5e-4)
    if kind.endswith("cached"):
        # the row's full pages were published as it left and its re-entry
        # attached to them: it did not prefill its whole input again
        assert eng.salvage_published_pages > 0
        assert eng.deck.cached_prompt_tokens > 0
    assert eng.kvledger.freed_by_cause["yield"] > 0
    _books_balance(eng, 19)


def test_the_row_that_yields_is_the_one_with_least_to_redo(dense):
    eng = _engine(dense, num_pages=14, steps_per_dispatch=4,
                  pipeline_depth=2)
    yielded = []
    real = eng._yield_row
    eng._yield_row = lambda slot: (yielded.append(
        (eng._slots[slot].req.rid,
         {eng._slots[i].req.rid: int(eng._n_generated[i])
          for i in np.flatnonzero(eng._active)})), real(slot))[1]
    try:
        old, young = _prompts(2, 12)
        q_old = eng.submit("old", old, _greedy(70))
        eng.start()
        _wait(lambda: int(eng._n_generated[0]) >= 20, "the old row's lead")
        q_young = eng.submit("young", young, _greedy(70))
        a, b = _drain(q_old), _drain(q_young)
        assert len(a[0]) == len(b[0]) == 70
        assert yielded and all(rid == "young" for rid, _ in yielded)
        for _rid, gen in yielded:
            assert gen["young"] < gen["old"]
    finally:
        eng.stop()
    _books_balance(eng, 13)


@pytest.mark.parametrize("family", ["hybrid", "cca", "sambay", "mixed",
                                    "sala"])
def test_the_tiny_hybrid_rebuilds_a_yielded_rows_state(request, family):
    """A model with a state in its slot (``hybrid``: KDA states beside a
    latent pool; ``cca``: convolution tails beside a K/V pair in the same
    layer; ``sambay``: Mamba states and window rings beside one shared K/V
    layer; ``mixed``: rope'd window layers' rings beside full layers'
    pages; ``sala``: linear-attention states beside sparse layers' pages
    and their pooled keys) re-enters from token 0: the chunks recompute the slot's rows
    with the pages, and nothing is streamed twice."""
    hybrid = request.getfixturevalue(family)
    opts = dict(prompt_buckets=(16, 64), prefill_chunk=16,
                steps_per_dispatch=4)
    tight, c_tight, eng = _run(hybrid, 16, budget=40, n=3, **opts)
    roomy, c_roomy, _ = _run(hybrid, 80, budget=40, n=3, **opts)
    assert eng.stateful and eng.prefix_cache is None
    assert c_tight["slot_yields"] > 0 == c_roomy["slot_yields"]
    # the continuation (12 + up to 39 tokens) went in 16-token chunks
    assert eng.chunk_dispatches > 0
    for (toks, lps, fins, ends), (r_toks, r_lps, *_r) in zip(tight, roomy):
        assert len(toks) == 40 and ends == 1 and fins[-1] and \
            not any(fins[:-1])
        assert toks == r_toks
        np.testing.assert_allclose(lps, r_lps, atol=5e-4)
    _books_balance(eng, 15)


def test_a_continuation_longer_than_the_largest_bucket_goes_in_chunks(dense):
    """No ``prefill_chunk`` and buckets up to 32: a row that yields with 12
    + 50 tokens comes back as an input of 62, which admission used to
    refuse outright; it goes in chunks of the largest bucket's pages."""
    tight, c, eng = _run(dense, 26, budget=90, n=3,
                         prompt_buckets=(16, 32), enable_prefix_cache=False)
    roomy, *_ = _run(dense, 64, budget=90, n=3,
                     prompt_buckets=(16, 32), enable_prefix_cache=False)
    assert c["slot_yields"] > 0 and eng.chunk_dispatches > 0
    for (toks, _l, _f, ends), (r_toks, *_r) in zip(tight, roomy):
        assert len(toks) == 90 and ends == 1 and toks == r_toks
    # a prompt that long is admitted the same way
    eng = _engine(dense, prompt_buckets=(16, 32))
    try:
        [p] = _prompts(1, 70)
        out = eng.generate([p], _greedy(6), timeout=120.0)[0]
        assert out["finish_reason"] == "length" and eng.chunk_dispatches == 2
    finally:
        eng.stop()
    _books_balance(eng, 63)


# -- headroom: who may come in, and no yield-admit-yield loop ------------------


def test_no_admission_while_the_headroom_test_fails(dense):
    """2 rows run; a third comes in only if, its 2 prompt pages taken, 3
    pages are left (one a row). 9 pages to hand out: 4 held, 5 free, the
    third comes in. 8 pages: it waits, though its prompt's pages are
    there."""
    for num_pages, admitted in ((10, 3), (9, 2)):
        eng = _engine(dense, num_pages=num_pages, steps_per_dispatch=1,
                      pipeline_depth=1)
        try:
            qs = []
            for i, p in enumerate(_prompts(3, 12)):
                qs.append(eng.submit(f"r{i}", p, _greedy(30)))
                eng._loop_iter()
            assert int(eng._active.sum()) == admitted
            assert len(eng._pending) == 3 - admitted
            assert eng._admission_waiting == (admitted == 2)
            if admitted == 2:
                assert eng.allocator.free_count >= 2  # its pages are there
                assert eng.profiler.counters()["admission_deferrals"] > 0
        finally:
            eng.stop()
        for q in qs:
            _drain(q)
        _books_balance(eng, num_pages - 1)


def test_a_lone_request_needs_no_headroom(dense):
    eng = _engine(dense, num_pages=9)  # 8 pages = 12 + 52 tokens exactly
    try:
        [p] = _prompts(1, 12)
        out = eng.generate([p], _greedy(52), timeout=120.0)[0]
        assert len(out["token_ids"]) == 52
        assert eng.profiler.counters()["slot_yields"] == 0
    finally:
        eng.stop()
    _books_balance(eng, 8)


def test_a_row_that_yields_waits_for_headroom_and_the_loop_does_not_thrash(
        dense):
    """Every event in order: a row that yields is not prefilled again
    before a row has ENDED (only that gives the pool its headroom back),
    and yields stay far below the dispatches."""
    eng = _engine(dense, num_pages=20, enable_prefix_cache=False)
    events = []
    real_yield, real_final = eng._yield_row, eng._finalize
    real_one = eng._prefill_request
    eng._yield_row = lambda slot: (events.append(
        ("yield", eng._slots[slot].req.rid)), real_yield(slot))[1]

    def finalize(slot, cause="finalize"):
        if cause == "finalize" and eng._slots[slot] is not None:
            events.append(("end", eng._slots[slot].req.rid))
        return real_final(slot, cause=cause)

    def prefill(slot, req, *a, **k):
        events.append(("prefill", req.rid, req.resumed))
        return real_one(slot, req, *a, **k)

    eng._finalize, eng._prefill_request = finalize, prefill
    try:
        outs = [eng.submit(f"r{i}", p, _greedy(60))
                for i, p in enumerate(_prompts(4, 12))]
        eng.start()
        streams = [_drain(q) for q in outs]
        assert all(len(s[0]) == 60 for s in streams)
    finally:
        eng.stop()
    c = eng.profiler.counters()
    yields = [e for e in events if e[0] == "yield"]
    assert 0 < len(yields) == c["slot_yields"] <= 4
    assert c["slot_yields"] * 5 < c["decode_dispatches"]
    for at, ev in enumerate(events):
        if ev[0] == "prefill" and ev[2] > 0:    # a continuation comes back
            left = max(i for i in range(at)
                       if events[i] == ("yield", ev[1]))
            assert any(e[0] == "end" for e in events[left:at]), events
    _books_balance(eng, 19)


# -- the per-layer metric that reads the counters ------------------------------


@pytest.mark.parametrize("samples,want", [
    ([{"decode_dispatches": 10, "slot_yields": 0},
      {"decode_dispatches": 190, "slot_yields": 0}], 0.0),
    ([{"decode_dispatches": 10, "slot_yields": 1},
      {"decode_dispatches": 110, "slot_yields": 3}], 2.0),
    ([{"occupancy": 1.0},
      {"decode_dispatches": 10, "slot_yields": 0},
      {"decode_dispatches": 60, "slot_yields": 1}], 2.0),
    # a parent's engine has no such counter; nothing dispatched
    ([{"decode_dispatches": 10}, {"decode_dispatches": 190}], None),
    ([{"decode_dispatches": 10, "slot_yields": 1},
      {"decode_dispatches": 10, "slot_yields": 1}], None),
])
def test_yield_share_of_a_server_info_pair(samples, want):
    from benchmark.lib import harness

    got = harness.load_reader("yield_share")({"server_info": samples})
    assert got == (want if want is None else pytest.approx(want))
