"""Continuous-batching engine: parity vs the fused v0 engine, admission,
aborts, budgets, page exhaustion."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import decoder
from polyrl_tpu.rollout.cb_engine import CBEngine, PageAllocator
from polyrl_tpu.rollout.engine import RolloutEngine
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


def test_greedy_parity_with_fused_engine(tiny):
    cfg, params = tiny
    eng0 = RolloutEngine(cfg, params, batch_buckets=(4,), prompt_buckets=(16,))
    cbe = _mk_engine(tiny)
    sp = SamplingParams(temperature=0.0, max_new_tokens=12, stop_token_ids=(7,))
    prompts = [[5, 3, 9, 2], [11, 4], [100, 101, 102, 103, 104, 105]]

    ref = eng0.generate(prompts, sp)
    out = cbe.generate(prompts, sp)
    cbe.stop()

    for r, o in zip(ref, out):
        # the two engines use different attention codepaths (dense einsum vs
        # paged reference/Pallas), so greedy argmax may legitimately diverge
        # at a near-tie on random weights; compare token-exactly up to the
        # first divergence, then require the divergence to BE a near-tie
        # (logprob gap within numerical noise), never silently truncate
        rt, ot = list(r.output_ids), o["token_ids"]
        rl, ol = list(r.output_token_logprobs), o["logprobs"]
        n = min(len(rt), len(ot))
        for j in range(n):
            if rt[j] != ot[j]:
                assert abs(rl[j] - ol[j]) < 5e-3, (
                    f"divergence at {j} is not a near-tie: "
                    f"{rt[j]}@{rl[j]} vs {ot[j]}@{ol[j]}")
                break
            np.testing.assert_allclose(rl[j], ol[j], rtol=0, atol=5e-3)
        else:
            assert len(rt) == len(ot)
            assert r.finish_reason == o["finish_reason"]


def test_mixed_sampling_admission(tiny):
    cbe = _mk_engine(tiny)
    cbe.start()
    sp_greedy = SamplingParams(temperature=0.0, max_new_tokens=6)
    sp_topp = SamplingParams(temperature=0.8, top_p=0.9, max_new_tokens=6)
    sp_topk = SamplingParams(temperature=1.0, top_k=5, max_new_tokens=6)
    outs = [cbe.submit(f"r{i}", [3 + i, 7], sp)
            for i, sp in enumerate([sp_greedy, sp_topp, sp_topk, sp_greedy])]
    from polyrl_tpu.rollout.cb_engine import STREAM_END
    for q in outs:
        toks = []
        while True:
            item = q.get(timeout=60)
            if item is STREAM_END:
                break
            toks.extend(item["token_ids"])
            if item["finished"]:
                assert item["finish_reason"] in ("stop", "length")
        assert len(toks) == 6
    cbe.stop()


def test_abort_mid_generation(tiny):
    # budget must exceed the default run-ahead window
    # (pipeline_depth * steps_per_dispatch tokens) or the stream can finish
    # entirely in flight before the abort cuts in; the abort terminal must
    # arrive promptly even with the whole window outstanding
    cbe = _mk_engine(tiny, max_seq_len=512, num_pages=128)
    cbe.start()
    ev = threading.Event()
    sp = SamplingParams(temperature=0.0, max_new_tokens=400)
    out = cbe.submit("abort-me", [5, 6, 7], sp, abort=ev)
    from polyrl_tpu.rollout.cb_engine import STREAM_END
    # read a couple tokens, then abort
    first = out.get(timeout=60)
    assert first["token_ids"]
    ev.set()
    seen_abort = False
    while True:
        item = out.get(timeout=60)
        if item is STREAM_END:
            break
        if item.get("finish_reason") == "abort":
            seen_abort = True
    assert seen_abort
    cbe.stop()
    # slot must be reclaimed
    assert all(s is None for s in cbe._slots)
    assert cbe.allocator.free_count == cbe.num_pages - 1


def test_budget_and_long_prompt_errors(tiny):
    cbe = _mk_engine(tiny)
    cbe.start()
    from polyrl_tpu.rollout.cb_engine import STREAM_END
    # prompt longer than a slot holds → error
    out = cbe.submit("too-long", list(range(1, 129)),
                     SamplingParams(max_new_tokens=4))
    item = out.get(timeout=60)
    assert item["finish_reason"] == "error"
    assert out.get(timeout=10) is STREAM_END
    # one longer than the largest bucket goes in chunks of that bucket (a
    # row that gave up its pages comes back with such an input)
    out1 = cbe.submit("over-bucket", list(range(1, 41)),
                      SamplingParams(temperature=0.0, max_new_tokens=4))
    n = 0
    while True:
        item = out1.get(timeout=120)
        if item is STREAM_END:
            break
        assert item["finish_reason"] in ("", "length")
        n += len(item["token_ids"])
    assert n == 4 and cbe.chunk_dispatches == 1
    # budget clamped by max_seq_len
    out2 = cbe.submit("clamped", [1, 2], SamplingParams(temperature=0.0,
                                                        max_new_tokens=10_000))
    n = 0
    while True:
        item = out2.get(timeout=120)
        if item is STREAM_END:
            break
        n += len(item["token_ids"])
    assert n <= cbe.max_seq_len - 2
    cbe.stop()


def test_page_exhaustion_queues_requests(tiny):
    # pool sized so only ~1 request fits at a time; all must still finish
    cbe = _mk_engine(tiny, num_pages=7, max_slots=4, max_seq_len=32)
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)
    res = cbe.generate([[2, 3], [4, 5], [6, 7], [8, 9]], sp, timeout=120)
    cbe.stop()
    assert len(res) == 4
    for r in res:
        assert len(r["token_ids"]) >= 1
        assert r["finish_reason"] in ("stop", "length")
    assert cbe.allocator.free_count == 6


def test_page_allocator():
    a = PageAllocator(10)
    p1 = a.alloc(4)
    p2 = a.alloc(5)
    assert p1 is not None and p2 is not None
    assert a.alloc(1) is None
    assert 0 not in p1 + p2  # null page never handed out
    a.free(p1)
    assert a.alloc(4) is not None


def test_weight_hot_swap_changes_output(tiny):
    cfg, params = tiny
    cbe = _mk_engine(tiny)
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)
    out1 = cbe.generate([[5, 3, 9]], sp)[0]
    params2 = decoder.init_params(jax.random.PRNGKey(42), cfg)
    cbe.update_weights(params2, version=7)
    assert cbe.weight_version == 7
    out2 = cbe.generate([[5, 3, 9]], sp)[0]
    cbe.stop()
    assert out1["token_ids"] != out2["token_ids"]


def test_release_resume_memory(tiny):
    cbe = _mk_engine(tiny)
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    cbe.generate([[1, 2, 3]], sp)
    cbe.release_memory()
    assert cbe._pools is None
    cbe.resume_memory()
    assert cbe._pools is not None
    res = cbe.generate([[1, 2, 3]], sp)
    cbe.stop()
    assert res[0]["finish_reason"] in ("stop", "length")


def test_slot_reuse_stale_emit_guard(tiny):
    """Regression (ABA): a queued 'step' entry dispatched for an old request
    must never emit into a NEW request admitted into the same slot after the
    old one finalized via the device-done path (which leaves _dev_state
    valid, so admission does not drain the queue). Guarded by the per-slot
    generation counter recorded in each dispatched entry."""
    from polyrl_tpu.rollout.cb_engine import STREAM_END

    cbe = _mk_engine(tiny, max_slots=1)
    cbe.pipeline_depth = 8  # keep dispatches queued until we drain explicitly
    sp = SamplingParams(temperature=0.0, max_new_tokens=2, stop_token_ids=())

    qa = cbe.submit("a", [5, 3, 9], sp)
    cbe._drain_queue()
    with cbe._pool_lock:
        cbe._admit()       # prefill A queued; budget=2 -> one decode step left
        cbe._step_once()   # step1: device-side done (n_gen hits budget)
        # simulate a stop-token-style early device finish: the device is
        # already done but the host mirror still sees remaining budget, so
        # the run-ahead tail cutoff does not stop the next dispatch
        cbe._budgets[0] = 100
        cbe._step_once()   # step2: host mirror lags -> STALE dispatch for slot 0
    assert len(cbe._emit_q) == 3

    # drain all but the stale step2 entry: A finishes and slot 0 is finalized
    # via device_done=True, i.e. WITHOUT invalidating the device state
    cbe._drain_emit_q(keep=1)
    assert cbe._slots[0] is None and len(cbe._emit_q) == 1
    a_tokens = []
    while True:
        item = qa.get_nowait()
        if item is STREAM_END:
            break
        a_tokens.extend(item["token_ids"])
    assert len(a_tokens) == 2

    # admit B into the reused slot 0 while the stale entry is still queued
    qb = cbe.submit("b", [7, 1], sp)
    cbe._drain_queue()
    with cbe._pool_lock:
        cbe._admit()
    assert cbe._slots[0] is not None and len(cbe._emit_q) == 2

    cbe._drain_emit_q()  # stale step2 drains FIRST and must be skipped
    first = qb.get_nowait()
    # without the generation guard the stale entry emits a pad token with
    # logprob 0.0 into B's stream and bumps the host mirrors out of sync
    assert len(first["token_ids"]) == 1
    assert not (first["token_ids"][0] == cbe.pad_token_id
                and first["logprobs"][0] == 0.0)
    assert int(cbe._n_generated[0]) == 1     # only B's prefill token counted
    assert int(cbe._seq_lens[0]) == 2       # B's prompt length, un-bumped
    cbe.stop()


def test_multi_step_decode_stop_and_budget_mid_scan():
    """Multi-step decode (steps_per_dispatch > 1): stop tokens and budget
    exhaustion landing MID-scan must terminate streams at exactly the right
    token — the pad tail of the fused scan is never emitted — and the freed
    pages must be safely reusable by later admissions (inactive slots write
    to the null page only)."""
    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    eng = CBEngine(cfg, params, pad_token_id=0, kv_cache_dtype=jnp.float32,
                   max_slots=4, page_size=8, max_seq_len=64,
                   prompt_buckets=(16,), steps_per_dispatch=4,
                   enable_prefix_cache=False)
    k1 = CBEngine(cfg, params, pad_token_id=0, kv_cache_dtype=jnp.float32,
                  max_slots=4, page_size=8, max_seq_len=64,
                  prompt_buckets=(16,), steps_per_dispatch=1,
                  enable_prefix_cache=False)
    prompts = [[7, 3, 9], [5, 5, 2, 8], [1, 2, 3, 4, 5]]
    # greedy: K-fused decode must produce EXACTLY the K=1 stream, including
    # budgets (6, not a multiple of K) that end mid-scan
    sp = SamplingParams(temperature=0.0, max_new_tokens=6, stop_token_ids=())
    outs_k = eng.generate(prompts, sp)
    outs_1 = k1.generate(prompts, sp)
    for a, b in zip(outs_k, outs_1):
        assert a["token_ids"] == b["token_ids"]
        assert len(a["token_ids"]) == 6
        assert a["finish_reason"] == "length"
    # greedy with the first generated token as the stop token → stream ends
    # at token 1 even though the scan ran K=4 steps
    stop_tok = outs_k[0]["token_ids"][0]
    sp_stop = SamplingParams(temperature=0.0, max_new_tokens=6,
                             stop_token_ids=(stop_tok,))
    out_stop = eng.generate([prompts[0]], sp_stop)[0]
    assert out_stop["token_ids"] == [stop_tok]
    assert out_stop["finish_reason"] == "stop"
    # page-reuse safety: run several generations so freed pages recycle
    # through new admissions while older slots' device rows are stale; the
    # greedy outputs must stay reproducible (no KV corruption)
    ref = eng.generate(prompts, sp)
    for _ in range(3):
        again = eng.generate(prompts, sp)
        for a, b in zip(again, ref):
            assert a["token_ids"] == b["token_ids"]
    eng.stop()
    k1.stop()


def test_cb_engine_tensor_parallel_matches_single_device():
    """TP serving (the reference's SGLang --tp-size role): the CB engine on
    a tp=2 mesh — params over (fsdp, tp), KV pools head-sharded — produces
    EXACTLY the single-device greedy output."""
    import jax

    from polyrl_tpu.models import decoder
    from polyrl_tpu.parallel import mesh as meshlib
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    kw = dict(pad_token_id=0, kv_cache_dtype=jnp.float32, max_slots=4,
              page_size=8, max_seq_len=64, prompt_buckets=(8,), num_pages=64)
    sp = SamplingParams(temperature=0.0, max_new_tokens=8, stop_token_ids=())
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7]]

    ref_engine = CBEngine(cfg, params, **kw)
    try:
        ref = [o["token_ids"] for o in
               ref_engine.generate(prompts, sp, timeout=120.0)]
    finally:
        ref_engine.stop()

    mesh = meshlib.make_mesh(meshlib.MeshConfig(fsdp=1, tp=2),
                             jax.devices()[:2])
    tp_engine = CBEngine(cfg, params, mesh=mesh, **kw)
    try:
        assert tp_engine.params["layers"]["wq"].sharding.spec[-1] == "tp"
        assert tp_engine._pools[0][0].sharding.spec[0] == "tp"
        got = [o["token_ids"] for o in
               tp_engine.generate(prompts, sp, timeout=120.0)]
    finally:
        tp_engine.stop()
    assert got == ref, (got, ref)


def test_cb_engine_tp_quantized_actually_shards():
    """Regression: a QuantWeight tree must tp-shard (the path-walk spec
    lookup used to silently fall back to replicated on QuantWeight nodes),
    update_weights must preserve the sharded layout, and tp must divide
    the head counts."""
    import jax
    import pytest as _pytest

    from polyrl_tpu.models import decoder
    from polyrl_tpu.models.quant import quantize_params
    from polyrl_tpu.parallel import mesh as meshlib
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    qparams = quantize_params(decoder.init_params(jax.random.PRNGKey(0), cfg))
    mesh = meshlib.make_mesh(meshlib.MeshConfig(fsdp=1, tp=2),
                             jax.devices()[:2])
    kw = dict(pad_token_id=0, kv_cache_dtype=jnp.float32, max_slots=4,
              page_size=8, max_seq_len=64, prompt_buckets=(8,), num_pages=64)
    engine = CBEngine(cfg, qparams, mesh=mesh, **kw)
    try:
        wq = engine.params["layers"]["wq"]
        assert wq.q.sharding.spec[-1] == "tp", wq.q.sharding
        assert wq.scale.sharding.spec[-1] == "tp", wq.scale.sharding
        sp = SamplingParams(temperature=0.0, max_new_tokens=5,
                            stop_token_ids=())
        out = engine.generate([[1, 2, 3]], sp, timeout=120.0)
        assert len(out[0]["token_ids"]) == 5
        # an in-process push of a host-side tree is re-sharded, not taken raw
        engine.update_weights(jax.device_get(engine.params), version=7)
        assert engine.params["layers"]["wq"].q.sharding.spec[-1] == "tp"
    finally:
        engine.stop()

    with _pytest.raises(ValueError, match="num_kv_heads"):
        CBEngine(decoder.get_config("tiny", num_kv_heads=1, num_heads=4,
                                    dtype=jnp.float32),
                 decoder.init_params(
                     jax.random.PRNGKey(0),
                     decoder.get_config("tiny", num_kv_heads=1, num_heads=4,
                                        dtype=jnp.float32)),
                 mesh=mesh, **kw)


def _mk_engines_for_chunking(prefill_chunk):
    import jax

    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.cb_engine import CBEngine

    cfg = decoder.get_config("tiny", dtype=jnp.float32)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    kw = dict(pad_token_id=0, kv_cache_dtype=jnp.float32, max_slots=4,
              page_size=8, max_seq_len=96, prompt_buckets=(8, 16, 64),
              num_pages=96)
    return cfg, CBEngine(cfg, params, prefill_chunk=prefill_chunk, **kw), kw, params


def test_chunked_prefill_matches_unchunked():
    """A long prompt admitted chunk-by-chunk (extend dispatches + final
    suffix admission) produces EXACTLY the single-dispatch greedy output."""
    import jax

    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    rng = np.random.default_rng(11)
    cfg, chunked, kw, params = _mk_engines_for_chunking(prefill_chunk=8)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (24, 40, 5)]  # 2 chunked (3/5 chunks), 1 direct
    sp = SamplingParams(temperature=0.0, max_new_tokens=8, stop_token_ids=())
    try:
        got = [o["token_ids"] for o in chunked.generate(prompts, sp,
                                                        timeout=180.0)]
    finally:
        chunked.stop()
    plain = CBEngine(cfg, params, **kw)
    try:
        ref = [o["token_ids"] for o in plain.generate(prompts, sp,
                                                      timeout=180.0)]
    finally:
        plain.stop()
    assert got == ref, (got, ref)


def test_chunked_prefill_interleaves_with_decode():
    """While a long prompt chunks in, an already-running stream keeps
    emitting tokens — the engine's counters must show chunk dispatches AND
    decode dispatches interleaved (neither starves)."""
    import time as _time

    cfg, engine, kw, params = _mk_engines_for_chunking(prefill_chunk=8)
    from polyrl_tpu.rollout.sampling import SamplingParams

    rng = np.random.default_rng(12)
    engine.start()
    sp_long = SamplingParams(temperature=0.0, max_new_tokens=24,
                             stop_token_ids=())
    # request 1: short prompt, long generation → decoding while...
    q1 = engine.submit("r1", rng.integers(1, cfg.vocab_size, 5).tolist(),
                       sp_long)
    _time.sleep(0.3)  # let it admit and start decoding
    # ...request 2's 40-token prompt chunks in (5 chunks of 8)
    q2 = engine.submit("r2", rng.integers(1, cfg.vocab_size, 40).tolist(),
                       sp_long)
    from polyrl_tpu.rollout.cb_engine import STREAM_END

    done = 0
    t0 = _time.monotonic()
    toks = {"r1": 0, "r2": 0}
    while done < 2 and _time.monotonic() - t0 < 180:
        for name, q in (("r1", q1), ("r2", q2)):
            try:
                item = q.get(timeout=0.05)
            except Exception:  # noqa: BLE001 — queue.Empty
                continue
            if item is STREAM_END:
                done += 1
            elif isinstance(item, dict):
                toks[name] += len(item.get("token_ids", []))
    rep = engine.profiler.counters()
    engine.stop()
    assert toks["r1"] == 24 and toks["r2"] == 24, toks
    # 40 tokens in chunks of 8: four mid-chunks, then the final chunk
    # through the suffix path
    assert engine.chunk_dispatches >= 4, engine.chunk_dispatches
    assert rep["decode_dispatches"] >= 3, rep
    assert rep["decode_steps_done"] >= 3, rep


def test_chunked_prefill_abort_frees_pages():
    """Abort fires MID-JOB (after ≥1 chunk dispatched) so the chunk-job
    abort branch — not _collect_wave's pre-admission check — must free the
    slot, pages, and cache refs."""
    import threading
    import time as _time

    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg, engine, kw, params = _mk_engines_for_chunking(prefill_chunk=8)
    engine.start()
    rng = np.random.default_rng(13)
    free0 = engine.allocator.free_count
    abort = threading.Event()
    q = engine.submit("rA", rng.integers(1, cfg.vocab_size, 40).tolist(),
                      SamplingParams(temperature=0.0, max_new_tokens=8,
                                     stop_token_ids=()), abort=abort)
    t0 = _time.monotonic()
    while engine.chunk_dispatches < 1 and _time.monotonic() - t0 < 120:
        _time.sleep(0.01)
    assert engine.chunk_dispatches >= 1
    abort.set()
    from polyrl_tpu.rollout.cb_engine import STREAM_END

    items = []
    while True:
        item = q.get(timeout=60)
        if item is STREAM_END:
            break
        items.append(item)
    assert any(i.get("finish_reason") == "abort" for i in items), items
    deadline = 10.0
    import time as _time

    t0 = _time.monotonic()
    while (engine.allocator.free_count != free0
           and _time.monotonic() - t0 < deadline):
        _time.sleep(0.05)
    engine.stop()
    assert engine.allocator.free_count == free0


def test_chunked_prefill_aborts_on_weight_swap():
    """A weight update mid-chunk-job must abort the job (its filled KV
    belongs to the old weights; finishing would publish mixed-version KV
    into the freshly flushed prefix cache)."""
    import time as _time

    from polyrl_tpu.rollout.cb_engine import STREAM_END
    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg, engine, kw, params = _mk_engines_for_chunking(prefill_chunk=8)
    engine.start()
    rng = np.random.default_rng(14)
    free0 = engine.allocator.free_count
    q = engine.submit("rW", rng.integers(1, cfg.vocab_size, 40).tolist(),
                      SamplingParams(temperature=0.0, max_new_tokens=8,
                                     stop_token_ids=()))
    t0 = _time.monotonic()
    while engine.chunk_dispatches < 1 and _time.monotonic() - t0 < 120:
        _time.sleep(0.01)
    engine.update_weights(engine.params, version=99)
    items = []
    while True:
        item = q.get(timeout=60)
        if item is STREAM_END:
            break
        items.append(item)
    reasons = {i.get("finish_reason") for i in items}
    # either the job aborted (swap landed mid-job) or it already finished
    # cleanly before the swap (tiny-model race) — but never an error, and
    # pages always return
    assert "error" not in reasons, items
    t0 = _time.monotonic()
    while (engine.allocator.free_count != free0
           and _time.monotonic() - t0 < 10):
        _time.sleep(0.05)
    engine.stop()
    assert engine.allocator.free_count == free0


def test_fetcher_failure_recovers_and_serving_continues(tiny):
    """A device_get failure surfaced by the fetcher thread must route
    through _recover (fail in-flight requests, rebuild pools) and leave the
    engine serving new requests — a dead loop thread wedges every connected
    HTTP handler."""
    from polyrl_tpu.rollout.cb_engine import STREAM_END

    cbe = _mk_engine(tiny, max_seq_len=512, num_pages=128)
    cbe.start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=300, stop_token_ids=())
    qa = cbe.submit("victim", [5, 3, 9], sp)
    first = qa.get(timeout=60)
    assert first["token_ids"]
    # inject a poisoned-backend failure exactly where the fetcher reports
    # one; the loop's next drain re-raises it -> _recover
    with cbe._fetch_cv:
        cbe._fetch_exc = RuntimeError("injected device_get failure")
        cbe._fetch_cv.notify_all()
    failed = False
    while True:
        item = qa.get(timeout=60)
        if item is STREAM_END:
            break
        if item.get("finish_reason") in ("error", "abort"):
            failed = True
    assert failed, "victim request should have been failed by _recover"
    # engine must still serve after the recovery
    out = cbe.generate([[7, 1, 4]], SamplingParams(
        temperature=0.0, max_new_tokens=8, stop_token_ids=()), timeout=60.0)
    assert len(out[0]["token_ids"]) == 8
    cbe.stop()
    assert all(s is None for s in cbe._slots)


@pytest.mark.parametrize("finished", [False, True])
def test_fetcher_takes_only_what_the_device_has_finished(tiny, monkeypatch,
                                                         finished):
    """The fetcher's batch is the oldest output and, behind it, only
    outputs the device has finished (at most half the window): with none
    finished every landing is one dispatch, so a saturated device streams
    dispatch by dispatch; with all finished, what piled up lands in one
    get. The tokens are the same either way."""
    from polyrl_tpu.rollout import cb_engine

    monkeypatch.setattr(cb_engine, "_finished_on_device",
                        lambda payload: finished)
    cbe = _mk_engine(tiny, pipeline_depth=4, steps_per_dispatch=2)
    landed = []
    landing = cbe._landed
    monkeypatch.setattr(cbe, "_landed", lambda batch, fetched: (
        landed.append(len(batch)), landing(batch, fetched)))
    sp = SamplingParams(temperature=0.0, max_new_tokens=24,
                        stop_token_ids=())
    out = cbe.generate([[5, 3, 9, 2], [11, 4]], sp, timeout=120.0)
    cbe.stop()
    assert [len(o["token_ids"]) for o in out] == [24, 24]
    assert landed and max(landed) <= 2          # half of pipeline_depth
    if not finished:
        assert set(landed) == {1}
    plain = _mk_engine(tiny, pipeline_depth=0)
    want = plain.generate([[5, 3, 9, 2], [11, 4]], sp, timeout=120.0)
    plain.stop()
    assert [o["token_ids"] for o in out] == [o["token_ids"] for o in want]


def test_weight_swap_mid_generation_with_pipeline(tiny):
    """update_weights while a long stream is mid-generation with the deep
    run-ahead pipeline: the stream must complete cleanly (no device-state
    tear), and a request AFTER the swap must decode with the new policy."""
    cfg, params = tiny
    cbe = _mk_engine(tiny, max_seq_len=512, num_pages=128)
    cbe.start()
    sp_long = SamplingParams(temperature=0.0, max_new_tokens=300,
                             stop_token_ids=())
    q = cbe.submit("mid", [5, 3, 9], sp_long)
    first = q.get(timeout=60)
    assert first["token_ids"]
    params2 = decoder.init_params(jax.random.PRNGKey(99), cfg)
    cbe.update_weights(params2, version=3)
    from polyrl_tpu.rollout.cb_engine import STREAM_END
    n = len(first["token_ids"])
    while True:
        item = q.get(timeout=120)
        if item is STREAM_END:
            break
        n += len(item["token_ids"])
    assert n == 300  # budget-bound stream still completes exactly
    assert cbe.weight_version == 3
    # post-swap decode equals a fresh engine on params2
    sp = SamplingParams(temperature=0.0, max_new_tokens=8, stop_token_ids=())
    got = cbe.generate([[7, 1, 4]], sp)[0]["token_ids"]
    ref_eng = CBEngine(cfg, params2, max_slots=4, page_size=8,
                       max_seq_len=128, prompt_buckets=(16, 32), num_pages=64)
    want = ref_eng.generate([[7, 1, 4]], sp)[0]["token_ids"]
    ref_eng.stop()
    cbe.stop()
    assert got == want
