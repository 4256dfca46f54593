"""Shared-prefix decode attention (ARCHITECTURE.md "Shared-prefix decode
attention"): the two-phase grouped paged-attention kernel (ref oracle +
pallas interpret) pinned against the ungrouped full-table oracle, and the
engine-level group-table lifecycle — parity with the off-switch engine,
abort/salvage mid-group, KV-read accounting, and the knob echoes."""

import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import decoder
from polyrl_tpu.ops.paged_attention import (
    grouped_paged_attention_pallas,
    grouped_paged_attention_ref,
    paged_attention_ref,
)
from polyrl_tpu.rollout.cb_engine import CBEngine, STREAM_END
from polyrl_tpu.rollout.sampling import SamplingParams

PAGE = 8


def _grouped_case(rng, groups=((4, 2, (3, 9, 1, 5)),), hkv=2, rep=2, d=16,
                  ungrouped_lens=(11,), n_pool=128):
    """Build pools + per-slot FULL page tables where each group's members
    share one physical prefix chain (the engine's page-table indirection)
    followed by private suffix pages. ``groups`` is a tuple of
    (g, n_pre_pages, suffix_lens). Returns everything both the grouped
    call and the plain full-table oracle need."""
    hq = hkv * rep
    k_pool = rng.standard_normal((hkv, n_pool, PAGE, d)).astype(np.float32)
    v_pool = rng.standard_normal((hkv, n_pool, PAGE, d)).astype(np.float32)
    free = list(range(1, n_pool))
    rng.shuffle(free)

    rows, lens = [], []
    seats, g_pages, g_lens = [], [], []
    max_pre = max((n for _g, n, _s in groups), default=1)
    max_pages = max_pre + 3
    for g, n_pre, sfx_lens in groups:
        pre = [free.pop() for _ in range(n_pre)]
        seat_row = []
        for i in range(g):
            sfx = sfx_lens[i % len(sfx_lens)]
            own = [free.pop() for _ in range(-(-sfx // PAGE))]
            row = np.zeros((max_pages,), np.int32)
            row[:n_pre] = pre
            row[n_pre:n_pre + len(own)] = own
            seat_row.append(len(rows))
            rows.append(row)
            lens.append(n_pre * PAGE + sfx)
        seats.append(seat_row)
        g_pages.append(pre)
        g_lens.append(n_pre * PAGE)
    for ln in ungrouped_lens:
        own = [free.pop() for _ in range(-(-ln // PAGE))]
        row = np.zeros((max_pages,), np.int32)
        row[:len(own)] = own
        rows.append(row)
        lens.append(ln)

    s = len(rows)
    ng = max(1, len(seats))
    gmax = max((len(sr) for sr in seats), default=1)
    group_slots = np.full((ng, gmax), -1, np.int32)
    group_prefix_pages = np.zeros((ng, max_pre), np.int32)
    group_prefix_lens = np.zeros((ng,), np.int32)
    for i, sr in enumerate(seats):
        group_slots[i, :len(sr)] = sr
        group_prefix_pages[i, :len(g_pages[i])] = g_pages[i]
        group_prefix_lens[i] = g_lens[i]
    q = rng.standard_normal((s, hq, d)).astype(np.float32)
    return (q, k_pool, v_pool, np.stack(rows), np.asarray(lens, np.int32),
            group_slots, group_prefix_pages, group_prefix_lens)


@pytest.mark.parametrize("g,rep", [(1, 1), (4, 1), (4, 4), (8, 4)])
def test_grouped_matches_full_oracle(g, rep):
    """Acceptance parity grid (G ∈ {1,4,8}, rep ∈ {1,4}): the grouped
    two-phase result — ref oracle AND pallas interpret — equals plain
    full-table attention over the reconstructed per-slot tables."""
    rng = np.random.default_rng(g * 10 + rep)
    case = _grouped_case(rng, groups=((g, 2, (3, 9, 1, 5)),), rep=rep)
    q, kp, vp, table, lens, gs, gpp, gpl = case
    full = paged_attention_ref(q, kp, vp, table, lens)
    gref = grouped_paged_attention_ref(q, kp, vp, table, lens, gs, gpp, gpl)
    gpal = grouped_paged_attention_pallas(q, kp, vp, table, lens, gs, gpp,
                                          gpl, interpret=True)
    np.testing.assert_allclose(np.asarray(gref), np.asarray(full),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gpal), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_pre", [1, 2, 3])
def test_prefix_lengths_cross_page_boundaries(n_pre):
    """Prefix chains of 1..3 whole pages with suffixes that land just
    before/on/after their own page boundaries (PAGE-1, PAGE, PAGE+1)."""
    rng = np.random.default_rng(n_pre)
    q, kp, vp, table, lens, gs, gpp, gpl = _grouped_case(
        rng, groups=((3, n_pre, (PAGE - 1, PAGE, PAGE + 1)),))
    full = paged_attention_ref(q, kp, vp, table, lens)
    gref = grouped_paged_attention_ref(q, kp, vp, table, lens, gs, gpp, gpl)
    gpal = grouped_paged_attention_pallas(q, kp, vp, table, lens, gs, gpp,
                                          gpl, interpret=True)
    np.testing.assert_allclose(np.asarray(gref), np.asarray(full),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gpal), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_empty_suffix_rows_right_after_attach():
    """A sibling fresh off the attach wave owns a single suffix token (the
    page-unaligned prompt tail / first decode position) — the phase-2 page
    loop must still merge correctly at n_sfx == 1."""
    rng = np.random.default_rng(42)
    q, kp, vp, table, lens, gs, gpp, gpl = _grouped_case(
        rng, groups=((4, 2, (1, 1, 1, 1)),))
    full = paged_attention_ref(q, kp, vp, table, lens)
    gpal = grouped_paged_attention_pallas(q, kp, vp, table, lens, gs, gpp,
                                          gpl, interpret=True)
    np.testing.assert_allclose(np.asarray(gpal), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_masked_seat_mid_group():
    """One sibling finished mid-group: its seat goes -1 and the slot (whose
    page row still holds the whole sequence) must fall back to the
    phase-2-only path while the survivors keep sharing — everyone still
    equals the full-table oracle. Also exercises multiple groups + an
    ungrouped bystander in one call."""
    rng = np.random.default_rng(7)
    q, kp, vp, table, lens, gs, gpp, gpl = _grouped_case(
        rng, groups=((4, 2, (3, 9, 1, 5)), (2, 1, (6, 2))),
        ungrouped_lens=(11, 5))
    gs[0, 2] = -1  # mid-row seat masked
    full = paged_attention_ref(q, kp, vp, table, lens)
    gref = grouped_paged_attention_ref(q, kp, vp, table, lens, gs, gpp, gpl)
    gpal = grouped_paged_attention_pallas(q, kp, vp, table, lens, gs, gpp,
                                          gpl, interpret=True)
    np.testing.assert_allclose(np.asarray(gref), np.asarray(full),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gpal), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_bf16_pools_grouped():
    rng = np.random.default_rng(3)
    q, kp, vp, table, lens, gs, gpp, gpl = _grouped_case(rng)
    out16 = grouped_paged_attention_ref(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(kp, jnp.bfloat16),
        jnp.asarray(vp, jnp.bfloat16), table, lens, gs, gpp, gpl)
    out32 = grouped_paged_attention_ref(q, kp, vp, table, lens, gs, gpp, gpl)
    np.testing.assert_allclose(np.asarray(out16, np.float32),
                               np.asarray(out32), rtol=0.1, atol=0.1)


# -- engine level ------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = decoder.get_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mk_engine(tiny, **kw):
    cfg, params = tiny
    defaults = dict(max_slots=16, page_size=8, max_seq_len=128,
                    prompt_buckets=(16, 32), num_pages=256)
    defaults.update(kw)
    return CBEngine(cfg, params, **defaults)


class _AbortAtFirstChunk(queue.Queue):
    """A request's stream that sets its abort event as the engine puts the
    first chunk, on the engine's own thread (as tests/test_kv_ledger.py)."""

    def __init__(self):
        super().__init__()
        self.abort = threading.Event()

    def put(self, item, block=True, timeout=None):
        super().put(item, block, timeout)
        self.abort.set()


def _collect(q, timeout=120):
    toks, lps, reason = [], [], ""
    while True:
        item = q.get(timeout=timeout)
        if item is STREAM_END:
            break
        toks.extend(item["token_ids"])
        lps.extend(item["logprobs"])
        if item["finished"]:
            reason = item["finish_reason"]
    return toks, lps, reason


def test_engine_parity_grouped_vs_ungrouped(tiny):
    """Acceptance: with decode_group_share on, greedy decode tokens are
    IDENTICAL to the off-switch engine on the CPU oracle, and logprobs stay
    within the established atol=5e-4 bound (the LSE merge legitimately
    reorders float reductions — bitwise is only required of the
    off-switch/singleton path, which compiles the pre-grouping step fn)."""
    cfg, _ = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size, 13).tolist()  # unaligned tail
    sp = SamplingParams(temperature=0.0, max_new_tokens=10,
                        stop_token_ids=())

    def run(decode_share):
        eng = _mk_engine(tiny, decode_group_share=decode_share)
        outs = [eng.submit(f"p-{i}", prompt, sp, group_id="gP", group_size=4)
                for i in range(4)]
        eng.start()
        res = [_collect(q) for q in outs]
        stats = (eng.grouped_decode_dispatches,
                 eng.deck.shared_prefix_read_frac())
        eng.stop()
        assert eng.allocator.free_count == eng.num_pages - 1
        assert eng._decode_groups == {} and eng._slot_decode_gid == {}
        return res, stats

    on, (disp_on, frac_on) = run(True)
    off, (disp_off, frac_off) = run(False)
    assert disp_on > 0 and frac_on > 0.0          # sharing actually engaged
    assert disp_off == 0 and frac_off == 0.0      # off-switch stays cold
    for (t1, l1, _), (t2, l2, _) in zip(on, off):
        assert t1 == t2                            # greedy token parity
        np.testing.assert_allclose(l1, l2, rtol=0, atol=5e-4)


def test_engine_group_table_lifecycle(tiny):
    """Admission seats leader + attach siblings on the SAME prefix chain;
    finalize drops seats; a lone survivor degrades to the ungrouped pack
    (pack returns None below 2 live members)."""
    cfg, _ = tiny
    eng = _mk_engine(tiny)
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, cfg.vocab_size, 12).tolist()
    sp = SamplingParams(temperature=0.0, max_new_tokens=4, stop_token_ids=())
    for i in range(3):
        eng.submit(f"l-{i}", prompt, sp, group_id="gL", group_size=3)
    eng._drain_queue()
    with eng._pool_lock:
        eng._admit()
    g = eng._decode_groups["gL"]
    assert len(g["slots"]) == 3
    n_pre = (len(prompt) - 1) // eng.page_size
    assert g["n_pre"] == n_pre
    for slot in sorted(g["slots"]):
        assert tuple(int(p) for p in eng._page_table[slot][:n_pre]) \
            == g["pages"]
    pack, gshape, rows = eng._decode_group_pack()
    assert gshape == (1, 4, 1) and len(rows) == 1  # pow2 seat bucket
    # two members leave → singleton survivor degrades to ungrouped
    slots = sorted(g["slots"])
    eng._active[slots[0]] = False
    eng._finalize(slots[0])
    eng._active[slots[1]] = False
    eng._finalize(slots[1])
    pack, gshape, rows = eng._decode_group_pack()
    assert pack is None and gshape is None
    assert eng._decode_groups["gL"]["slots"] == {slots[2]}
    eng._active[slots[2]] = False
    eng._finalize(slots[2])
    assert eng._decode_groups == {}
    eng.stop()


def test_engine_abort_mid_group_survivors_keep_decoding(tiny):
    """Acceptance regression: a group member is aborted (salvage on)
    mid-decode and the SURVIVORS keep decoding correctly — same greedy
    tokens as an undisturbed reference engine, full budget, accounting
    reconciled, no seats left behind."""
    cfg, _ = tiny
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, cfg.vocab_size, 13).tolist()
    sp = SamplingParams(temperature=0.0, max_new_tokens=24,
                        stop_token_ids=())

    ref_eng = _mk_engine(tiny, decode_group_share=True)
    ref = ref_eng.generate([prompt] * 4, sp)
    ref_eng.stop()

    # two dispatches (16 tokens) at most are out when a first chunk is
    # put, and the abort is set there, on the engine's own thread: the
    # aborted members cannot have reached their 24 tokens, however late a
    # loaded box schedules the thread that reads the streams
    eng = _mk_engine(tiny, decode_group_share=True, pipeline_depth=1)
    outs = [_AbortAtFirstChunk() if i in (1, 2) else queue.Queue()
            for i in range(4)]
    for i, q in enumerate(outs):
        eng.submit(f"a-{i}", prompt, sp, out=q,
                   abort=getattr(q, "abort", None),
                   group_id="gA", group_size=4)
    eng.start()
    res = []
    for q in outs:
        toks, lps, reason = _collect(q)
        res.append((toks, reason))
    assert res[1][1] == "abort" and res[2][1] == "abort"
    for i in (0, 3):  # survivors: full budget, greedy-identical to ref
        assert res[i][1] == "length"
        assert res[i][0] == list(ref[i]["token_ids"])
    # aborted members' salvaged partials are prefixes of the reference
    for i in (1, 2):
        n = len(res[i][0])
        assert res[i][0] == list(ref[i]["token_ids"])[:n]
    assert eng.deck.attributed_frac() == 1.0
    eng.stop()
    assert eng._decode_groups == {} and eng._slot_decode_gid == {}
    assert eng.allocator.free_count == eng.num_pages - 1


def test_kv_read_accounting_and_knob_echo(tiny):
    """Satellites: the flight deck's KV-read ledger quantifies the dedup
    (streamed < logical with sharing on; equal with it off), and
    server_info + /statusz echo decode_group_share / group_preref_ttl_s
    next to the existing admit_wave geometry."""
    from polyrl_tpu.rollout.server import RolloutServer

    cfg, _ = tiny
    eng = _mk_engine(tiny, decode_group_share=True, group_preref_ttl_s=7.5)
    assert eng.group_preref_ttl_s == 7.5
    srv = RolloutServer(eng, host="127.0.0.1", port=0)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, 12).tolist()
    sp = SamplingParams(temperature=0.0, max_new_tokens=8, stop_token_ids=())
    subs = [srv.submit(f"k-{i}", prompt, sp, group_id="gK", group_size=4)
            for i in range(4)]
    srv.start()
    for q, _ev in subs:
        _collect(q)
    deck = eng.deck
    assert deck.kv_pages_logical > deck.kv_pages_streamed > 0
    assert 0.0 < deck.shared_prefix_read_frac() < 1.0
    assert deck.kv_read_pages_per_token() > 0.0
    info = srv.server_info()
    assert info["decode_group_share"] is True
    assert info["group_preref_ttl_s"] == 7.5
    assert info["grouped_decode_dispatches"] > 0
    assert info["shared_prefix_read_frac"] > 0.0
    assert info["kv_read_pages_per_token"] > 0.0
    snap = srv.statusz_snapshot()
    grp = snap["engine"]["group"]
    assert grp["decode_group_share"] is True
    assert grp["group_preref_ttl_s"] == 7.5
    assert grp["shared_prefix_read_frac"] > 0.0
    pages = snap["engine"]["pages"]
    assert pages["kv_logical"] > pages["kv_streamed"] > 0
    assert snap["counters"]["grouped_decode_dispatches"] >= 1.0
    srv.stop()


def test_decode_group_share_off_is_bitwise_off_switch(tiny):
    """The off switch takes the pre-grouping compiled step (same jit key,
    no group pack): tokens AND logprobs bitwise-equal to a plain engine
    that never saw group hints."""
    cfg, _ = tiny
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, cfg.vocab_size, 12).tolist()
    sp = SamplingParams(temperature=0.0, max_new_tokens=6, stop_token_ids=())
    eng_off = _mk_engine(tiny, decode_group_share=False)
    outs = [eng_off.submit(f"o-{i}", prompt, sp, group_id="gO", group_size=3)
            for i in range(3)]
    eng_off.start()
    hinted = [_collect(q) for q in outs]
    assert eng_off._decode_groups == {}  # hints ignored entirely
    eng_off.stop()

    eng_plain = _mk_engine(tiny)  # share on, but no hints → no groups
    res = eng_plain.generate([prompt] * 3, sp)
    assert eng_plain.grouped_decode_dispatches == 0
    eng_plain.stop()
    for (toks, lps, _), r in zip(hinted, res):
        assert toks == list(r["token_ids"])
        assert lps == list(r["logprobs"])  # bitwise: same compiled path
