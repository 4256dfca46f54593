"""Continuous-batching rollout engine over a paged KV pool.

TPU-native equivalent of SGLang's continuous-batching scheduler + paged KV
runtime that the reference builds its rollout layer on (SURVEY.md §2.2
native-census row 1; queue-depth telemetry patches.py:423-425; abort
sglang_http_async_engine.py:286-298). Design:

- ONE compiled decode step for every request mix: a fixed array of ``S``
  slots; per-slot sampling params (temperature/top-p/top-k/stop tokens) are
  traced arrays, so admission never recompiles (contrast the bucketed v0
  ``StepDecoder`` which compiles per sampling group).
- Paged KV: slots own page lists from a shared pool
  (``decoder.make_paged_pools``); attention is
  ``ops.paged_attention`` (Pallas on TPU). No shape buckets in decode.
  A slot holds the pages it has WRITTEN plus what the dispatches in flight
  can write: admission takes the prompt's, every decode dispatch is
  preceded by the pages its rows grow into (``_grow_rows``), and when the
  pool runs out the youngest row gives up slot and pages and re-enters as
  a continuation of itself (``_yield_row``; ARCHITECTURE.md "The life of
  a page").
  Dispatches with live GRPO groups route through the two-phase GROUPED
  kernel (``grouped_paged_attention``): one HBM stream of the group's
  shared prompt KV serves every sibling per decode step, suffixes merge
  via the flash LSE — the group tables ride each dispatch as traced data
  (ARCHITECTURE.md "Shared-prefix decode attention").
- Admission: FUSED async prefill (compiled per prompt bucket) — one packed
  int32 control upload per request; the prefill inserts the slot into the
  device-resident control state and the first token joins the deferred
  emission queue. No host round trip per admission.
- Decode: the control state lives on device and the step ADVANCES it there;
  dispatches stay `pipeline_depth` ahead while a dedicated FETCHER THREAD
  owns the blocking device->host output transfer, batching every queued
  dispatch output the device has finished into one ``device_get`` — so the
  loop keeps the device fed and result round trips overlap compute: a
  blocking fetch per dispatch would leave the device idle for every
  device->host round trip. Host np mirrors (updated at drain) drive
  admission and are re-uploaded only after host-side events (abort,
  overflow stop); a full drain (``keep=0``) barriers on the fetcher
  first, so re-uploads never rewind slots past results still in flight.
- Admission never waits for the device. A request that finds no pages or
  no slot takes what the fetcher has ALREADY landed (``_retry_landed``),
  tries once more, and stays in ``_pending``; the finisher it needs is
  landed by the throttle like every other output. While a request waits
  the throttle (``_throttle``) is one program, not `pipeline_depth`: the
  next dispatch goes out when fewer than two are unfinished on the
  device, so the device never runs dry and a freed slot is refilled a
  dispatch later, not a window later.

Weight hot-swap = atomic ``self.params`` swap between steps (buffer shapes
and shardings unchanged → no recompilation), mirroring the reference's
update_weights_from_tensor contract. ``release_memory`` frees the KV pool
when idle — the TPU analogue of SGLang's release_memory_occupation for
colocated time-slicing.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import logging
import queue
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu import obs
from polyrl_tpu.engine_options import EngineOptions
from polyrl_tpu.models import cache_spec, decoder, hybrid
from polyrl_tpu.obs.engine_profile import (CUMULATIVE_KEYS,
                                           EngineLoopProfiler)
from polyrl_tpu.rollout.engine import next_bucket
from polyrl_tpu.rollout.flightdeck import EngineFlightDeck, ThroughputEWMA
from polyrl_tpu.rollout.kvledger import PageLedger
from polyrl_tpu.rollout.kvspill import HostSpillPool
from polyrl_tpu.rollout.prefix_cache import PrefixCache
from polyrl_tpu.rollout.sampling import (
    SamplingParams,
    sample_token_vec,
    spec_verify_sample_vec,
)

log = logging.getLogger(__name__)

STREAM_END = object()  # terminal marker on every request's output queue

MAX_STOP_TOKENS = 8


class StreamLine(dict):
    """One line of a request's stream as the engine queues it. ``t_put``,
    the engine's monotonic clock at the put, rides the object and not the
    mapping: the server reads it for ``stream_lag_s``, and what it
    serializes to the wire is the mapping alone."""

    __slots__ = ("t_put",)

    def __init__(self, fields: dict):
        super().__init__(fields)
        self.t_put = time.monotonic()


# reusable no-op phase context (contextlib.nullcontext is reentrant):
# _phase() hands this out when the loop profiler is off so the hot path
# pays one attribute read, not an allocation
_NULL_PHASE = contextlib.nullcontext()


def _finished_on_device(payload) -> bool:
    """Whether the device has finished every array of a dispatch's output,
    so that a ``device_get`` of it waits for the transfer alone."""
    return all(a.is_ready() for a in jax.tree_util.tree_leaves(payload)
               if isinstance(a, jax.Array))


def device_ngram_propose(tok_buf: jnp.ndarray, hist_len: jnp.ndarray,
                         n_draft: int) -> jnp.ndarray:
    """Vectorized prompt-lookup proposal on device: for each slot, find the
    LATEST earlier occurrence of the history's final TRIGRAM in
    ``tok_buf[s, :hist_len[s]]`` — falling back to the final bigram, then
    to repeating the last token — and propose the ``n_draft`` tokens that
    followed the match. Longer context matches are what make prompt-lookup
    precise on repetitive text (a repeated bigram often continues
    differently; a repeated trigram rarely does). Rejection sampling keeps
    ANY proposal distribution-exact — a bad guess only wastes verify
    FLOPs. O(S·L) compares; jit-safe static shapes.

    tok_buf: [S, L] int32 (prompt + generated, front-filled)
    hist_len: [S] int32 valid-prefix lengths
    returns: [S, n_draft] int32
    """
    s, length = tok_buf.shape
    rows = jnp.arange(s)
    t_last = tok_buf[rows, jnp.clip(hist_len - 1, 0, length - 1)]
    t_prev = tok_buf[rows, jnp.clip(hist_len - 2, 0, length - 1)]
    t_prev2 = tok_buf[rows, jnp.clip(hist_len - 3, 0, length - 1)]
    idx2 = jnp.arange(length - 1)
    # bigram match at p: buf[p] == t_prev and buf[p+1] == t_last, with the
    # matched bigram strictly before the final one (p+1 < hist_len-1)
    m2 = ((tok_buf[:, :-1] == t_prev[:, None])
          & (tok_buf[:, 1:] == t_last[:, None])
          & (idx2[None] + 1 < (hist_len - 1)[:, None]))
    p2 = jnp.max(jnp.where(m2, idx2[None], -1), axis=1)           # latest
    found2 = (p2 >= 0) & (hist_len >= 3)
    # trigram match at p: buf[p:p+3] == (t_prev2, t_prev, t_last), matched
    # strictly before the final trigram (p+2 < hist_len-1)
    idx3 = jnp.arange(length - 2)
    m3 = ((tok_buf[:, :-2] == t_prev2[:, None])
          & (tok_buf[:, 1:-1] == t_prev[:, None])
          & (tok_buf[:, 2:] == t_last[:, None])
          & (idx3[None] + 2 < (hist_len - 1)[:, None]))
    p3 = jnp.max(jnp.where(m3, idx3[None], -1), axis=1)
    found3 = (p3 >= 0) & (hist_len >= 4)
    # continuation starts right after whichever match won
    start = jnp.where(found3, p3 + 3, p2 + 2)
    found = found3 | found2
    gather = jnp.clip(start[:, None] + jnp.arange(n_draft)[None], 0,
                      length - 1)
    cont = jnp.take_along_axis(tok_buf, gather, axis=1)
    # past-the-history continuation positions fall back to the last token
    cont = jnp.where(gather < hist_len[:, None], cont, t_last[:, None])
    return jnp.where(found[:, None], cont,
                     jnp.broadcast_to(t_last[:, None], (s, n_draft))
                     ).astype(jnp.int32)


@dataclasses.dataclass
class _Request:
    rid: str
    input_ids: list[int]
    sampling: SamplingParams
    out: queue.Queue
    abort: Any  # threading.Event-like or None
    t_submit: float = 0.0  # admission timestamp (per-request latency obs)
    # group-shared prefill hint (GRPO: rollout_n completions of one prompt
    # submitted together): members of a group share group_id; group_size is
    # the expected member count. The engine prefills the shared prompt ONCE
    # and batch-attaches the siblings to the published pages — the hint
    # sizes the pre-taken prefix refs; the attach batching itself is
    # structural (prompt-equality through the prefix cache), so a missing
    # or wrong hint degrades to per-request admission, never corrupts.
    group_id: str = ""
    group_size: int = 0
    # tokens this rid had streamed when it last gave up its slot for want
    # of pages (``_yield_row``): they are the tail of ``input_ids`` now,
    # and they count when the engine looks for its youngest row
    resumed: int = 0


@dataclasses.dataclass
class _SlotInfo:
    req: _Request
    pages: list[int]            # slot-PRIVATE pages (freed on finalize)
    stop_set: set
    cache_entries: list = dataclasses.field(default_factory=list)
    # prefix-cache refs (released on finalize; cache owns those pages)
    # tokens already streamed to the client (partial-rollout salvage: the
    # abort path publishes prompt+emitted pages so a continuation landing
    # back on this engine re-uses the decoded KV) + the weight version the
    # slot was admitted under (KV written across a swap must not be
    # published — the cache flush on update_weights would be defeated)
    emitted: list = dataclasses.field(default_factory=list)
    admit_version: int = 0


class PageAllocator:
    """Free-list allocator over pages 1..n-1 (page 0 = reserved null page)."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        if n > len(self._free):
            return None
        out = self._free[-n:]
        del self._free[-n:]
        return out

    def free(self, pages: list[int]) -> None:
        self._free.extend(pages)


class CBEngine:
    """Continuous-batching engine; drop-in serving backend for RolloutServer."""

    def __init__(
        self,
        cfg: decoder.ModelConfig,
        params: Any,
        *,
        mesh=None,
        pad_token_id: int = 0,
        seed: int = 0,
        kv_cache_dtype=jnp.bfloat16,
        # the tests' reference run; no entry point turns it off
        enable_prefix_cache: bool = True,
        **options,
    ):
        # names, defaults and what each does: polyrl_tpu/engine_options.py
        # (an unknown name is a TypeError there)
        o = EngineOptions(**options)
        max_slots, page_size = o.max_slots, o.page_size
        self.cfg = cfg
        self.mesh = mesh
        if mesh is not None:
            # tensor-parallel serving (the reference's SGLang --tp-size
            # role, launch_sglang.sh:13): params shard over (fsdp, tp) per
            # decoder.param_specs, KV pools over tp on the head dim, and
            # GSPMD inserts the attention/matmul collectives inside the
            # existing compiled step — no engine-logic changes. Quantized
            # trees shard via quant_param_specs.
            tp = mesh.shape.get("tp", 1)
            if cfg.num_heads % tp or cfg.num_kv_heads % tp:
                raise ValueError(
                    f"tp={tp} must divide num_heads ({cfg.num_heads}) and "
                    f"num_kv_heads ({cfg.num_kv_heads}) — the KV pools and "
                    "paged attention shard on the head dim")
            params = self._shard_params_for_mesh(params)
        self.params = params
        # two questions of the model's layers (models/cache_spec.py), and
        # no option. Does a sequence keep anything outside pages? A model
        # with a recurrent state in its slot has no snapshot to re-enter a
        # sequence from: no prefix cache (so no hit, no publish, no spill,
        # no salvage publish; a GRPO group's siblings and a resumed
        # partial prefill from token 0), no shared-prefix decode groups,
        # and no prompt-lookup speculation
        self.stateful = cache_spec.is_stateful(cfg)
        # and which features that act on pages have a kernel for every
        # mixer of the plan? What needs none (prefix cache, a group's
        # shared prompt, salvage, the ledger, growth and yield) runs on
        # any paged pool; speculation is refused, the grouped decode
        # kernel and the spill tier are off, with the mixers named
        self._no_kernel = {f: cache_spec.without_kernel(cfg, f)
                           for f in cache_spec.FEATURE_KERNELS}
        if self.stateful:
            if int(o.spec_tokens) > 0:
                raise ValueError(
                    "spec_tokens > 0 needs a state to roll back to after a "
                    "rejected draft; this model keeps a recurrent state "
                    "with no snapshot in its "
                    f"{'/'.join(self._no_kernel['spec_tokens'])} layers "
                    "(models/cache_spec.py)")
            if mesh is not None and mesh.size > 1:
                raise NotImplementedError(
                    "a model with a recurrent state on a mesh of several "
                    "chips")
            enable_prefix_cache = False
        elif int(o.spec_tokens) > 0 and self._no_kernel["spec_tokens"]:
            raise ValueError(
                "spec_tokens > 0 verifies several tokens a row in one "
                "forward, which is written for gqa layers; this model "
                f"has {'/'.join(self._no_kernel['spec_tokens'])} layers "
                "(models/cache_spec.py::without_kernel)")
        for feature, on in (("decode_group_share", o.decode_group_share),
                            ("kv_spill", o.kv_spill)):
            if on and self._no_kernel[feature]:
                log.info("%s is off: no kernel for %s layers "
                         "(models/cache_spec.py::without_kernel)",
                         feature, "/".join(self._no_kernel[feature]))
        # the profiler's counters that every decode step of a dispatch
        # moves: the share counters of the kernels the plan's layers take
        # at the step's rows (``hybrid.step_counters``), and where the
        # dispatch samples inside the head the sampler's; one answer for
        # the engine's life
        kernels = hybrid.step_counters(
            cfg, max_slots + 1, one_chip=mesh is None or mesh.size == 1)
        self._step_counters = {False: kernels,
                               True: (*kernels, "fused_sample_steps")}
        self.max_slots = max_slots
        self.page_size = page_size
        self.max_seq_len = o.max_seq_len
        self.pages_per_slot = -(-o.max_seq_len // page_size)
        # default pool: enough for half the slots at full length + slack
        self.num_pages = o.num_pages or (
            max_slots * self.pages_per_slot // 2 + 1)
        self.prompt_buckets = o.prompt_buckets
        self.kv_cache_dtype = kv_cache_dtype
        self.pad_token_id = pad_token_id

        s, p = max_slots, self.pages_per_slot
        self._page_table = np.zeros((s, p), np.int32)
        self._seq_lens = np.zeros((s,), np.int32)
        self._last_tokens = np.full((s,), pad_token_id, np.int32)
        self._n_generated = np.zeros((s,), np.int32)
        self._budgets = np.zeros((s,), np.int32)
        self._active = np.zeros((s,), bool)
        self._temps = np.ones((s,), np.float32)
        self._top_ps = np.ones((s,), np.float32)
        self._top_ks = np.zeros((s,), np.int32)
        self._stop_table = np.full((s, MAX_STOP_TOKENS), -1, np.int32)
        self._slots: list[_SlotInfo | None] = [None] * s
        # per-slot admission generation: queued emit entries record the
        # generation they were dispatched against, so an entry that outlives
        # its slot (finalized via the device-done path, then reused by a new
        # admission before the entry drains) is detected and skipped instead
        # of leaking pad tokens into the new request's stream (ABA race)
        self._slot_gen = np.zeros((s,), np.int64)

        self.allocator = PageAllocator(self.num_pages)
        # KV memory plane (rollout/kvledger.py): per-page owner/role/age
        # ledger + hot/warm/cold residency tiers, fed synchronously at
        # every page transition below. None (rollout.kv_ledger=false)
        # disables all accounting — the engine's output is bitwise
        # identical either way (the ledger never touches RNG, device state
        # or scheduling).
        self.kvledger = (PageLedger(
            self.num_pages, page_size,
            cold_after_dispatches=o.kv_cold_after_dispatches)
            if o.kv_ledger else None)
        self._weight_bytes: int | None = None  # cached tree-leaves total
        # the cache frees through _free_cache_pages so the ledger sees the
        # cause the cache booked (capacity / flush / preref_ttl)
        self.prefix_cache = (PrefixCache(page_size, self._free_cache_pages)
                             if enable_prefix_cache else None)
        # host-RAM KV spill tier (rollout/kvspill.py): cold published
        # prefix-cache pages page out to host under watermark pressure and
        # restore on a prefix hit. Requires the ledger (candidate ranking
        # + accounting) and the prefix cache (the spillable population) —
        # kv_ledger=False therefore disables the sweep entirely, keeping
        # the off-engine bitwise identical (spill never touches RNG or
        # device state unless a spill/restore actually fires, and with the
        # pool absent none can).
        self.kvspill = (HostSpillPool(
            capacity_bytes=int(float(o.kv_spill_host_gb) * 1e9))
            if (o.kv_spill and o.kv_ledger and enable_prefix_cache
                and not self._no_kernel["kv_spill"])
            else None)
        self.kv_spill_high_watermark = float(o.kv_spill_high_watermark)
        self.kv_spill_low_watermark = float(o.kv_spill_low_watermark)
        if self.prefix_cache is not None and self.kvledger is not None:
            # cold-first capacity eviction (ledger idle age beats
            # insertion order) — on whenever the ledger is, spill or not
            self.prefix_cache.idle_age = self.kvledger.idle_age
        if self.kvspill is not None:
            self.prefix_cache.drop_spilled = self._drop_spilled_entries
        self._pools = self._make_pools()
        if self.kvledger is not None:
            # HBM truth reads the chips THIS engine's pools live on
            self.kvledger.devices = tuple(
                jax.tree_util.tree_leaves(self._pools)[0].devices())
        self._rng = jax.random.PRNGKey(seed)

        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._pending: collections.deque = collections.deque()
        self._stop = threading.Event()
        self._paused = threading.Event()
        self._idle = threading.Event()
        self._idle.set()
        # serializes pool use (admit/step) against release_memory freeing it
        self._pool_lock = threading.Lock()
        self._loop_thread: threading.Thread | None = None

        self._step_fns: dict = {}
        self._prefill_fns: dict = {}
        # device-resident control state (mirrors of the np arrays above) and
        # the deferred-emission pipeline: dispatches (prefills + steps) are
        # queued async and their (token, logp, done) outputs fetched later,
        # so device compute overlaps the fetch round trips and streaming
        self._dev_state: dict | None = None
        # fetch pipeline (loop thread dispatches; fetcher thread transfers):
        #   _emit_q     dispatched outputs awaiting device_get
        #   _fetched_q  (epoch, entry, np arrays) awaiting emission
        #   _fetch_inflight  entries inside the fetcher's current device_get
        #   _fetch_epoch     bumped by _recover/stop: stale results dropped
        # all four guarded by _fetch_cv; emission stays on the loop thread
        self._emit_q: collections.deque = collections.deque()
        self._fetched_q: collections.deque = collections.deque()
        self._fetch_cv = threading.Condition()
        self._fetch_inflight = 0
        self._fetch_epoch = 0
        self._fetch_exc: BaseException | None = None
        self._fetch_thread: threading.Thread | None = None
        # per-slot lower bound on tokens the in-flight dispatches will
        # deliver (loop thread only) — drives the tail cutoff in
        # _step_once: once every mirror-active slot's remaining budget is
        # covered by work already in flight FOR THAT SLOT, dispatching
        # more could only produce pad rows. Per-slot matters: a slot
        # admitted after a dispatch launched gets nothing from it.
        self._inflight_tok = np.zeros(s, np.int64)
        # in-flight dispatch budget: how far the loop runs ahead of
        # emission. A negative one would make the drain's `outstanding <=
        # keep` exit unreachable and spin the loop thread forever
        self.pipeline_depth = max(0, int(o.pipeline_depth))
        # admission left a request pending for want of pages or a slot
        # (set by _collect_wave, cleared at the top of every _admit): what
        # _throttle reads. Loop thread only.
        self._admission_waiting = False
        # outputs of the newest two dispatches, oldest first (loop thread
        # only): the device runs its programs in order, so fewer than two
        # are unfinished on it exactly when the older of these is ready
        self._last_two: collections.deque = collections.deque(maxlen=2)
        self.steps_per_dispatch = max(1, int(o.steps_per_dispatch))
        self.prefill_chunk = int(o.prefill_chunk)
        self.prefill_first = bool(o.prefill_first)
        self.prefix_pages_floor = int(o.prefix_pages_floor)
        if not 1 <= self.prefix_pages_floor <= self.pages_per_slot:
            raise ValueError(
                f"prefix_pages_floor {self.prefix_pages_floor}: a slot has "
                f"{self.pages_per_slot} pages")
        self._chunk_jobs: collections.deque = collections.deque()
        # prompt-lookup speculative decoding: each decode dispatch runs
        # spec_rounds fused speculation rounds; every round proposes
        # spec_tokens draft tokens per slot by DEVICE-side n-gram lookup
        # (trigram-preferred, bigram fallback) in a device token buffer,
        # verifies them all in ONE forward, and distribution-exact
        # rejection sampling (spec_verify_sample_vec) emits the accepted
        # prefix + 1
        self.spec_tokens = int(o.spec_tokens)
        self.spec_rounds = int(o.spec_rounds)
        # per-slot token history mirror (prompt + emitted) — rebuilds the
        # device token buffer on state re-uploads; spec mode only
        self._hist: list[list[int] | None] | None = (
            [None] * s if self.spec_tokens > 0 else None)
        self.spec_emitted = 0     # tokens emitted by spec dispatches
        self.spec_dispatches = 0  # spec dispatch count (acceptance telemetry)
        self.chunk_dispatches = 0  # chunked-prefill extend dispatch count

        # admission scheduler geometry (ARCHITECTURE.md "Group-shared
        # prefill")
        self.admit_wave = max(1, int(o.admit_wave))
        self.admit_reorder_window = max(0, int(o.admit_reorder_window))
        self.group_share = bool(o.group_share)
        # admission counters (server_info): dispatches, not
        # requests — the dispatch count is what bounds admission throughput
        self.prefill_dispatches = 0         # all admission dispatches
        self.sibling_attach_dispatches = 0  # batched suffix-attach dispatches
        self.group_forked_requests = 0      # requests admitted by attach wave
        # MoE load of the decode steps landed so far (they move with
        # ``decode_steps_done``), as the model counted it on the device
        # (decoder._moe_mlp), summed over fused steps and layers: (row,
        # expert) pairs of live rows, experts with a row, rows of the
        # busiest expert; then what the plan's mixers count
        # (``hybrid.load_names``, the one declaration of the vector's
        # entries). Empty for a dense uniform model.
        self._load_names = hybrid.load_names(cfg)
        self._moe_load = np.zeros(len(self._load_names), np.int64)
        # the entries that the profiler's cumulative counters carry, by
        # their names; ``moe_info`` has the rest
        self._load_profiled = tuple(
            i for i, name in enumerate(self._load_names)
            if name in CUMULATIVE_KEYS)
        # group pre-ref registry: leader publish pre-takes group_size-1 refs
        # on the shared prefix entries so pool-pressure eviction can't race
        # the siblings' attach; consumed per attach, TTL-swept for groups
        # whose siblings never arrive, disbanded on any cache flush.
        # Guarded by _pool_lock (same discipline as the prefix cache).
        self._group_prerefs: dict[str, dict] = {}
        self.group_preref_ttl_s = float(o.group_preref_ttl_s)

        # shared-prefix decode attention (ARCHITECTURE.md "Shared-prefix
        # decode attention"): decode group table — group_id → the group's
        # shared prefix page chain + the live member slots. Decode
        # dispatches with >=2 live members per group route through the
        # two-phase grouped paged-attention kernel (ONE HBM stream of the
        # prompt KV per group instead of one per sibling); singleton
        # leftovers and decode_group_share=False degrade to the ungrouped
        # kernel (bitwise the pre-PR decode path). Loop-thread only.
        self.decode_group_share = (
            bool(o.decode_group_share) and not self.stateful
            and not self._no_kernel["decode_group_share"])
        self._decode_groups: dict[str, dict] = {}
        self._slot_decode_gid: dict[int, str] = {}
        self._grouped_attn = None  # built lazily (TP wrapper under a mesh)
        self.grouped_decode_dispatches = 0  # dispatches that ran grouped

        # token-level continuous generation (partial-rollout salvage): on
        # abort/preempt/shutdown the run-ahead pipeline is DRAINED into the
        # stream instead of dropped, the terminal line is a partial the
        # manager/trainer resume from, and the decoded pages are published
        # to the prefix cache so a continuation landing back here re-uses
        # the KV. False restores fastest-abort semantics (drop in-flight).
        self.salvage_partials = bool(o.salvage_partials)
        self.tokens_salvaged = 0   # tokens flushed into abort partials
        self.salvage_published_pages = 0  # decoded pages kept via the cache

        # serving telemetry (server_info contract). last_gen_throughput is
        # EWMA-smoothed (flightdeck.ThroughputEWMA): heartbeat-sampled
        # consumers (manager stats poller, /statusz) must not alias on one
        # fast/slow drain tick.
        self.weight_version = 0
        # _recover() calls: the loop survives a failed iteration by
        # resetting, so a kernel the compiler refuses ends as failed
        # requests, not a dead process — this count is how a caller in
        # the same process (chip_smoke.py) tells the two apart
        self.recoveries = 0
        self.num_running = 0
        self.num_queued = 0
        self.last_gen_throughput = 0.0
        self.total_tokens_served = 0
        self._tok_window: collections.deque = collections.deque(maxlen=64)
        self._tput_ewma = ThroughputEWMA()
        # engine flight deck: per-request lifecycle (queue wait / TTFT /
        # TPOT / token counts) + scheduler occupancy ledger, with exact
        # request-vs-scheduler token reconciliation (flightdeck.py)
        self.deck = EngineFlightDeck(max_slots, self.num_pages, page_size)
        # speculative acceptance ceiling: tokens the spec dispatches COULD
        # have emitted (active_slots * rounds * (spec_tokens+1) each) —
        # spec_emitted / this ratio is the acceptance-rate gauge
        self.spec_token_ceiling = 0
        # engine-loop profiler (obs/engine_profile.py): the one seam that
        # times an engine phase (each also a TraceAnnotation on the device
        # trace's clock), the completion-stamp counters, the windowed
        # device/host gauges. rollout.loop_profile=False leaves the loop
        # without it: the profiler never touches RNG, device state or
        # scheduling, only clocks around them.
        self.profiler = EngineLoopProfiler() if o.loop_profile else None

    def _phase(self, name: str):
        """Profiler phase context for ``name`` (no-op when off)."""
        prof = self.profiler
        return prof.phase(name) if prof is not None else _NULL_PHASE

    def _fetch_scope(self):
        """The fetcher thread's blocking transfer (``engine/fetch``)."""
        prof = self.profiler
        return prof.fetch() if prof is not None else _NULL_PHASE

    def _landed(self, batch: list, fetched: list) -> None:
        """The oldest ``len(batch)`` queued dispatch outputs reached the
        host as ``fetched``: the completion-stamp counters move, and with
        them (the same steps) the load the decode steps counted."""
        # the entries the profiler carries move by what these steps added
        rows = self._load_profiled if self.profiler is not None else ()
        before = self._moe_load.copy() if rows else None
        for entry, arrs in zip(batch, fetched):
            if entry[0] == "step" and arrs[3] is not None:
                self._moe_load += arrs[3]
        if self.profiler is not None:
            if rows:
                self.profiler.on_cache_rows({
                    self._load_names[i]: int(self._moe_load[i] - before[i])
                    for i in rows})
            stalled_s = self.profiler.on_landed(len(batch))
            if stalled_s is not None:
                self._log_stall(stalled_s)

    def _log_stall(self, gap_s: float) -> None:
        """The one record of a stall (``engine_profile.STALL_GAP_S``), at
        the landing that ended it, from the thread that landed it and
        before the others have moved far. Reads no lock: a thread that is
        stuck may hold it."""
        from polyrl_tpu.rollout.kvledger import hbm_truth

        devices = self.kvledger.devices if self.kvledger is not None else None
        used_gb = hbm_truth(0.0, devices).get("hbm_used_gb")
        log.warning(
            "stall: %.2f s between landings with work outstanding "
            "throughout; loop thread in phase %r, fetcher held %d "
            "dispatches, %d more outstanding (_emit_q), %d landed and not "
            "emitted (_fetched_q), device memory in use %s",
            gap_s, self.profiler.loop_open_phase(), self._fetch_inflight,
            len(self._emit_q), len(self._fetched_q),
            "unknown" if used_gb is None else f"{used_gb:.2f} GB")

    def loop_profile_info(self) -> dict:
        """Flat server_info fields for the loop profiler ({} when off).
        Safe from HTTP handler threads: the profiler locks internally."""
        if self.profiler is None:
            return {}
        return self.profiler.server_info_fields()

    def loop_profile_snapshot(self) -> dict:
        """The /statusz ``engine.loop`` block (always present: a disabled
        profiler reports ``{"enabled": False}`` so one curl answers
        whether the plane is on)."""
        if self.profiler is None:
            return {"enabled": False}
        return self.profiler.snapshot()

    # -- KV memory plane (rollout/kvledger.py) -------------------------------

    # cache-side free causes → ledger taxonomy
    _CACHE_CAUSE = {"capacity": "cache_pressure", "flush": "flush",
                    "preref_ttl": "preref_ttl"}

    def _free_cache_pages(self, pages: list[int]) -> None:
        """The prefix cache's free callback: return the pages to the
        allocator, then attribute them in the ledger with the cause the
        cache booked just before calling (PrefixCache._free)."""
        self.allocator.free(pages)
        if self.kvledger is not None:
            cause = getattr(self.prefix_cache, "last_free_cause", "capacity")
            self.kvledger.on_free(pages,
                                  self._CACHE_CAUSE.get(cause,
                                                        "cache_pressure"))

    def _accounted_bytes(self) -> float:
        """Bytes the ledger can attribute on device: KV pools + weights
        (weights cached — the tree never changes size across swaps)."""
        if self._weight_bytes is None:
            self._weight_bytes = sum(
                int(x.nbytes) for x in jax.tree_util.tree_leaves(self.params)
                if hasattr(x, "nbytes"))
        pool_b = 0
        pools = self._pools
        if pools is not None:
            pool_b = sum(int(x.nbytes)
                         for x in jax.tree_util.tree_leaves(pools)
                         if hasattr(x, "nbytes"))
        if self.kvledger is not None and pool_b:
            # bytes a page: the paged arrays', not a recurrent state's
            # (a model of several kinds of layer: (paged, state rows); a
            # layer may have a part in both)
            paged = pools if cache_spec.is_uniform(self.cfg) else pools[0]
            self.kvledger.page_bytes = sum(
                int(x.nbytes) for x in jax.tree_util.tree_leaves(paged)
            ) // max(1, self.num_pages)
        return float(self._weight_bytes + pool_b)

    def _cache_pages(self) -> int:
        return (self.prefix_cache.num_entries
                if self.prefix_cache is not None else 0)

    def moe_info(self) -> dict:
        """Flat cumulative server_info fields of the load a routed model's
        decode steps counted, landed so far, under the names of
        ``hybrid.load_names`` ({} for a dense model): the MoE blocks' (in a
        model of several kinds of layer ``moe_choices`` too: every choice
        of a live row, held here or not, where ``moe_routed`` counts the
        held ones) and what the plan's mixers count."""
        if not self.cfg.num_experts:
            return {}
        return {name: int(v)
                for name, v in zip(self._load_names, self._moe_load)
                if name not in CUMULATIVE_KEYS}

    def recurrent_state(self, rid: str):
        """What the slot of the running request ``rid`` holds of its
        recurrent state now: (tokens it has consumed, prompt and fed-back
        answer alike; one float32 array a layer that keeps a slot, in
        order: a KDA layer's ``[H, Dk, Dv]`` state, a CCA layer's three
        tails side by side ``[(K0-1 + K1-1) * C + Hkv*D/2]``), on the
        host. None for a model without such a state or a
        request that is not decoding. Waits for the programs in flight;
        the read-only half of a state snapshot (ROADMAP M8)."""
        if not self.stateful:
            return None
        with self._pool_lock:
            for i, info in enumerate(self._slots):
                if (info is not None and self._active[i]
                        and info.req.rid == rid):
                    break
            else:
                return None
            self._ensure_dev_state()
            consumed = int(np.asarray(self._dev_state["seq_lens"])[i])
            rows = hybrid.held_state(self.cfg, self._pools[1], i)
        return consumed, rows

    def kv_memory_info(self) -> dict:
        """Flat server_info fields for the memory plane ({} when the
        ledger is off). Safe from HTTP handler threads: the ledger locks
        internally and the pool reads are atomic snapshots."""
        if self.kvledger is None:
            return {}
        return self.kvledger.server_info_fields(
            self.allocator.free_count, self._cache_pages(),
            self._accounted_bytes())

    def kv_memory_snapshot(self) -> dict:
        """The /statusz ``memory`` section ({} when the ledger is off).
        The ledger owns the spill page/byte counters; the host-pool truth
        (residency, capacity, copy-lane depth) merges in as
        ``spill.host``."""
        if self.kvledger is None:
            return {}
        snap = self.kvledger.snapshot(
            self.allocator.free_count, self._cache_pages(),
            self._accounted_bytes())
        if self.kvspill is not None:
            snap.setdefault("spill", {})["host"] = self.kvspill.stats()
        return snap

    # -- host-RAM KV spill tier (rollout/kvspill.py) -------------------------

    def _drop_spilled_entries(self, entries: list) -> None:
        """Spilled content died without a restore (cache flush, stale-
        squatter replacement, engine stop): free the host tier and settle
        the ledger — the physical pages were freed at spill time."""
        handles = [e.spill_handle for e in entries if e.spilled]
        for e in entries:
            e.spilled = False
            e.spill_handle = -1
        if not handles:
            return
        self.kvspill.drop(handles)
        if self.kvledger is not None:
            self.kvledger.on_spill_drop(len(handles))

    def _spill_sweep(self) -> None:
        """Per-dispatch watermark check (loop thread, off the traced hot
        path — the same seam as the ledger's residency sweep): page util
        at or over the HIGH watermark spills cold unreferenced published
        pages down toward the LOW watermark. The high/low gap is the
        hysteresis band — demand restores land util between the marks
        without immediately re-arming the sweep, so spill/restore cannot
        thrash page-by-page at a single threshold."""
        n = max(1, self.num_pages - 1)
        util = 1.0 - self.allocator.free_count / n
        if util < self.kv_spill_high_watermark:
            return
        target = int(np.ceil((util - self.kv_spill_low_watermark) * n))
        if target > 0:
            self._spill_pages(target, cold_only=True)

    def _spill_pages(self, target: int, cold_only: bool) -> int:
        """Page out up to ``target`` unreferenced published prefix-cache
        pages, coldest first (``cold_only`` restricts to the ledger's cold
        tier — the sweep's proactive mode; allocation pressure relaxes it
        to any unreferenced published page, still coldest-first, because
        spilling preserves the KV that plain eviction would destroy).
        Returns how many pages were spilled.

        The extraction slices are independent device buffers ordered after
        every previously dispatched write by the pools data dependency, so
        the physical pages return to the allocator immediately; nothing
        can rewrite them until a later prefill reallocates them, which the
        same dependency orders after the extraction."""
        if (self.kvspill is None or self.kvledger is None
                or self._pools is None or target <= 0):
            return 0
        if not self.kvspill.lane_free():
            return 0  # copy lane full: double-buffer backpressure
        with self._phase("spill_sweep"):
            return self._spill_pages_inner(target, cold_only)

    def _spill_pages_inner(self, target: int, cold_only: bool) -> int:
        age = self.kvledger.idle_age
        cands = [(age(e.page), e) for e in self.prefix_cache.spill_candidates()]
        if cold_only:
            cands = [c for c in cands if c[0] >= self.kvledger.cold_after]
        if not cands:
            return 0
        cands.sort(key=lambda c: (-c[0], c[1].tick))
        page_bytes = int(self.kvledger.page_bytes)
        if page_bytes <= 0:
            self._accounted_bytes()  # sets ledger.page_bytes from the pools
            page_bytes = int(self.kvledger.page_bytes)
        take = min(target, len(cands))
        while take > 0 and not self.kvspill.can_spill(take, page_bytes):
            take -= 1  # host capacity: spill what fits, never evict here
        if take <= 0:
            return 0
        entries = [e for _age, e in cands[:take]]
        pages = [e.page for e in entries]
        kp, vp = self._pools
        idx = jnp.asarray(np.asarray(pages, np.int32))
        k_dev = jnp.stack([kp[layer][:, idx] for layer in range(len(kp))])
        v_dev = jnp.stack([vp[layer][:, idx] for layer in range(len(vp))])
        handles = self.kvspill.spill(k_dev, v_dev, len(pages), page_bytes)
        for e, h in zip(entries, handles):
            e.spilled = True
            e.spill_handle = h
        self.allocator.free(pages)
        self.kvledger.on_spill(pages)
        return len(pages)

    def _restore_matched(self, matched_entries: list
                         ) -> tuple[list[int], list]:
        """A prefix-cache match landed on spilled entries: restore them
        into fresh physical pages before the attach (restore-then-attach).
        If pages for the full chain cannot be found, the chain truncates
        at the first still-spilled entry (the dropped tail's match refs
        are released) — a shorter hit, never a corrupt one. Returns the
        (possibly truncated) page list + entry list."""
        spilled = [e for e in matched_entries if e.spilled]
        if spilled and not self._restore_entries(spilled):
            cut = next(i for i, e in enumerate(matched_entries) if e.spilled)
            self.prefix_cache.release(matched_entries[cut:])
            matched_entries = matched_entries[:cut]
        return [e.page for e in matched_entries], matched_entries

    def _restore_entries(self, entries: list) -> bool:
        """Batch-restore spilled entries into freshly allocated physical
        pages (host→device, one scatter per layer). The new physical index
        is fine: every consumer goes through the page-table indirection,
        and decode-group seating keys on exact physical chains so a
        restored chain simply decodes solo. Returns False (nothing
        restored) when no pages can be found even after spilling colder
        pages / evicting the cache."""
        with self._phase("restore"):
            return self._restore_entries_inner(entries)

    def _restore_entries_inner(self, entries: list) -> bool:
        need = len(entries)
        pages = self._retry_landed(lambda: self.allocator.alloc(need))
        if pages is None:
            # colder spillable pages can make room without losing KV;
            # the entries being restored are already spilled, so they are
            # not candidates — no recursion, no self-displacement
            if self._spill_pages(need - self.allocator.free_count,
                                 cold_only=False):
                pages = self.allocator.alloc(need)
        if pages is None and self.prefix_cache.evict(
                need - self.allocator.free_count):
            pages = self.allocator.alloc(need)
        if pages is None:
            return False
        k_host = np.stack([self.kvspill.fetch(e.spill_handle)[0]
                           for e in entries], axis=2)
        v_host = np.stack([self.kvspill.fetch(e.spill_handle)[1]
                           for e in entries], axis=2)
        kp, vp = self._pools
        idx = jnp.asarray(np.asarray(pages, np.int32))
        self._pools = (
            tuple(kp[layer].at[:, idx].set(
                jnp.asarray(k_host[layer]).astype(kp[layer].dtype))
                for layer in range(len(kp))),
            tuple(vp[layer].at[:, idx].set(
                jnp.asarray(v_host[layer]).astype(vp[layer].dtype))
                for layer in range(len(vp))))
        self.kvspill.drop([e.spill_handle for e in entries], restored=True)
        for e, p in zip(entries, pages):
            e.page = int(p)
            e.spilled = False
            e.spill_handle = -1
        if self.kvledger is not None:
            self.kvledger.on_restore(pages)
        return True

    def _shard_params_for_mesh(self, params):
        from polyrl_tpu.models.quant import (
            LoraWeight, QuantWeight, quant_param_specs,
        )
        from polyrl_tpu.parallel import mesh as meshlib

        wrappers = (QuantWeight, LoraWeight)
        leaves = jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, wrappers))
        has_quant = any(
            isinstance(x, QuantWeight)
            or (isinstance(x, LoraWeight) and isinstance(x.base, QuantWeight))
            for x in leaves)
        has_lora = any(isinstance(x, LoraWeight) for x in leaves)
        specs = decoder.param_specs(self.cfg)
        if has_quant:
            specs = quant_param_specs(specs)
        if has_lora:
            # wrapper specs must mirror the wrapper tree or the path-keyed
            # lookup misses every wrapped leaf → silent full replication
            from polyrl_tpu.models.lora import lora_param_specs

            specs = lora_param_specs(specs)
        return meshlib.shard_params(self.mesh, params, specs)

    def _make_pools(self):
        """Paged KV pools; under a mesh, each layer's [Hkv, N, ps, D] pool
        shards its head dim over tp (matching the attention einsums the
        params induce, decoder.cache_specs rationale)."""
        pools = decoder.make_paged_pools(
            self.cfg, self.num_pages, self.page_size,
            dtype=self.kv_cache_dtype, slots=self.max_slots + 1)
        if self.mesh is None:
            return pools
        from jax.sharding import NamedSharding, PartitionSpec as P

        from polyrl_tpu.parallel.mesh import TP

        sh = NamedSharding(self.mesh, P(TP, None, None, None))
        return tuple(tuple(jax.device_put(a, sh) for a in side)
                     for side in pools)

    # -- compiled pieces ----------------------------------------------------

    def _program(self, table: dict, kind: str, key, jitted) -> None:
        """Enter a newly jitted program into ``table``. Its first call
        (trace, lower, compile or cache read, enqueue) is timed, logged,
        annotated on the device trace and counted as a build: a shape the
        warm-up missed shows by name, not as a stall. Each program's
        function has a name of its own, which is its module's name on the
        device trace (``jit_<name>``). Under a mesh every call is made with
        the mesh set (``parallel.mesh.under``)."""
        from polyrl_tpu.parallel.mesh import under

        jitted = under(self.mesh, jitted)

        @functools.wraps(jitted)
        def first_call(*args, **kwargs):
            table[key] = jitted
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(f"engine/build {kind} {key}"):
                out = jitted(*args, **kwargs)
            dt = time.monotonic() - t0
            log.info("built program %s %s: %.2fs to first return",
                     kind, key, dt)
            if self.profiler is not None:
                self.profiler.on_build(kind, key, dt)
            return out

        table[key] = first_call

    def _get_step(self, use_filters: bool, k: int = 1, gshape=None):
        """``k`` fused decode steps per dispatch, state advanced on device.

        The host loop keeps np mirrors for admission decisions but never
        re-uploads state between steps (each host→device array is a
        transfer round trip — at ~10 uploads + 3 fetches per step the old
        loop was round-trip-bound). Fusing k steps into one
        ``lax.scan`` divides the remaining per-dispatch overhead (enqueue
        RPC + fetch RTT + host bookkeeping) by k as well — the same
        multi-step scheduling vLLM/SGLang use, but expressed as a compiled
        on-device loop. Slots that finish mid-scan go inactive and emit pad
        tokens for the remaining iterations (filtered host-side); inactive
        slots' KV writes are routed to the null page (their freed pages may
        already belong to another request — see forward_paged_decode's
        ``active`` mask). Outputs are [k, slots].

        ``gshape=(ng, gmax, p_pre)`` compiles the shared-prefix GROUPED
        variant: the step takes one extra packed int32 vector carrying the
        dispatch's decode-group tables (seat matrix, shared prefix pages,
        prefix lengths — traced data, so membership churn never retraces)
        and the decode attention routes through the two-phase grouped
        kernel. The shape triple is bucketed by ``_decode_group_pack`` so
        the jit cache stays bounded; ``gshape=None`` is the unchanged
        ungrouped step (bitwise the pre-grouping compiled fn — the
        ``decode_group_share=false`` / singleton degrade path)."""
        key = (use_filters, k, gshape)
        if key not in self._step_fns:
            cfg, pad = self.cfg, self.pad_token_id
            fused = self._samples_in_head(use_filters)
            paged_attn = self._tp_paged_attn()
            kv_write = self._tp_kv_write()
            grouped_attn = self._grouped_attn_fn() if gshape else None
            ng, gmax, p_pre = gshape or (0, 0, 0)

            def step(params, kp, vp, rng, page_table, seq_lens, last_tokens,
                     n_generated, budgets, active, temps, top_ps, top_ks,
                     stop_table, group_pack=None):
                if gshape is not None:
                    o = ng * gmax
                    with jax.named_scope("glue"):   # the groups' tables
                        g_slots = group_pack[:o].reshape(ng, gmax)
                        g_pages = group_pack[
                            o:o + ng * p_pre].reshape(ng, p_pre)
                        g_lens = group_pack[
                            o + ng * p_pre:o + ng * p_pre + ng]

                    def attn(q, kp_, vp_, pt, lens):
                        return grouped_attn(q, kp_, vp_, pt, lens, g_slots,
                                            g_pages, g_lens)
                else:
                    attn = paged_attn

                def body(carry, _):
                    kp, vp, rng, seq_lens, last_tokens, n_generated, active = carry
                    head_fn = None
                    if fused:  # token and log-prob come out of the head
                        with jax.named_scope("sample"):
                            rng, sub = jax.random.split(rng)
                        head_fn = functools.partial(
                            decoder.head_and_sample, rng=sub, temps=temps)
                    out, (kp, vp), moe_load = decoder.forward_paged_decode(
                        params, cfg, last_tokens, seq_lens, (kp, vp),
                        page_table, seq_lens, active=active,
                        attn_fn=attn, kv_write_fn=kv_write, head_fn=head_fn)
                    with jax.named_scope("sample"):
                        if fused:
                            token, logp = out
                        else:
                            rng, sub = jax.random.split(rng)
                            token, logp = sample_token_vec(
                                out, sub, temps, top_ps, top_ks,
                                use_filters=use_filters)
                        n_gen = n_generated + active.astype(jnp.int32)
                        hit_stop = jnp.any(token[:, None] == stop_table,
                                           axis=-1)
                        done = active & (hit_stop | (n_gen >= budgets))
                        token = jnp.where(active, token, pad)
                        logp = jnp.where(active, logp, 0.0)
                        new_active = active & ~done
                        new_seq = seq_lens + active.astype(jnp.int32)
                        new_last = jnp.where(active, token, last_tokens)
                    return ((kp, vp, rng, new_seq, new_last, n_gen, new_active),
                            (token, logp, done, moe_load))

                carry, (token, logp, done, moe_load) = jax.lax.scan(
                    body,
                    (kp, vp, rng, seq_lens, last_tokens, n_generated, active),
                    None, length=k)
                kp, vp, rng, seq_lens, last_tokens, n_generated, active = carry
                if moe_load is not None:   # a MoE model: [k, 3] -> [3]
                    with jax.named_scope("glue"):
                        moe_load = jnp.sum(moe_load, axis=0)
                return (kp, vp, rng, token, logp, done, seq_lens,
                        last_tokens, n_generated, active, moe_load)

            self._program(self._step_fns, "step", key, jax.jit(
                step, donate_argnums=(1, 2, 5, 6, 7, 9), static_argnames=()))
        return self._step_fns[key]

    def _get_spec_step(self, use_filters: bool, m: int, rounds: int):
        """``rounds`` fused speculation rounds per dispatch, fully
        device-resident. Each round: propose m-1 draft tokens per slot via
        n-gram lookup (trigram preferred) in the device token buffer
        (:func:`device_ngram_propose`), verify all m (the newest real token
        + drafts) in ONE forward, rejection-sample the accepted prefix + 1,
        and write the emitted tokens back into the buffer for the next
        round's lookup. The verify forward IS ``forward_paged_decode`` on
        S·m flattened 'virtual slots' — token (s, i) is a row at position
        seq_lens[s]+i sharing slot s's page table, so the paged-attention
        kernel and KV scatter are reused unchanged; within a layer all m
        rows' KV is scattered before the attention reads, giving exact
        causal semantics. Outputs are [rounds·m, slots] rows + an
        ``emitted`` mask (rejected-draft rows are not real emissions)."""
        key = ("spec", use_filters, m, rounds)
        if key not in self._step_fns:
            cfg, pad = self.cfg, self.pad_token_id
            paged_attn = self._tp_paged_attn()
            kv_write = self._tp_kv_write()
            page_size = self.page_size

            def spec_step(params, kp, vp, rng, tok_buf, page_table,
                          seq_lens, last_tokens, n_generated, budgets,
                          active, temps, top_ps, top_ks, stop_table):
                s = seq_lens.shape[0]
                buf_len = tok_buf.shape[1]
                rows = jnp.arange(s)
                max_pos = page_table.shape[1] * page_size
                pt_rep = jnp.repeat(page_table, m, axis=0)

                def one_round(carry, _):
                    (kp, vp, rng, tok_buf, seq_lens, last_tokens,
                     n_generated, active) = carry
                    # splice the newest (KV-pending) token into the
                    # history — prefill-sampled first tokens arrive this
                    # way; idempotent for tokens this fn wrote itself
                    tok_buf = tok_buf.at[
                        rows, jnp.clip(seq_lens, 0, buf_len - 1)
                    ].set(last_tokens)
                    draft = device_ngram_propose(tok_buf, seq_lens + 1,
                                                 m - 1)
                    tokens_in = jnp.concatenate(
                        [last_tokens[:, None], draft], 1)
                    pos = (seq_lens[:, None]
                           + jnp.arange(m, dtype=jnp.int32)[None])
                    # rows past the slot's page capacity write to the null
                    # page (garbage logits; budgets stop emission first)
                    okf = (pos < max_pos) & active[:, None]
                    logits, (kp, vp), _moe = decoder.forward_paged_decode(
                        params, cfg, tokens_in.reshape(s * m),
                        pos.reshape(s * m), (kp, vp), pt_rep,
                        pos.reshape(s * m), active=okf.reshape(s * m),
                        attn_fn=paged_attn, kv_write_fn=kv_write)
                    logits = logits.reshape(s, m, -1)
                    with jax.named_scope("sample"):
                        rng, sub = jax.random.split(rng)
                        toks, logps, n_acc = spec_verify_sample_vec(
                            logits, draft, sub, temps, top_ps, top_ks,
                            use_filters)
                    # sequential stop/budget semantics over the prefix
                    stopped = jnp.zeros_like(active)
                    n_gen = n_generated
                    emit_cnt = jnp.zeros((s,), jnp.int32)
                    last_emitted = last_tokens
                    out_t, out_l, out_d, out_e = [], [], [], []
                    for i in range(m):  # static unroll, m is small
                        want = active & ~stopped & (i <= n_acc)
                        tok_i = jnp.where(want, toks[:, i], pad)
                        n_gen = n_gen + want.astype(jnp.int32)
                        hit = (jnp.any(tok_i[:, None] == stop_table, axis=-1)
                               & want)
                        done_i = want & (hit | (n_gen >= budgets))
                        out_t.append(tok_i)
                        out_l.append(jnp.where(want, logps[:, i], 0.0))
                        out_d.append(done_i)
                        out_e.append(want)
                        stopped = stopped | done_i
                        emit_cnt = emit_cnt + want.astype(jnp.int32)
                        last_emitted = jnp.where(want, toks[:, i],
                                                 last_emitted)
                    # write emitted tokens into the history at
                    # seq+1 .. seq+emit_cnt (masked rows re-write their
                    # current value — a no-op)
                    emit_mask = jnp.stack(out_e, axis=1)        # [S, m]
                    widx = jnp.clip(pos + 1, 0, buf_len - 1)
                    cur = jnp.take_along_axis(tok_buf, widx, axis=1)
                    tok_buf = tok_buf.at[rows[:, None], widx].set(
                        jnp.where(emit_mask, toks, cur))
                    carry = (kp, vp, rng, tok_buf, seq_lens + emit_cnt,
                             last_emitted, n_gen, active & ~stopped)
                    return carry, (jnp.stack(out_t), jnp.stack(out_l),
                                   jnp.stack(out_d), jnp.stack(out_e))

                carry = (kp, vp, rng, tok_buf, seq_lens, last_tokens,
                         n_generated, active)
                carry, (t, l, d, e) = jax.lax.scan(one_round, carry, None,
                                                   length=rounds)
                (kp, vp, rng, tok_buf, seq_lens, last_tokens, n_generated,
                 active) = carry
                # [rounds, m, S] → [rounds·m, S] rows in emission order
                return (kp, vp, rng, tok_buf,
                        t.reshape(rounds * m, s), l.reshape(rounds * m, s),
                        d.reshape(rounds * m, s), e.reshape(rounds * m, s),
                        seq_lens, last_tokens, n_generated, active)

            self._program(self._step_fns, "spec_step", key, jax.jit(
                spec_step, donate_argnums=(1, 2, 4, 6, 7, 8, 10)))
        return self._step_fns[key]

    def _many_chips(self) -> bool:
        """A mesh of more than one device: no Mosaic kernel lowers in its
        programs outside a shard_map over every axis, whatever ``tp`` is
        (an ``ep``-only mesh too; PERF.md section 6, PR 27)."""
        return self.mesh is not None and self.mesh.size > 1

    def _samples_in_head(self, use_filters: bool) -> bool:
        """Whether the decode step draws its tokens inside the output
        matmul (``decoder.samples_in_head``). One answer a ``use_filters``
        for the engine's life: a weight update keeps the tree's
        structure."""
        return decoder.samples_in_head(self.cfg, self.params, use_filters,
                                       self._many_chips())

    def _tp_paged_attn(self):
        """On a mesh of several chips the Pallas paged-attention custom
        call must be shard_mapped, over the head dim where tp > 1 (GSPMD
        cannot partition custom calls); None otherwise →
        forward_paged_decode's default."""
        if not self._many_chips():
            return None
        from polyrl_tpu.ops.paged_attention import make_tp_paged_attention

        return make_tp_paged_attention(self.mesh)

    def _grouped_attn_fn(self):
        """The grouped two-phase decode attention callable (built once):
        shard_mapped over the head dim on a mesh of several chips (same
        custom-call constraint as ``_tp_paged_attn``), the plain dispatcher (Pallas on
        TPU, jnp oracle elsewhere) otherwise. The group tables ride as
        replicated operands either way."""
        if self._grouped_attn is None:
            from polyrl_tpu.ops.paged_attention import (
                grouped_paged_attention,
                make_tp_grouped_paged_attention,
            )

            if self._many_chips():
                self._grouped_attn = make_tp_grouped_paged_attention(self.mesh)
            else:
                self._grouped_attn = grouped_paged_attention
        return self._grouped_attn

    def _tp_kv_write(self):
        """Same constraint as _tp_paged_attn for the Pallas K/V write
        kernel; None under no mesh -> forward_paged_decode's default."""
        if not self._many_chips():
            return None
        from polyrl_tpu.ops.paged_attention import make_tp_paged_kv_write

        return make_tp_paged_kv_write(self.mesh)

    def _insert_slot_state(self, st: dict, slot, prompt_len, token, done,
                           budget, temp, top_p, top_k, stop_row, row):
        """Device-side slot insertion shared by both prefill variants: the
        host never round-trips for admission (a blocking first-token fetch
        flushed the whole pipeline per request — admission-bound serving)."""
        st = dict(st)
        st["seq_lens"] = st["seq_lens"].at[slot].set(prompt_len)
        st["last_tokens"] = st["last_tokens"].at[slot].set(token)
        st["n_generated"] = st["n_generated"].at[slot].set(1)
        st["budgets"] = st["budgets"].at[slot].set(budget)
        st["active"] = st["active"].at[slot].set(~done)
        st["temps"] = st["temps"].at[slot].set(temp)
        st["top_ps"] = st["top_ps"].at[slot].set(top_p)
        st["top_ks"] = st["top_ks"].at[slot].set(top_k)
        st["stop_table"] = st["stop_table"].at[slot].set(stop_row)
        st["page_table"] = st["page_table"].at[slot].set(row)
        return st

    _STATE_KEYS = ("page_table", "seq_lens", "last_tokens", "n_generated",
                   "budgets", "active", "temps", "top_ps", "top_ks",
                   "stop_table")

    # packed-buffer layout for fused prefill uploads: every per-request host
    # value rides ONE int32 vector (floats bitcast) — a dozen separate tiny
    # jnp.asarray uploads per admission dominated the admission cost
    _PACK_SCALARS = 8  # prompt/suffix_len, prefix_len, slot, budget, top_k,
                       # temp_bits, top_p_bits, (pad)

    def _pack_prefill(self, ids, page_ids, row, stops, prefix_ids,
                      len_a, len_b, slot, budget, sp) -> np.ndarray:
        parts = [np.asarray(ids, np.int32), np.asarray(page_ids, np.int32),
                 np.asarray(row, np.int32), np.asarray(stops, np.int32),
                 np.asarray(prefix_ids, np.int32),
                 np.array([len_a, len_b, slot, budget, sp.top_k,
                           np.float32(sp.temperature).view(np.int32),
                           np.float32(sp.top_p).view(np.int32), 0], np.int32)]
        return np.concatenate(parts)

    @staticmethod
    def _unpack_prefill(packed, pb, n_pg, pps, n_pre):
        ids = packed[:pb]; o = pb
        page_ids = packed[o:o + n_pg]; o += n_pg
        row = packed[o:o + pps]; o += pps
        stops = packed[o:o + MAX_STOP_TOKENS]; o += MAX_STOP_TOKENS
        prefix_ids = packed[o:o + n_pre]; o += n_pre
        sc = packed[o:]
        temp = jax.lax.bitcast_convert_type(sc[5], jnp.float32)
        top_p = jax.lax.bitcast_convert_type(sc[6], jnp.float32)
        return (ids, page_ids, row, stops, prefix_ids,
                sc[0], sc[1], sc[2], sc[3], sc[4], temp, top_p)

    def _get_prefill(self, pb: int, use_filters: bool):
        """Fused admission: prefill + sample + insert the slot into the
        device-resident control state, returning (token, logp, done) device
        scalars for DEFERRED emission. ``use_filters`` is a compile-time
        variant: the top-p/top-k sort over the vocab is ~a third of prefill
        wall time and most requests don't need it."""
        key = (pb, use_filters)
        if key not in self._prefill_fns:
            cfg = self.cfg
            n_pg, pps = pb // self.page_size, self.pages_per_slot

            def prefill_one(params, kp, vp, packed, rng, **state):
                (ids, page_ids, row, stop_row, _pre, prompt_len, _b, slot,
                 budget, top_k, temp, top_p) = self._unpack_prefill(
                    packed, pb, n_pg, pps, 0)
                (kp, vp), last_logits = decoder.prefill_into_pages(
                    params, cfg, ids, prompt_len, (kp, vp), page_ids, slot)
                with jax.named_scope("sample"):
                    rng, sub = jax.random.split(rng)
                    token, logp = sample_token_vec(
                        last_logits[None], sub, temp[None], top_p[None],
                        top_k[None], use_filters=use_filters)
                token, logp = token[0], logp[0]
                done = jnp.any(token == stop_row) | (budget <= 1)
                st = self._insert_slot_state(
                    state, slot, prompt_len, token, done, budget,
                    temp, top_p, top_k, stop_row, row)
                return kp, vp, rng, token, logp, done, st

            self._program(self._prefill_fns, "prefill_one", key, jax.jit(
                prefill_one, donate_argnums=(1, 2)))
        return self._prefill_fns[key]

    def _get_prefill_batch(self, pb: int, nb: int, use_filters: bool):
        """Fused BATCHED admission: nb requests prefill + sample + insert in
        ONE dispatch (admission dispatch count bounds serving throughput on
        dispatch-latency-bound links — 256 serialized admissions were the
        whole serve wall). ``packed`` is [nb, row]; wave padding rows target
        the dedicated SINK state row (see _ensure_dev_state) so their
        independently sampled tokens can't collide with a real slot."""
        key = ("batch", pb, nb, use_filters)
        if key not in self._prefill_fns:
            cfg = self.cfg
            n_pg, pps = pb // self.page_size, self.pages_per_slot

            def prefill_batch(params, kp, vp, packed, rng, **state):
                o = 0
                ids = packed[:, o:o + pb]; o += pb
                page_ids = packed[:, o:o + n_pg]; o += n_pg
                rows = packed[:, o:o + pps]; o += pps
                stop_rows = packed[:, o:o + MAX_STOP_TOKENS]; o += MAX_STOP_TOKENS
                sc = packed[:, o:]
                prompt_lens, slots = sc[:, 0], sc[:, 2]
                budgets, top_ks = sc[:, 3], sc[:, 4]
                temps = jax.lax.bitcast_convert_type(sc[:, 5], jnp.float32)
                top_ps = jax.lax.bitcast_convert_type(sc[:, 6], jnp.float32)
                (kp, vp), last_logits = decoder.prefill_batch_into_pages(
                    params, cfg, ids, prompt_lens, (kp, vp), page_ids, slots)
                with jax.named_scope("sample"):
                    rng, sub = jax.random.split(rng)
                    token, logp = sample_token_vec(
                        last_logits, sub, temps, top_ps, top_ks,
                        use_filters=use_filters)
                done = (jnp.any(token[:, None] == stop_rows, axis=-1)
                        | (budgets <= 1))
                st = dict(state)
                st["seq_lens"] = st["seq_lens"].at[slots].set(prompt_lens)
                st["last_tokens"] = st["last_tokens"].at[slots].set(token)
                st["n_generated"] = st["n_generated"].at[slots].set(1)
                st["budgets"] = st["budgets"].at[slots].set(budgets)
                st["active"] = st["active"].at[slots].set(~done)
                st["temps"] = st["temps"].at[slots].set(temps)
                st["top_ps"] = st["top_ps"].at[slots].set(top_ps)
                st["top_ks"] = st["top_ks"].at[slots].set(top_ks)
                st["stop_table"] = st["stop_table"].at[slots].set(stop_rows)
                st["page_table"] = st["page_table"].at[slots].set(rows)
                return kp, vp, rng, token, logp, done, st

            self._program(self._prefill_fns, "prefill_batch", key, jax.jit(
                prefill_batch, donate_argnums=(1, 2)))
        return self._prefill_fns[key]

    def _get_prefill_extend(self, pb: int, n_prefix_pg: int):
        """Chunked prefill's mid-chunk: fill the chunk's KV attending over
        the already-filled prefix pages — no sampling, no slot insertion
        (the FINAL chunk goes through the suffix path, which samples and
        activates the slot)."""
        key = ("ext", pb, n_prefix_pg)
        if key not in self._prefill_fns:
            cfg = self.cfg
            n_pg, pps = pb // self.page_size, self.pages_per_slot

            def prefill_extend(params, kp, vp, packed, rng):
                (ids, page_ids, _row, _stop, prefix_ids, suffix_len,
                 prefix_len, slot, *_rest) = self._unpack_prefill(
                    packed, pb, n_pg, pps, n_prefix_pg)
                (kp, vp), _ = decoder.prefill_suffix_into_pages(
                    params, cfg, ids, suffix_len, prefix_len, (kp, vp),
                    prefix_ids, page_ids, slot)
                return kp, vp, rng

            self._program(self._prefill_fns, "prefill_extend", key, jax.jit(
                prefill_extend, donate_argnums=(1, 2)))
        return self._prefill_fns[key]

    def _pack_suffix(self, tokens, suffix_len: int, prefix_len: int,
                     prefix_pages: list[int], sfx_pages: list[int],
                     row, stops, slot: int, budget: int, sp,
                     pb: int | None = None, n_pre_b: int | None = None):
        """Shared packing for the suffix-attending prefill variants (cache
        hit, chunk extend, chunk final): returns (packed, pb, n_pre_b).
        ``pb``/``n_pre_b`` override the per-request buckets — the batched
        sibling attach packs every wave row to ONE (suffix, prefix-page)
        bucket pair."""
        if pb is None:
            pb = next_bucket(suffix_len, self.prompt_buckets)
        n_sfx_pages = -(-suffix_len // self.page_size)
        page_ids = np.zeros((pb // self.page_size,), np.int32)
        page_ids[:n_sfx_pages] = sfx_pages[:n_sfx_pages]
        if n_pre_b is None:
            n_pre_b = self.prefix_pages_floor
            while n_pre_b < len(prefix_pages):
                n_pre_b *= 2
        prefix_ids = np.zeros((n_pre_b,), np.int32)
        prefix_ids[:len(prefix_pages)] = prefix_pages
        ids = np.full((pb,), self.pad_token_id, np.int32)
        ids[:suffix_len] = tokens
        packed = self._pack_prefill(ids, page_ids, row, stops, prefix_ids,
                                    suffix_len, prefix_len, slot, budget, sp)
        return packed, pb, n_pre_b

    def _advance_chunk_job(self) -> None:
        """One chunk of the head chunked-prefill job — one dispatch per loop
        iteration, so decode steps interleave with long-prompt admission."""
        job = self._chunk_jobs[0]
        req = job["req"]
        if ((req.abort is not None and req.abort.is_set())
                or self.weight_version != job["version"]):
            # aborted, or a weight swap landed mid-job: the filled chunks'
            # KV belongs to the OLD weights — finishing (and publishing)
            # would mix weight versions into the freshly flushed prefix
            # cache. Abort; the manager's continuation layer re-dispatches.
            self._abort_chunk_job(self._chunk_jobs.popleft())
            return
        n_prompt = len(req.input_ids)
        remaining = n_prompt - job["pos"]
        chunk = job["chunk"]
        if remaining <= chunk:
            # final chunk: standard suffix admission (samples the first
            # token, activates the slot, publishes the whole prompt)
            self._chunk_jobs.popleft()
            self._slots[job["slot"]] = None  # _prefill_request re-creates
            try:
                self._prefill_request(
                    job["slot"], req, job["pages"], job["budget"],
                    matched_pages=job["matched_pages"],
                    matched_entries=job["matched_entries"],
                    own_prefix_pages=job["own_filled"])
            except Exception:
                # mirror _admit's failure contract: the job left the deque
                # and the slot placeholder, so no other path can clean it
                self.allocator.free(job["pages"])
                if self.kvledger is not None:
                    self.kvledger.on_free(job["pages"], "abort")
                if self.prefix_cache is not None:
                    self.prefix_cache.release(job["matched_entries"])
                self._emit_error(req, "prefill failed")
                raise  # pools may be donation-poisoned: _recover resets
            return
        pos = job["pos"]
        prefix_pages = (job["matched_pages"]
                        + job["pages"][:job["own_filled"]])
        n_chunk_pg = chunk // self.page_size
        chunk_pages = job["pages"][job["own_filled"]:
                                   job["own_filled"] + n_chunk_pg]
        packed, pb, n_pre_b = self._pack_suffix(
            req.input_ids[pos:pos + chunk], chunk, pos, prefix_pages,
            chunk_pages, np.zeros((self.pages_per_slot,), np.int32),
            np.full((MAX_STOP_TOKENS,), -1, np.int32), job["slot"], 0,
            req.sampling)
        fn = self._get_prefill_extend(pb, n_pre_b)
        # on failure the job still heads the deque: _recover's
        # _abort_chunk_jobs frees pages/entries and emits the terminal line
        kp, vp, self._rng = fn(self.params, self._pools[0], self._pools[1],
                               jnp.asarray(packed), self._rng)
        self._pools = (kp, vp)
        self.chunk_dispatches += 1
        if self.profiler is not None:
            self.profiler.on_dispatch("prefill_extend", lands=False)
        job["pos"] = pos + chunk
        job["own_filled"] += n_chunk_pg

    def _get_prefill_suffix(self, pb: int, n_prefix_pg: int, use_filters: bool):
        """Prefix-cache-hit fused prefill: compute only the suffix, attend
        over cached prefix pages. Compile key = (suffix bucket, prefix-page
        bucket) — both power-of-two-ish, so the cache stays small."""
        key = ("sfx", pb, n_prefix_pg, use_filters)
        if key not in self._prefill_fns:
            cfg = self.cfg
            n_pg, pps = pb // self.page_size, self.pages_per_slot

            def prefill_suffix(params, kp, vp, packed, rng, **state):
                (ids, page_ids, row, stop_row, prefix_page_ids, suffix_len,
                 prefix_len, slot, budget, top_k, temp, top_p) = \
                    self._unpack_prefill(packed, pb, n_pg, pps, n_prefix_pg)
                (kp, vp), last_logits = decoder.prefill_suffix_into_pages(
                    params, cfg, ids, suffix_len, prefix_len, (kp, vp),
                    prefix_page_ids, page_ids, slot)
                with jax.named_scope("sample"):
                    rng, sub = jax.random.split(rng)
                    token, logp = sample_token_vec(
                        last_logits[None], sub, temp[None], top_p[None],
                        top_k[None], use_filters=use_filters)
                token, logp = token[0], logp[0]
                done = jnp.any(token == stop_row) | (budget <= 1)
                st = self._insert_slot_state(
                    state, slot, prefix_len + suffix_len, token, done, budget,
                    temp, top_p, top_k, stop_row, row)
                return kp, vp, rng, token, logp, done, st

            self._program(self._prefill_fns, "prefill_suffix", key, jax.jit(
                prefill_suffix, donate_argnums=(1, 2)))
        return self._prefill_fns[key]

    def _get_prefill_suffix_batch(self, pb: int, nb: int, n_prefix_pg: int,
                                  use_filters: bool):
        """Batched sibling attach: ``nb`` full prefix hits with a UNIFORM
        prefix length prefill their suffixes + sample + insert in ONE
        dispatch (``decoder.prefill_suffix_batch_into_pages``). GRPO's
        G−1 siblings of a published prompt used to admit as G−1 serialized
        singleton suffix dispatches — admission dispatch count linear in
        the rollout count. Wave padding rows target the SINK state row,
        exactly like ``_get_prefill_batch``."""
        key = ("sfxb", pb, nb, n_prefix_pg, use_filters)
        if key not in self._prefill_fns:
            cfg = self.cfg
            n_pg, pps = pb // self.page_size, self.pages_per_slot

            def prefill_suffix_batch(params, kp, vp, packed, rng, **state):
                o = 0
                ids = packed[:, o:o + pb]; o += pb
                page_ids = packed[:, o:o + n_pg]; o += n_pg
                rows = packed[:, o:o + pps]; o += pps
                stop_rows = packed[:, o:o + MAX_STOP_TOKENS]; o += MAX_STOP_TOKENS
                prefix_ids = packed[:, o:o + n_prefix_pg]; o += n_prefix_pg
                sc = packed[:, o:]
                suffix_lens, slots = sc[:, 0], sc[:, 2]
                budgets, top_ks = sc[:, 3], sc[:, 4]
                # prefix_len is UNIFORM across the wave (attach contract);
                # row 0 is always a real request (padding is appended)
                prefix_len = sc[0, 1]
                temps = jax.lax.bitcast_convert_type(sc[:, 5], jnp.float32)
                top_ps = jax.lax.bitcast_convert_type(sc[:, 6], jnp.float32)
                (kp, vp), last_logits = decoder.prefill_suffix_batch_into_pages(
                    params, cfg, ids, suffix_lens, prefix_len, (kp, vp),
                    prefix_ids, page_ids, slots)
                with jax.named_scope("sample"):
                    rng, sub = jax.random.split(rng)
                    token, logp = sample_token_vec(
                        last_logits, sub, temps, top_ps, top_ks,
                        use_filters=use_filters)
                done = (jnp.any(token[:, None] == stop_rows, axis=-1)
                        | (budgets <= 1))
                st = dict(state)
                st["seq_lens"] = st["seq_lens"].at[slots].set(
                    prefix_len + suffix_lens)
                st["last_tokens"] = st["last_tokens"].at[slots].set(token)
                st["n_generated"] = st["n_generated"].at[slots].set(1)
                st["budgets"] = st["budgets"].at[slots].set(budgets)
                st["active"] = st["active"].at[slots].set(~done)
                st["temps"] = st["temps"].at[slots].set(temps)
                st["top_ps"] = st["top_ps"].at[slots].set(top_ps)
                st["top_ks"] = st["top_ks"].at[slots].set(top_ks)
                st["stop_table"] = st["stop_table"].at[slots].set(stop_rows)
                st["page_table"] = st["page_table"].at[slots].set(rows)
                return kp, vp, rng, token, logp, done, st

            self._program(
                self._prefill_fns, "prefill_suffix_batch", key,
                jax.jit(prefill_suffix_batch, donate_argnums=(1, 2)))
        return self._prefill_fns[key]

    def _sink_pad_row(self, pb: int, n_pre: int = 0) -> np.ndarray:
        """A packed prefill row targeting the SINK state row (index
        max_slots): budget 0 → immediately done/inactive, pages all null.
        Used for wave padding and warmup — a duplicated REAL row would
        scatter a conflicting sampled token into the real slot's
        last_tokens/active. ``n_pre`` sizes the (null) prefix-page vector
        for the suffix-prefill variants."""
        pad_sp = SamplingParams(temperature=1.0, top_p=1.0, top_k=0,
                                max_new_tokens=0, stop_token_ids=())
        return self._pack_prefill(
            np.full((pb,), self.pad_token_id, np.int32),
            np.zeros((pb // self.page_size,), np.int32),
            np.zeros((self.pages_per_slot,), np.int32),
            np.full((MAX_STOP_TOKENS,), -1, np.int32),
            np.zeros((n_pre,), np.int32),
            1, 0, self.max_slots, 0, pad_sp)

    def warmup(self, batch_sizes=(2, 4, 8), filter_variants=(False, True),
               suffix: bool = True) -> None:
        """Precompile every admission + decode dispatch variant
        deterministically, before serving traffic.

        Generate-based warmup ("run a few requests first") is unreliable:
        submission trickle and prefix-cache hits fragment admission waves,
        so the larger batch-prefill buckets may never compile during
        warmup — and then a multi-second XLA compile lands inside the
        first real serving burst (observed: ~17 s per bucket for an 8B
        model). This drives each compiled variant once with dummy rows
        targeting the SINK state row (slot index max_slots, null pages) —
        the same mechanism wave padding uses — so pools/state stay valid.
        """
        with self._pool_lock:
            self._ensure_dev_state()
            for pb in self.prompt_buckets:
                base = self._sink_pad_row(pb)
                for uf in filter_variants:
                    self._warm_call(self._get_prefill(pb, uf),
                                    jnp.asarray(base))
                    for nb in batch_sizes:
                        self._warm_call(
                            self._get_prefill_batch(pb, nb, uf),
                            jnp.asarray(np.stack([base] * nb)))
                    if suffix:
                        # prefix-cache-hit variants: power-of-two prefix-
                        # page buckets up to a full prompt's pages — the
                        # second request of a shared-system-prompt workload
                        # hits this path immediately
                        n_pre = 1
                        while n_pre <= max(1, pb // self.page_size):
                            self._warm_call(
                                self._get_prefill_suffix(pb, n_pre, uf),
                                jnp.asarray(self._sink_pad_row(pb, n_pre)))
                            n_pre *= 2
                    if (suffix and self.group_share
                            and pb == self.prompt_buckets[0]):
                        # batched sibling-attach variants: a true attach
                        # wave's suffix is ≤ page_size tokens (full-hit
                        # members), so only the FIRST suffix bucket ever
                        # dispatches — but the prefix-page bucket spans up
                        # to the largest prompt's pages. Warm the full-wave
                        # batch size only (a full GRPO group's siblings);
                        # smaller waves compile on first dispatch.
                        nb_full = max(batch_sizes)
                        n_pre = 1
                        while n_pre <= max(
                                1, self.prompt_buckets[-1] // self.page_size):
                            self._warm_call(
                                self._get_prefill_suffix_batch(
                                    pb, nb_full, n_pre, uf),
                                jnp.asarray(np.stack(
                                    [self._sink_pad_row(pb, n_pre)]
                                    * nb_full)))
                            n_pre *= 2
            for uf in filter_variants:
                st = self._dev_state
                if self.spec_tokens > 0:
                    # speculative engines route EVERY decode dispatch
                    # through the spec step — precompile it (the k-step
                    # variants would never run)
                    m = self.spec_tokens + 1
                    fn = self._get_spec_step(uf, m, self.spec_rounds)
                    (kp, vp, self._rng, st["tok_buf"], _t, _l, _d, _e,
                     st["seq_lens"], st["last_tokens"], st["n_generated"],
                     st["active"]) = fn(
                        self.params, self._pools[0], self._pools[1],
                        self._rng, st["tok_buf"], st["page_table"],
                        st["seq_lens"], st["last_tokens"],
                        st["n_generated"], st["budgets"], st["active"],
                        st["temps"], st["top_ps"], st["top_ks"],
                        st["stop_table"])
                else:
                    fn = self._get_step(uf, self.steps_per_dispatch)
                    (kp, vp, self._rng, _t, _l, _d, st["seq_lens"],
                     st["last_tokens"], st["n_generated"], st["active"],
                     _m) = fn(
                        self.params, self._pools[0], self._pools[1],
                        self._rng, st["page_table"], st["seq_lens"],
                        st["last_tokens"], st["n_generated"], st["budgets"],
                        st["active"], st["temps"], st["top_ps"],
                        st["top_ks"], st["stop_table"])
                self._pools = (kp, vp)
            jax.block_until_ready(self._pools[0][0])

    def _warm_call(self, fn, packed_dev) -> None:
        """One discarded dispatch of a prefill variant against the sink row
        (pools donated in, updated pools threaded back)."""
        state_kwargs = {k: self._dev_state[k] for k in self._STATE_KEYS}
        kp, vp, self._rng, _t, _l, _d, new_st = fn(
            self.params, self._pools[0], self._pools[1], packed_dev,
            self._rng, **state_kwargs)
        self._pools = (kp, vp)
        self._carry_spec_state(new_st, [])
        self._dev_state = new_st

    # -- submission API (server-facing) -------------------------------------

    def submit(self, rid: str, input_ids: list[int], sampling: SamplingParams,
               out: queue.Queue | None = None, abort=None,
               group_id: str = "", group_size: int = 0) -> queue.Queue:
        out = out if out is not None else queue.Queue()
        self._queue.put(_Request(rid, list(input_ids), sampling, out, abort,
                                 time.monotonic(), group_id=str(group_id),
                                 group_size=int(group_size)))
        self.num_queued = self._queue.qsize() + len(self._pending)
        return out

    def start(self) -> "CBEngine":
        if self._loop_thread is None:
            self._loop_thread = threading.Thread(target=self._loop, daemon=True)
            self._loop_thread.start()
        if self._fetch_thread is None:
            self._fetch_thread = threading.Thread(target=self._fetch_loop,
                                                  daemon=True)
            self._fetch_thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self.profiler is not None:
            self.profiler.on_stop()
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=10.0)
        if self._fetch_thread is not None:
            with self._fetch_cv:
                self._fetch_cv.notify_all()
            self._fetch_thread.join(timeout=10.0)
        if self.salvage_partials and self._pools is not None:
            # flush partials instead of dropping them: both engine threads
            # are joined, so the drain's dead-fetcher path lands every
            # dispatched output synchronously and the decoded tokens stream
            # out before the terminal lines below. Best-effort — a poisoned
            # pool must not wedge shutdown.
            try:
                self._drain_emit_q()
            except Exception:  # noqa: BLE001
                log.exception("shutdown salvage drain failed")
        self._abandon_pipeline()
        # every in-flight and queued request must still see a terminal line +
        # STREAM_END or its HTTP handler thread blocks forever. With salvage
        # on, in-flight requests end in a PARTIAL (abort) — the manager's
        # continuation resumes them elsewhere from the last streamed token —
        # instead of an error that would discard the decoded prefix.
        self._fail_all("engine shutdown",
                       finish_reason="abort" if self.salvage_partials
                       else "error")
        self._decode_groups.clear()
        self._slot_decode_gid.clear()
        if self.prefix_cache is not None:
            # a stopped engine's cached KV (including salvage-published
            # pages) is dead weight: hand every unreferenced page back so
            # page accounting balances after shutdown
            self._disband_group_prerefs()
            self.prefix_cache.flush()
        while self._chunk_jobs:
            job = self._chunk_jobs.popleft()
            self._emit_error(job["req"], "engine shutdown")
            self._finalize(job["slot"], cause="abort")
        self._drain_queue()
        while self._pending:
            self._emit_error(self._pending.popleft(), "engine shutdown")
        if self.kvspill is not None:
            # the cache flush above dropped every spilled entry (both
            # tiers freed); now join the copy lane thread
            self.kvspill.stop()

    # -- weight / memory lifecycle ------------------------------------------

    def update_weights(self, params: Any, version: int | None = None) -> None:
        # atomic ref swap; the loop picks it up on its next step (shapes and
        # shardings identical → the compiled step keeps working). Structure
        # must match exactly: a mismatch (e.g. a bf16 tree swapped into a
        # quantized engine — the caller should re-quantize first, see
        # server.weight_preprocess) would silently retrace every compiled
        # step and double weight HBM; fail loudly instead.
        import jax

        if (jax.tree_util.tree_structure(params)
                != jax.tree_util.tree_structure(self.params)):
            raise ValueError(
                "update_weights tree structure mismatch (quantized engines "
                "need the push re-quantized first — models/quant.py)")
        if self.mesh is not None:
            # keep the compiled step's layout: an in-process push from a
            # colocated trainer arrives host-side/replicated — without the
            # re-shard every weight swap would retrace the decode step (or
            # force the full unsharded tree through one chip's HBM)
            params = self._shard_params_for_mesh(params)
        self.params = params
        self.weight_version = self.weight_version + 1 if version is None else version
        if self.prefix_cache is not None:
            # cached KV belongs to the old weights (the reference flushes the
            # radix cache after every update, patches.py:374-377); group
            # pre-refs ride the entries being flushed — disband them first
            # or the orphans' pages stay pinned until the TTL sweep
            with self._pool_lock:
                self._disband_group_prerefs()
                self.prefix_cache.flush()

    def flush_prefix_cache(self) -> None:
        """Invalidate all cached prefix pages (public surface — weight
        updates do this implicitly; benchmarks/tests use it to isolate
        phases)."""
        with self._pool_lock:
            if self.prefix_cache is not None:
                self._disband_group_prerefs()
                self.prefix_cache.flush()

    def release_memory(self) -> None:
        """Pause serving and, once the decode batch drains, free the KV pool
        (real HBM release for colocated time-slicing — the manager aborts
        in-flight requests first, handlers.rs:500-513)."""
        self._paused.set()
        if self._idle.wait(timeout=30.0):
            with self._pool_lock:
                if not self._active.any():
                    # mid-chunk prefill jobs lose their filled KV with the
                    # pool — abort them (the manager's continuation layer
                    # re-dispatches aborted requests)
                    self._abort_chunk_jobs()
                    if self.prefix_cache is not None:
                        self._disband_group_prerefs()
                        self.prefix_cache.flush()
                    self._pools = None

    def resume_memory(self) -> None:
        with self._pool_lock:
            if self._pools is None:
                self._pools = self._make_pools()
        self._paused.clear()

    # -- engine loop ---------------------------------------------------------

    def _loop(self) -> None:
        prof = self.profiler
        while not self._stop.is_set():
            try:
                if prof is not None:
                    # each iteration is one profiler attribution window:
                    # phase self-times partition its wall, the leftover
                    # lands in the `other` residual (engine_profile.py)
                    with prof.iteration():
                        self._loop_iter()
                else:
                    self._loop_iter()
            except Exception:  # noqa: BLE001 — loop must survive anything:
                # a dead loop wedges every connected HTTP handler forever
                log.exception("engine iteration failed; resetting")
                self._recover()

    def _loop_iter(self) -> None:
        if self._paused.is_set():
            self._drain_emit_q()
            self._idle.set()
            with self._phase("idle"):
                time.sleep(0.02)
            return
        self._drain_queue()
        if (not self._pending and not self._active.any()
                and not self._chunk_jobs):
            self._drain_emit_q()  # drain only ever deactivates slots
            self.deck.on_idle()
            self._idle.set()
            try:
                with self._phase("idle"):
                    req = self._queue.get(timeout=0.05)
                self._pending.append(req)
            except queue.Empty:
                pass
            return
        self._idle.clear()
        with self._pool_lock:
            if self._paused.is_set():  # raced with release_memory
                return
            self._admit()
            if self._chunk_jobs:
                # one chunk per iteration: long-prompt admission interleaves
                # with the decode step below instead of monopolizing the
                # device for the whole prefill
                with self._phase("prefill_dispatch"):
                    self._advance_chunk_job()
            if self.prefill_first and self._chunk_jobs:
                # decode waits for the prompts; what has landed streams out
                self._emit_landed()
            elif self._active.any():
                self._step_once()
            elif self._pending and not self._chunk_jobs:
                with self._phase("idle"):
                    time.sleep(0.005)  # pending but blocked on pages/slots

    def _abort_chunk_job(self, job: dict) -> None:
        self._emit_abort(job["req"])
        self._finalize(job["slot"], cause="abort")
        if self.profiler is not None:
            # its mid-chunk dispatches return nothing that will ever land
            self.profiler.drop_outstanding(tail_only=True)

    def _abort_chunk_jobs(self) -> None:
        while self._chunk_jobs:
            self._abort_chunk_job(self._chunk_jobs.popleft())

    def _recover(self) -> None:
        """After any jit failure the pools may have been donated to the dead
        call; fail everything and reallocate so serving can continue."""
        self.recoveries += 1
        self._abandon_pipeline()
        self._fail_all("engine error")
        self._decode_groups.clear()
        self._slot_decode_gid.clear()
        with self._pool_lock:
            self._abort_chunk_jobs()
            if self.prefix_cache is not None:
                self._disband_group_prerefs()
                self.prefix_cache.flush()
            self._pools = self._make_pools()

    def _abandon_pipeline(self) -> None:
        """Forget every dispatch output not yet emitted (an engine reset
        or stop): nothing of it will be streamed."""
        with self._fetch_cv:
            # bump the epoch FIRST: results a still-running (or hung)
            # device_get lands after this point are dropped at emission
            # (slot generations would drop most anyway; the epoch also
            # covers mirrors)
            self._fetch_epoch += 1
            self._emit_q.clear()
            self._fetched_q.clear()
            self._fetch_exc = None
        self._last_two.clear()
        if self.profiler is not None:
            self.profiler.drop_outstanding()
        self._inflight_tok[:] = 0
        self._invalidate_dev_state()

    def _drain_queue(self) -> None:
        while True:
            try:
                self._pending.append(self._queue.get_nowait())
            except queue.Empty:
                break
        self.num_queued = len(self._pending)

    def _admit(self) -> None:
        with self._phase("accounting"):
            self._sweep_group_prerefs()
        self._admission_waiting = False
        while self._pending:
            with self._phase("collect_wave"):
                wave, kind = self._collect_wave()
            if not wave:
                break
            try:
                with self._phase("prefill_dispatch"):
                    if len(wave) == 1:
                        req, slot, pages, budget, mp, me = wave[0]
                        self._prefill_request(slot, req, pages, budget,
                                              mp, me)
                    elif kind == "attach":
                        self._prefill_attach_wave(wave)
                    else:
                        self._prefill_wave(wave)
                self.prefill_dispatches += 1
                self.deck.on_admit_wave(len(wave))
            except Exception:
                for req, _slot, pages, _b, _mp, me in wave:
                    self.allocator.free(pages)
                    if self.kvledger is not None:
                        self.kvledger.on_free(pages, "abort")
                    if self.prefix_cache is not None:
                        self.prefix_cache.release(me)
                    self._emit_error(req, "prefill failed")
                raise  # pools may be donation-poisoned: let _recover reset
        if self._admission_waiting:
            # deferred, not stalled: the loop goes on to dispatch, and a
            # request aborted while it waits (behind a starved head the
            # scan never reaches it) is released now, not when pages come
            self._drop_aborted_pending()
            if self.profiler is not None:
                self.profiler.on_admission_deferred()
        self.num_queued = len(self._pending)

    def _drop_aborted_pending(self) -> None:
        for req in [r for r in self._pending
                    if r.abort is not None and r.abort.is_set()]:
            self._pending.remove(req)
            self._emit_abort(req)
            self._consume_group_preref(req)  # sibling that never attaches

    def _collect_wave(self) -> tuple[list, str]:
        """Collect up to ``admit_wave`` admissible requests, taking a slot
        and the PROMPT's pages for each (what its answer needs comes as it
        is written, ``_grow_rows``): (req, slot, pages, budget,
        matched_pages, matched_entries), plus the wave kind:

        - ``"fresh"`` — no cached prefix anywhere in the wave: one batched
          full-prompt prefill (or a singleton).
        - ``"attach"`` — every member is a FULL prefix hit with the same
          prefix page count (GRPO siblings of a published prompt, or any
          equal-length full hits): one batched suffix dispatch. Partial
          hits stay singletons — their suffix publishes fresh pages, and
          two same-prompt partials in one dispatch would duplicate that
          publish instead of chaining off it.

        Admission reorder window: a head that cannot join the forming wave
        (a sibling waiting for its leader's publish, a prefix hit amid a
        fresh wave, a chunk-bound prompt) is SKIPPED — left pending while
        scanning continues — up to ``admit_reorder_window`` skips, instead
        of ``break``-ing admission for every unrelated request queued
        behind it. Page exhaustion still ends the scan: skipping past a
        page-starved head would let small requests starve big ones. So does
        a pool without headroom: a request comes in only while, its own
        pages taken, every row that is running could still take its next
        page (``_pages_in_reach``), so that a row which has just yielded its
        pages does not come back to take them from the next."""
        wave: list = []
        kind = "fresh"
        attach_len = -1  # prefix page count of a forming attach wave
        assigned: set[int] = set()
        wave_page_keys: set = set()
        chunk_keys = {job.get("first_key") for job in self._chunk_jobs}
        chunk_keys.discard(None)
        skipped = 0
        scan = 0

        def free_slots() -> list[int]:
            return [int(i) for i in np.flatnonzero(
                        ~self._active & np.asarray(
                            [s is None for s in self._slots]))
                    if int(i) not in assigned]

        while len(wave) < self.admit_wave and scan < len(self._pending):
            # a finished slot may stand among the outputs the fetcher has
            # landed since the last throttle: emit those and look again,
            # once, and never wait for the device (holding _pool_lock)
            free = free_slots() if wave else self._retry_landed(free_slots)
            if not free:
                self._admission_waiting = True
                break
            req = self._pending[scan]
            if req.abort is not None and req.abort.is_set():
                del self._pending[scan]
                self._emit_abort(req)
                self._consume_group_preref(req)  # sibling that never attaches
                continue
            n_prompt = len(req.input_ids)
            budget = min(req.sampling.max_new_tokens,
                         self.max_seq_len - n_prompt)
            # refused at once: an input no slot holds, and one that with
            # its whole answer is more than the pool (it could never end)
            if (n_prompt == 0 or n_prompt > self.max_seq_len - 1
                    or -(-(n_prompt + budget) // self.page_size)
                    > self.num_pages - 1):
                del self._pending[scan]
                self._emit_error(req, f"prompt length {n_prompt} unsupported")
                self._consume_group_preref(req)
                continue
            # the prompt's pages, the first decode step's write among them
            n_pages = -(-(n_prompt + 1) // self.page_size)
            n_full = max(0, (n_prompt - 1) // self.page_size)
            matched_pages: list[int] = []
            matched_entries: list = []
            first_key = None
            if self.prefix_cache is not None:
                matched_pages, matched_entries = self.prefix_cache.match(
                    req.input_ids)
                if self.kvspill is not None and any(
                        e.spilled for e in matched_entries):
                    # a hit on spilled KV restores-then-attaches: the
                    # chain lands in fresh physical pages (truncating at
                    # the first entry that cannot be restored)
                    matched_pages, matched_entries = \
                        self._restore_matched(matched_entries)
                if n_full > 0:
                    first_key = self.prefix_cache._keys_for(
                        req.input_ids, 1)[0]
            full_hit = bool(matched_pages) and len(matched_pages) == n_full
            # sibling wait: the prompt's first full page is being computed
            # by a request already in this wave (GRPO siblings of an
            # unpublished leader) or by an in-flight chunked prefill job —
            # admitting it now would recompute the prefix that is about to
            # be published (structurally defeating the cache)
            sibling_blocked = (not matched_pages and first_key is not None
                              and (first_key in wave_page_keys
                                   or first_key in chunk_keys))
            prefix_cached = len(matched_pages) * self.page_size
            chunk = self._chunk_tokens(n_prompt - prefix_cached)
            chunked = chunk > 0
            blocked = sibling_blocked
            if wave:
                if kind == "attach":
                    blocked = blocked or chunked or not (
                        full_hit and len(matched_pages) == attach_len)
                else:
                    blocked = blocked or chunked or bool(matched_pages)
            if blocked:
                if self.prefix_cache is not None:
                    self.prefix_cache.release(matched_entries)
                if skipped >= self.admit_reorder_window:
                    break  # window exhausted: stop reordering, flush wave
                skipped += 1
                scan += 1
                continue
            need = n_pages - len(matched_pages)
            rows = (int(self._active.sum()) + len(self._chunk_jobs)
                    + len(wave) + 1)
            want = need + rows
            if rows > 1 and not self._retry_landed(
                    lambda: self._pages_in_reach(want) >= want):
                # no headroom: wait (a lone row needs none: it fits the
                # pool, or it was refused above)
                if self.prefix_cache is not None:
                    self.prefix_cache.release(matched_entries)
                self._admission_waiting = True
                break
            pages = self._try_alloc(need, matched_entries)
            if pages is None:
                # pages exhausted: wait (no skip — alloc fairness)
                self._admission_waiting = True
                break
            del self._pending[scan]
            slot = free[0]
            assigned.add(slot)
            if self.kvledger is not None:
                # pages become slot-owned active-decode (here and where a
                # row grows, ``_grow_rows``)
                self.kvledger.on_alloc(pages,
                                       owner=req.group_id or req.rid)
            if self.prefix_cache is not None:
                self.prefix_cache.note_request(bool(matched_pages))
            if chunked:
                # reserve the slot (placeholder keeps it out of the free
                # scan; active stays False until the final chunk inserts).
                # first_key marks the in-flight prompt so group siblings
                # WAIT for the final chunk's publish instead of
                # re-prefilling the whole prompt in parallel
                self._slots[slot] = _SlotInfo(
                    req, list(pages), set(req.sampling.stop_token_ids),
                    cache_entries=list(matched_entries))
                self._chunk_jobs.append({
                    "req": req, "slot": slot, "pages": list(pages),
                    "matched_pages": list(matched_pages),
                    "matched_entries": list(matched_entries),
                    "budget": budget, "pos": prefix_cached, "chunk": chunk,
                    "own_filled": 0, "version": self.weight_version,
                    "first_key": first_key,
                })
                chunk_keys.add(first_key)
                continue
            if not wave and matched_pages:
                if full_hit and self.group_share:
                    # start an attach wave: later equal-prefix full hits
                    # (the other G-1 siblings) join this dispatch
                    kind, attach_len = "attach", len(matched_pages)
                else:
                    # partial hit (or sharing disabled): singleton suffix
                    wave.append((req, slot, pages, budget, matched_pages,
                                 matched_entries))
                    break
            if not matched_pages and first_key is not None:
                wave_page_keys.add(first_key)
            wave.append((req, slot, pages, budget, matched_pages,
                         matched_entries))
        return wave, kind

    def _try_alloc(self, need: int, matched_entries: list):
        """Page allocation with the landed-output, spill and cache-evict
        fallbacks, in that order; releases the caller's matched cache
        entries on failure. Never waits for the device: a finisher still
        in the pipeline returns its pages when the loop's throttle lands
        it, and the request is placed on the iteration after."""
        pages = self._retry_landed(lambda: self.allocator.alloc(need))
        if pages is None:
            pages = self._alloc_reclaiming(need)
        if pages is None and self.prefix_cache is not None:
            self.prefix_cache.release(matched_entries)
        return pages

    def _alloc_reclaiming(self, need: int):
        """``need`` pages once the free list has too few: from what the
        cache holds unreferenced, spilled before evicted."""
        pages = None
        if self.kvspill is not None:
            # allocation pressure: page unreferenced published KV out to
            # host BEFORE evicting it — spilling preserves what eviction
            # destroys, which is what lets sessions oversubscribe HBM
            if self._spill_pages(need - self.allocator.free_count,
                                 cold_only=False):
                pages = self.allocator.alloc(need)
        if pages is None and self.prefix_cache is not None:
            # pool pressure: evict unreferenced cached pages and retry
            if self.prefix_cache.evict(need - self.allocator.free_count):
                pages = self.allocator.alloc(need)
        return pages

    def _chunk_tokens(self, n_new: int) -> int:
        """Tokens a chunk for a prefill of ``n_new`` tokens the cache does
        not hold: 0 where it goes whole, ``prefill_chunk`` where that is
        set and exceeded, and for an input longer than the largest prompt
        bucket (a row that yielded comes back with its answer so far as
        input, up to ``max_seq_len - 1`` tokens) the largest bucket's whole
        pages."""
        if self.prefill_chunk and n_new > self.prefill_chunk:
            return self.prefill_chunk
        if n_new <= self.prompt_buckets[-1]:
            return 0
        return self.prompt_buckets[-1] // self.page_size * self.page_size

    def _pages_in_reach(self, want: int) -> int:
        """Pages an allocation could get without taking any from a row:
        the free ones and, only where those are fewer than ``want``, the
        cache's unreferenced resident pages (``_try_alloc`` spills or
        evicts them)."""
        n = self.allocator.free_count
        if n < want and self.prefix_cache is not None:
            n += len(self.prefix_cache.spill_candidates())
        return n

    def _grow_rows(self) -> None:
        """Before a decode dispatch: every active row gets the pages that
        cover what it has landed plus everything the dispatches in flight
        and this one can write. The host's mirror plus ``_inflight_tok``
        bounds the device's length when this dispatch runs (the loop is
        ``pipeline_depth`` ahead); a dispatch writes ``steps_per_dispatch``
        positions a row, the speculative one ``spec_tokens + 1`` a round;
        no row writes at or past the position of its budget's last token.
        When a row needs a page, every row is topped up further ahead
        while the free list is roomy: its even share of half the free pages,
        a share that shrinks to the bare need as the pool fills (each upload
        of the table is a fresh device buffer, and a decode window wants few
        of them: PERF.md section 6, PR 34). The requests that wait to be
        taken in share too: a row that decodes alone while the others of
        its batch stand in the queue (they arrive during its prefill) took
        half the pool, and they yielded mid-answer for want of what it held
        ahead (PERF.md section 6, PR 51).
        Where the pool has no more (after what ``_try_alloc`` tries: the
        landed finishers, spill, eviction) the youngest row yields
        (``_yield_row``) and the count is made again. The new ids go into
        the host's table, which is uploaded whole as the step's
        ``page_table`` operand (``_page_table_dev``)."""
        spec = self.spec_tokens > 0
        width = self.spec_tokens + 1 if spec else 1
        ahead = self.spec_rounds * width if spec else self.steps_per_dispatch
        while True:
            rows = np.flatnonzero(self._active)
            if not rows.size:
                return
            seq = self._seq_lens[rows].astype(np.int64)
            flight = seq + self._inflight_tok[rows] * width
            end = seq + self._budgets[rows] - self._n_generated[rows]
            held = np.count_nonzero(self._page_table[rows], axis=1)

            def short(tokens: int):
                """Pages each row lacks to write ``tokens`` more."""
                reach = np.minimum(flight + tokens, end)
                return np.maximum(-(-reach // self.page_size) - held, 0)

            more = short(ahead)
            if not more.any():
                return
            # some row is at its last page's end. While the free list is
            # roomy EVERY row takes what it will ask for some way ahead
            # (its even share of half the free pages), so that the table
            # goes up a few times a pool's filling and not with every
            # dispatch; as the pool fills the share shrinks to the bare need
            sharers = rows.size + len(self._pending) + self._queue.qsize()
            stride = self.allocator.free_count // (2 * sharers)
            wide = short(ahead + max(1, stride) * self.page_size)
            pages = self.allocator.alloc(int(wide.sum()))
            if pages is not None:
                more = wide
                break
            pages = self.allocator.alloc(int(more.sum()))
            if pages is None and self._emit_landed():
                continue  # a finisher's pages may be back: count again
            if pages is None:
                pages = self._alloc_reclaiming(int(more.sum()))
            if pages is not None:
                break
            self._yield_row(min(
                rows, key=lambda i: (
                    self._slots[i].req.resumed + int(self._n_generated[i]),
                    -self._slots[i].req.t_submit)))
        for i, at, n in zip(rows, held, more):
            if n:
                info = self._slots[i]
                got, pages = pages[:n], pages[n:]
                self._page_table[i, at:at + n] = got
                info.pages.extend(got)
                if self.kvledger is not None:
                    self.kvledger.on_alloc(
                        got, owner=info.req.group_id or info.req.rid)
        if self._dev_state is not None:
            # the step's operand; a state that is gone (a row yielded) is
            # built anew from the same table before the dispatch
            self._dev_state["page_table"] = self._page_table_dev()

    def _yield_row(self, slot: int) -> None:
        """The pool ran out: row ``slot`` gives up its slot and pages and
        goes back to the head of ``_pending`` as a continuation (the
        paper's token-level continuation, inside one engine): its input is
        its prompt plus what it has streamed, its budget what is left, its
        rid and stream the same, so nothing is streamed twice and no
        terminal line stands in between. Everything dispatched lands first,
        so the mirrors are exact. With a prefix cache its full pages are
        published (``_salvage_publish``: not KV written under older
        weights) and its re-entry attaches to what is still there; a
        stateful model has none and prefills the row anew, in chunks where
        it is longer than a bucket. It comes back when admission's
        headroom test passes (``_collect_wave``)."""
        self._drain_emit_q()
        info = self._slots[slot]
        if info is None or not self._active[slot]:
            return  # it ended in the drain: its pages are back
        req = info.req
        left = int(self._budgets[slot] - self._n_generated[slot])
        self._active[slot] = False
        self._slot_gen[slot] += 1
        self._salvage_publish(slot, info)
        self._finalize(slot, cause="yield")
        self._invalidate_dev_state()
        self._pending.appendleft(_Request(
            req.rid, list(req.input_ids) + [int(t) for t in info.emitted],
            dataclasses.replace(req.sampling, max_new_tokens=left),
            req.out, req.abort, time.monotonic(),
            resumed=req.resumed + len(info.emitted)))
        self.num_running = int(self._active.sum())
        if self.profiler is not None:
            self.profiler.on_slot_yield()

    def _page_table_dev(self):
        """The host's page table and the sink's null row, on the device."""
        return jnp.asarray(np.concatenate(
            [self._page_table, np.zeros((1, self.pages_per_slot), np.int32)]))

    def _prefill_wave(self, wave: list) -> None:
        """Batched fused admission: ONE dispatch prefills every request in
        the wave (see _get_prefill_batch). The wave is padded to a size
        bucket by repeating row 0 — duplicate scatters write identical
        values and duplicate outputs are never emitted."""
        self._ensure_dev_state()
        state_kwargs = {k: self._dev_state[k] for k in self._STATE_KEYS}
        pb = next_bucket(max(len(r.input_ids) for r, *_ in wave),
                         self.prompt_buckets)
        use_filters = any(r.sampling.top_p < 1.0 or r.sampling.top_k > 0
                          for r, *_ in wave)
        rows_np, metas = [], []
        for req, slot, pages, budget, _mp, _me in wave:
            sp = req.sampling
            n_prompt = len(req.input_ids)
            n_pp = -(-n_prompt // self.page_size)
            page_ids = np.zeros((pb // self.page_size,), np.int32)
            page_ids[:n_pp] = pages[:n_pp]
            row = np.zeros((self.pages_per_slot,), np.int32)
            row[:len(pages)] = pages
            stops = np.full((MAX_STOP_TOKENS,), -1, np.int32)
            for i, t in enumerate(sp.stop_token_ids[:MAX_STOP_TOKENS]):
                stops[i] = t
            ids = np.full((pb,), self.pad_token_id, np.int32)
            ids[:n_prompt] = req.input_ids
            rows_np.append(self._pack_prefill(
                ids, page_ids, row, stops, np.zeros((0,), np.int32),
                n_prompt, 0, slot, budget, sp))
            metas.append((req, slot, pages, budget, row, stops))
        nb = next_bucket(len(wave), (2, 4, 8))
        if len(rows_np) < nb:
            pad_row = self._sink_pad_row(pb)
            while len(rows_np) < nb:
                rows_np.append(pad_row)
        fn = self._get_prefill_batch(pb, nb, use_filters)
        kp, vp, self._rng, token, logp, done, new_st = fn(
            self.params, self._pools[0], self._pools[1],
            jnp.asarray(np.stack(rows_np)), self._rng, **state_kwargs)
        self._pools = (kp, vp)
        self._carry_spec_state(new_st,
                               [(slot, req.input_ids)
                                for req, slot, *_rest in metas])
        self._dev_state = new_st

        idxs = []
        for req, slot, pages, budget, row, stops in metas:
            private = list(pages)
            entries: list = []
            if self.prefix_cache is not None:
                published = self.prefix_cache.publish(
                    req.input_ids, pages, n_cached=0)
                pub_pages = {e.page for _, e in published}
                private = [p for p in pages if p not in pub_pages]
                entries = [e for _, e in published]
                if self.kvledger is not None:
                    self.kvledger.on_publish(pub_pages)
            sp = req.sampling
            n_prompt = len(req.input_ids)
            self._page_table[slot] = row
            self._seq_lens[slot] = n_prompt
            self._last_tokens[slot] = self.pad_token_id
            self._n_generated[slot] = 1
            self._budgets[slot] = budget
            self._active[slot] = True
            self._temps[slot] = sp.temperature
            self._top_ps[slot] = sp.top_p
            self._top_ks[slot] = sp.top_k
            self._stop_table[slot] = stops
            self._slots[slot] = _SlotInfo(req, private, set(sp.stop_token_ids),
                                          cache_entries=entries,
                                          admit_version=self.weight_version)
            if self._hist is not None:
                self._hist[slot] = list(req.input_ids)
            self._slot_gen[slot] += 1
            self.deck.on_admit(slot, req.rid, req.t_submit, n_prompt)
            self._consume_group_preref(req)
            self._register_group_prerefs(req, entries)
            # leader seat: its first full prompt pages ARE the chain the
            # siblings will attach to (publish keeps the ids)
            self._register_decode_group(
                req, slot, max(0, (n_prompt - 1) // self.page_size), row)
            idxs.append((slot, int(self._slot_gen[slot])))
        self._enqueue_output(("prefillb", (token, logp, done), idxs,
                              self.weight_version))

    def _prefill_attach_wave(self, wave: list) -> None:
        """Batched sibling attach: every wave member is a FULL prefix hit
        with the SAME prefix page count (GRPO siblings of a published
        leader, or any equal-length full hits) — one
        ``_get_prefill_suffix_batch`` dispatch admits them all, replacing
        G−1 serialized singleton suffix dispatches. Full hits publish
        nothing (the whole prompt's full pages are already cached), so the
        members' suffix/decode pages stay slot-private and their cache
        refs are exactly the ``match()`` entries."""
        self._ensure_dev_state()
        state_kwargs = {k: self._dev_state[k] for k in self._STATE_KEYS}
        attach_pages = len(wave[0][4])
        prefix_len = attach_pages * self.page_size
        pb = next_bucket(max(len(r.input_ids) - prefix_len
                             for r, *_ in wave), self.prompt_buckets)
        n_pre_b = 1
        while n_pre_b < attach_pages:
            n_pre_b *= 2
        use_filters = any(r.sampling.top_p < 1.0 or r.sampling.top_k > 0
                          for r, *_ in wave)
        rows_np, metas = [], []
        for req, slot, pages, budget, mp, me in wave:
            sp = req.sampling
            n_prompt = len(req.input_ids)
            all_pages = mp + pages
            row = np.zeros((self.pages_per_slot,), np.int32)
            row[:len(all_pages)] = all_pages
            stops = np.full((MAX_STOP_TOKENS,), -1, np.int32)
            for i, t in enumerate(sp.stop_token_ids[:MAX_STOP_TOKENS]):
                stops[i] = t
            packed, _pb, _np = self._pack_suffix(
                req.input_ids[prefix_len:], n_prompt - prefix_len,
                prefix_len, mp, pages, row, stops, slot, budget, sp,
                pb=pb, n_pre_b=n_pre_b)
            rows_np.append(packed)
            metas.append((req, slot, pages, budget, row, stops, me))
        nb = next_bucket(len(wave), (2, 4, 8))
        if len(rows_np) < nb:
            pad_row = self._sink_pad_row(pb, n_pre_b)
            while len(rows_np) < nb:
                rows_np.append(pad_row)
        fn = self._get_prefill_suffix_batch(pb, nb, n_pre_b, use_filters)
        kp, vp, self._rng, token, logp, done, new_st = fn(
            self.params, self._pools[0], self._pools[1],
            jnp.asarray(np.stack(rows_np)), self._rng, **state_kwargs)
        self._pools = (kp, vp)
        self._carry_spec_state(new_st,
                               [(slot, req.input_ids)
                                for req, slot, *_rest in metas])
        self._dev_state = new_st

        idxs = []
        for req, slot, pages, budget, row, stops, me in metas:
            sp = req.sampling
            n_prompt = len(req.input_ids)
            self._page_table[slot] = row
            self._seq_lens[slot] = n_prompt
            self._last_tokens[slot] = self.pad_token_id
            self._n_generated[slot] = 1
            self._budgets[slot] = budget
            self._active[slot] = True
            self._temps[slot] = sp.temperature
            self._top_ps[slot] = sp.top_p
            self._top_ks[slot] = sp.top_k
            self._stop_table[slot] = stops
            self._slots[slot] = _SlotInfo(req, list(pages),
                                          set(sp.stop_token_ids),
                                          cache_entries=list(me),
                                          admit_version=self.weight_version)
            if self._hist is not None:
                self._hist[slot] = list(req.input_ids)
            self._slot_gen[slot] += 1
            self.deck.on_admit(slot, req.rid, req.t_submit, n_prompt,
                               cached_tokens=prefix_len)
            self._consume_group_preref(req)
            # sibling seat: the attach wave's matched pages are exactly the
            # leader's published chain (row's leading columns)
            self._register_decode_group(req, slot, attach_pages, row)
            idxs.append((slot, int(self._slot_gen[slot])))
        self.sibling_attach_dispatches += 1
        self.group_forked_requests += len(wave)
        self._enqueue_output(("prefillb", (token, logp, done), idxs,
                              self.weight_version))

    # -- group-shared prefill pre-refs ---------------------------------------

    def _register_group_prerefs(self, req: _Request, entries: list) -> None:
        """After a group leader's prompt pages publish, pre-take
        ``group_size−1`` refs on the chain so pool-pressure eviction can't
        reclaim the shared prefix before the siblings attach. Refs are
        dropped one unit per sibling admission (``_consume_group_preref``),
        TTL-swept for groups whose siblings never arrive, and disbanded
        before any cache flush (the entries are about to be orphaned)."""
        if (not self.group_share or self.prefix_cache is None
                or not req.group_id or req.group_size <= 1 or not entries
                or req.group_id in self._group_prerefs):
            return
        n = req.group_size - 1
        self.prefix_cache.retain(entries, n)
        self._group_prerefs[req.group_id] = {
            "entries": list(entries), "remaining": n,
            "t": time.monotonic(),
        }
        if self.kvledger is not None:
            self.kvledger.on_preref_hold([e.page for e in entries])

    def _consume_group_preref(self, req: _Request) -> None:
        """One group member accounted for (admitted, aborted, or errored
        pre-admission): drop one pre-ref unit on the group's chain."""
        if not req.group_id:
            return
        g = self._group_prerefs.get(req.group_id)
        if g is None:
            return
        if self.prefix_cache is not None:
            self.prefix_cache.release(g["entries"])
        g["remaining"] -= 1
        if g["remaining"] <= 0:
            del self._group_prerefs[req.group_id]
            if self.kvledger is not None:
                self.kvledger.on_preref_release(
                    [e.page for e in g["entries"]])

    def _sweep_group_prerefs(self) -> None:
        """Expire pre-refs for groups whose siblings never arrived (dropped
        groups, mis-sized hints) so the shared pages return to normal LRU
        eviction instead of being pinned forever."""
        if not self._group_prerefs:
            return
        now = time.monotonic()
        for gid in [g for g, v in self._group_prerefs.items()
                    if now - v["t"] > self.group_preref_ttl_s]:
            g = self._group_prerefs.pop(gid)
            if self.prefix_cache is not None:
                for _ in range(max(0, g["remaining"])):
                    # TTL expiry: orphan frees under this release book as
                    # preref_ttl (the page died because the group's
                    # siblings never came for it)
                    self.prefix_cache.release(g["entries"],
                                              cause="preref_ttl")
            if self.kvledger is not None:
                self.kvledger.on_preref_release(
                    [e.page for e in g["entries"]])

    def _disband_group_prerefs(self) -> None:
        """Release every outstanding pre-ref NOW — called before any cache
        flush (weight swap, memory release, recover, shutdown): the flush
        orphans the entries, and pre-refs on orphans would pin their pages
        until the TTL sweep."""
        for g in self._group_prerefs.values():
            if self.prefix_cache is not None:
                for _ in range(max(0, g["remaining"])):
                    self.prefix_cache.release(g["entries"])
            if self.kvledger is not None:
                self.kvledger.on_preref_release(
                    [e.page for e in g["entries"]])
        self._group_prerefs.clear()

    # -- shared-prefix decode groups -----------------------------------------

    def _register_decode_group(self, req: _Request, slot: int,
                               n_pre_pages: int, prefix_pages) -> None:
        """Seat ``slot`` in its GRPO group's decode-sharing table. The seat
        is only taken when the member's leading page-table columns are the
        group's EXACT physical prefix chain (the PR-8 indirection is what
        makes one HBM stream serve everyone) — a member admitted after a
        cache flush re-prefilled onto fresh pages and must not join the
        old cohort (it keeps decoding correctly via the ungrouped path).
        Loop-thread only; membership leaves through ``_finalize``."""
        if (not self.decode_group_share or not self.group_share
                or self.prefix_cache is None or not req.group_id
                or req.group_size <= 1 or n_pre_pages <= 0):
            return
        pages_t = tuple(int(p) for p in list(prefix_pages)[:n_pre_pages])
        if len(pages_t) < n_pre_pages:
            return
        g = self._decode_groups.get(req.group_id)
        if g is None or not g["slots"]:
            g = {"n_pre": int(n_pre_pages), "pages": pages_t, "slots": set()}
            self._decode_groups[req.group_id] = g
        if g["n_pre"] != n_pre_pages or g["pages"] != pages_t:
            return  # different physical prefix (flush mid-group): stay solo
        g["slots"].add(slot)
        self._slot_decode_gid[slot] = req.group_id

    def _drop_decode_seat(self, slot: int) -> None:
        gid = self._slot_decode_gid.pop(slot, None)
        if gid is None:
            return
        g = self._decode_groups.get(gid)
        if g is not None:
            g["slots"].discard(slot)
            if not g["slots"]:
                del self._decode_groups[gid]

    @staticmethod
    def _pow2(n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return b

    def _decode_group_pack(self):
        """Build this dispatch's decode-group tables from the host registry:
        (packed int32 vector, bucketed (ng, gmax, p_pre) jit key, the
        group rows used — for the KV-read ledger), or (None, None, ())
        when nothing shares. Only groups with >=2 mirror-ACTIVE members
        pack (a lone survivor degrades to the ungrouped kernel — its page
        row still holds the whole sequence); every dimension buckets to a
        power of two so the compiled-step cache stays bounded."""
        if not self.decode_group_share or self.spec_tokens > 0:
            return None, None, ()
        rows = []
        for g in self._decode_groups.values():
            live = sorted(s for s in g["slots"] if self._active[s])
            if len(live) >= 2:
                rows.append((live, g["n_pre"], g["pages"]))
        if not rows:
            return None, None, ()
        ng = self._pow2(len(rows))
        gmax = self._pow2(max(len(r[0]) for r in rows))
        p_pre = self._pow2(max(r[1] for r in rows))
        g_slots = np.full((ng, gmax), -1, np.int32)
        g_pages = np.zeros((ng, p_pre), np.int32)
        g_lens = np.zeros((ng,), np.int32)
        for i, (live, n_pre, pages) in enumerate(rows):
            g_slots[i, :len(live)] = live
            g_pages[i, :n_pre] = pages[:n_pre]
            g_lens[i] = n_pre * self.page_size
        pack = np.concatenate([g_slots.ravel(), g_pages.ravel(), g_lens])
        return pack, (ng, gmax, p_pre), rows

    def _account_kv_reads(self, group_rows, k: int,
                          k_tokens: int | None = None) -> None:
        """Dispatch-time KV-read ledger (host mirrors, no device work):
        LOGICAL pages = what every active slot attends; STREAMED = what the
        kernels actually pull from HBM — each packed group's prefix chain
        counts ONCE instead of once per member. Page counts are sampled at
        dispatch time (the k fused steps may each cross at most one page
        boundary — a <1-page-per-slot estimate error, documented in the
        flight deck). ``k_tokens`` decouples the emission floor from the
        attention-row count for spec dispatches (m verify rows per round
        but >=1 emitted token per round)."""
        active_idx = np.flatnonzero(self._active)
        if active_idx.size == 0:
            return
        pages_tot = self._seq_lens[active_idx] // self.page_size + 1
        logical = int(pages_tot.sum())
        streamed = logical
        for live, n_pre, _pages in group_rows:
            streamed -= (len(live) - 1) * n_pre
        self.deck.on_kv_read(
            streamed * k, logical * k,
            int(active_idx.size) * (k if k_tokens is None else k_tokens))

    def _prefill_request(self, slot: int, req: _Request, pages: list[int],
                         budget: int, matched_pages: list[int] | None = None,
                         matched_entries: list | None = None,
                         own_prefix_pages: int = 0) -> None:
        """Fused async admission: the compiled prefill also inserts the slot
        into the device control state, and the first token's emission is
        deferred to the emit queue — no host round trip per request.
        ``own_prefix_pages``: leading entries of ``pages`` whose KV is
        ALREADY filled (chunked prefill's earlier chunks) — they join the
        attended prefix but, unlike cache-matched pages, belong to this
        request and get published as fresh pages."""
        matched_pages = matched_pages or []
        matched_entries = list(matched_entries or [])
        n_prompt = len(req.input_ids)
        prefix_pages_all = matched_pages + pages[:own_prefix_pages]
        prefix_len = len(prefix_pages_all) * self.page_size
        sp = req.sampling

        all_pages = matched_pages + pages
        row = np.zeros((self.pages_per_slot,), np.int32)
        row[:len(all_pages)] = all_pages
        stops = np.full((MAX_STOP_TOKENS,), -1, np.int32)
        for i, t in enumerate(sp.stop_token_ids[:MAX_STOP_TOKENS]):
            stops[i] = t

        self._ensure_dev_state()
        state_kwargs = {k: self._dev_state[k] for k in self._STATE_KEYS}
        use_filters = bool(sp.top_p < 1.0 or sp.top_k > 0)
        if prefix_pages_all:
            # prefix-cache hit and/or chunk-filled prefix: prefill only the
            # remaining suffix, attending over the filled pages
            suffix_len = n_prompt - prefix_len
            packed, pb, n_pre_b = self._pack_suffix(
                req.input_ids[prefix_len:], suffix_len, prefix_len,
                prefix_pages_all, pages[own_prefix_pages:], row, stops,
                slot, budget, sp)
            fn = self._get_prefill_suffix(pb, n_pre_b, use_filters)
        else:
            pb = next_bucket(n_prompt, self.prompt_buckets)
            n_prompt_pages = -(-n_prompt // self.page_size)
            page_ids = np.zeros((pb // self.page_size,), np.int32)
            page_ids[:n_prompt_pages] = pages[:n_prompt_pages]
            ids = np.full((pb,), self.pad_token_id, np.int32)
            ids[:n_prompt] = req.input_ids
            packed = self._pack_prefill(ids, page_ids, row, stops,
                                        np.zeros((0,), np.int32),
                                        n_prompt, 0, slot, budget, sp)
            fn = self._get_prefill(pb, use_filters)
        kp, vp, self._rng, token, logp, done, new_st = fn(
            self.params, self._pools[0], self._pools[1],
            jnp.asarray(packed), self._rng, **state_kwargs)
        self._pools = (kp, vp)
        self._carry_spec_state(new_st, [(slot, req.input_ids)])
        self._dev_state = new_st

        # publish the prompt's freshly computed full pages; ownership of
        # published pages moves to the cache (the slot holds refs)
        private = list(pages)
        if self.prefix_cache is not None:
            published = self.prefix_cache.publish(
                req.input_ids, all_pages, n_cached=len(matched_pages),
                matched_entries=matched_entries)
            pub_pages = {e.page for _, e in published}
            private = [p for p in pages if p not in pub_pages]
            matched_entries += [e for _, e in published]
            if self.kvledger is not None:
                self.kvledger.on_publish(pub_pages)
        self._consume_group_preref(req)
        self._register_group_prerefs(req, matched_entries)
        # singleton admission (leader, full/partial hit, chunk final): the
        # full prompt chain is cached after this dispatch's publish, so the
        # seat key is the first n_full page ids — identical across members
        self._register_decode_group(
            req, slot, max(0, (n_prompt - 1) // self.page_size), row)

        # host mirrors: everything except the (device-side) first token;
        # _emit_prefill fills last_tokens when the output is drained, and
        # finalizes immediately-finished requests
        self._page_table[slot] = row
        self._seq_lens[slot] = n_prompt
        self._last_tokens[slot] = self.pad_token_id
        self._n_generated[slot] = 1
        self._budgets[slot] = budget
        self._active[slot] = True
        self._temps[slot] = sp.temperature
        self._top_ps[slot] = sp.top_p
        self._top_ks[slot] = sp.top_k
        self._stop_table[slot] = stops
        self._slots[slot] = _SlotInfo(req, private, set(sp.stop_token_ids),
                                      cache_entries=matched_entries,
                                      admit_version=self.weight_version)
        if self._hist is not None:
            self._hist[slot] = list(req.input_ids)
        self._slot_gen[slot] += 1
        # cached_tokens = the prefix this dispatch did NOT compute (cache
        # hit and/or chunk-filled pages); the ledger's prefill total still
        # counts the full prompt — token accounting is about attribution,
        # not compute
        self.deck.on_admit(slot, req.rid, req.t_submit, n_prompt,
                           cached_tokens=prefix_len)
        self._enqueue_output(("prefill", (token, logp, done),
                             (slot, int(self._slot_gen[slot])),
                             self.weight_version))

    # -- device-resident state + pipelined stepping --------------------------

    def _invalidate_dev_state(self) -> None:
        self._dev_state = None

    def _carry_spec_state(self, new_st: dict,
                          admissions: list[tuple[int, list[int]]]) -> None:
        """Prefill dispatches return a fresh state dict without the spec
        token buffer — carry it over and write each newly admitted slot's
        PROMPT into its row (the device-sampled first token arrives via
        the spec step's last_tokens splice)."""
        if self._hist is None or self._dev_state is None:
            return
        buf = self._dev_state.get("tok_buf")
        if buf is None:
            return
        if admissions:
            # ONE batched scatter for the whole admission wave (per-slot
            # .at[].set would copy the full buffer once per request)
            slots = np.array([s for s, _ in admissions], np.int32)
            width = min(max(len(ids) for _, ids in admissions),
                        self.max_seq_len)
            rows = np.zeros((len(admissions), width), np.int32)
            keep = np.zeros((len(admissions), width), bool)
            for j, (_s, ids) in enumerate(admissions):
                n = min(len(ids), width)
                rows[j, :n] = ids[:n]
                keep[j, :n] = True
            cur = buf[jnp.asarray(slots), :width]
            buf = buf.at[jnp.asarray(slots), :width].set(
                jnp.where(jnp.asarray(keep), jnp.asarray(rows), cur))
        new_st["tok_buf"] = buf

    def _ensure_dev_state(self) -> None:
        if self._dev_state is not None:
            return
        # mirrors must be exact before a re-upload: queued emissions still
        # carry device-side first tokens (mirror last_tokens is a
        # placeholder until drained)
        self._drain_emit_q()
        # device state carries ONE extra row (index max_slots): the SINK —
        # admission-wave padding rows insert there (never active, pages all
        # null), so padded batch prefills can't collide with a real slot's
        # sampled token / active flag
        self._dev_state = {
            "page_table": self._page_table_dev(),
            "seq_lens": jnp.asarray(np.append(self._seq_lens, 0).astype(np.int32)),
            "last_tokens": jnp.asarray(np.append(
                self._last_tokens, self.pad_token_id).astype(np.int32)),
            "n_generated": jnp.asarray(np.append(self._n_generated, 0).astype(np.int32)),
            "budgets": jnp.asarray(np.append(self._budgets, 0).astype(np.int32)),
            "active": jnp.asarray(np.append(self._active, False)),
            "temps": jnp.asarray(np.append(self._temps, 1.0).astype(np.float32)),
            "top_ps": jnp.asarray(np.append(self._top_ps, 1.0).astype(np.float32)),
            "top_ks": jnp.asarray(np.append(self._top_ks, 0).astype(np.int32)),
            "stop_table": jnp.asarray(np.concatenate(
                [self._stop_table,
                 np.full((1, MAX_STOP_TOKENS), -1, np.int32)])),
        }
        if self._hist is not None:
            # spec token buffer (prompt + emitted per slot, front-filled),
            # rebuilt from the host history mirror
            buf = np.zeros((self.max_slots + 1, self.max_seq_len), np.int32)
            for i, h in enumerate(self._hist):
                if h:
                    n = min(len(h), self.max_seq_len)
                    buf[i, :n] = h[:n]
            self._dev_state["tok_buf"] = jnp.asarray(buf)


    def _enqueue_output(self, entry, fused_sample: bool = False) -> None:
        """Queue a dispatch output for the fetcher thread (wakes it).
        ``fused_sample``: its decode steps sampled inside the head."""
        if self.profiler is not None:
            # before the fetcher can see the entry: it lands them in order
            kind = entry[0]
            decode = kind in ("step", "spec")
            self.profiler.on_dispatch(
                kind, entry[3] if decode else 0,
                rows=len(entry[2]) if decode else 0,
                counters=self._step_counters[fused_sample] if decode else ())
        self._last_two.append(entry[1])
        with self._fetch_cv:
            self._emit_q.append(entry)
            self._fetch_cv.notify_all()

    def _outstanding(self) -> int:
        """Dispatch outputs not yet emitted (queued + in device_get + landed)."""
        with self._fetch_cv:
            return (len(self._emit_q) + self._fetch_inflight
                    + len(self._fetched_q))

    def _fetch_loop(self) -> None:
        """Fetcher thread: own the blocking device->host transfer. Grabs
        every queued output in one batched ``device_get`` (a get per entry
        would serialize a round trip each), then hands the host arrays back
        for the loop thread to emit. ``device_get`` releases the GIL during
        the transfer, so round trips overlap dispatching AND each other."""
        cv = self._fetch_cv
        while not self._stop.is_set():
            with cv:
                if not self._emit_q:
                    cv.wait(timeout=0.05)
                    continue
                # the oldest entry, and with it only what the device has
                # finished already (at most half the window): a get blocks
                # until its NEWEST entry finishes on device, so a batch
                # that reached into work still computing would hold every
                # older result back behind it. A saturated device then
                # streams dispatch by dispatch as each one ends (a burst
                # of steps_per_dispatch tokens a stream, not of half a
                # window's); a slow host or a long round trip still gets
                # everything that piled up in one get
                cap = max(1, self.pipeline_depth // 2)
                batch = [self._emit_q.popleft()]
                while (self._emit_q and len(batch) < cap
                       and _finished_on_device(self._emit_q[0][1])):
                    batch.append(self._emit_q.popleft())
                self._fetch_inflight = len(batch)
                epoch = self._fetch_epoch
            handed_off = False
            try:
                try:
                    with self._fetch_scope():
                        fetched = jax.device_get([e[1] for e in batch])
                except Exception as exc:  # noqa: BLE001 — surface on the
                    # loop thread (next drain) where _recover can reset
                    # pools; true BaseExceptions (SystemExit et al) must
                    # NOT be forwarded: _loop only recovers from Exception
                    with cv:
                        self._fetch_inflight = 0
                        if epoch == self._fetch_epoch:
                            self._fetch_exc = exc
                        cv.notify_all()
                    handed_off = True
                    continue
                self._landed(batch, fetched)
                with cv:
                    self._fetched_q.extend(
                        (epoch, e, a) for e, a in zip(batch, fetched))
                    self._fetch_inflight = 0
                    cv.notify_all()
                handed_off = True
            finally:
                if not handed_off:
                    # a BaseException is killing this thread mid-batch:
                    # requeue the batch (front, preserving FIFO) and zero
                    # the inflight count so _drain_emit_q's accounting
                    # stays consistent and its dead-fetcher fallback can
                    # fetch these entries synchronously — otherwise the
                    # loop thread (and every HTTP handler) wedges forever
                    with cv:
                        for e in reversed(batch):
                            self._emit_q.appendleft(e)
                        self._fetch_inflight = 0
                        cv.notify_all()

    def _emit_landed(self) -> int:
        """Stream out the dispatch outputs the fetcher has ALREADY landed
        (``_fetched_q``), bringing the host mirrors up to date, and say how
        many there were. Never waits: not for ``_emit_q``, not for a
        ``device_get`` in flight. A failure of the fetcher's surfaces here,
        on the loop thread."""
        with self._fetch_cv:
            ready = list(self._fetched_q)
            self._fetched_q.clear()
            exc, self._fetch_exc = self._fetch_exc, None
            epoch = self._fetch_epoch
        if ready:
            if self.profiler is not None:
                self.profiler.on_emit(len(ready))
            with self._phase("emit"):
                for ep, entry, arrs in ready:
                    if ep == epoch:
                        self._emit_entry(entry, arrs)
        if exc is not None:
            raise exc
        return len(ready)

    def _retry_landed(self, attempt):
        """How admission waits: not at all. ``attempt()`` (take pages, find
        a free slot) and, where it comes back empty, once more after
        emitting what the fetcher has landed meanwhile: a finisher's pages
        and slot return at its emission. What is still on the device or in
        a transfer is left to the loop's throttle (``_throttle``), and the
        caller leaves its request pending for the next iteration."""
        got = attempt()
        if not got and self._emit_landed():
            got = attempt()
        return got

    def _throttle(self) -> None:
        """After a decode dispatch: how far the loop may run ahead of the
        device. With nobody waiting, ``pipeline_depth`` outputs un-emitted
        (older ones stream out of the fetcher while the device computes,
        hiding the fetch round trips entirely). While admission has a
        request it could not place, one program: the next dispatch goes
        out when fewer than two are unfinished ON THE DEVICE (one running,
        one queued behind it), so the device never runs dry, outputs that
        are finished and in transit do not hold the loop, and a finisher's
        slot is refilled a dispatch after the device freed it."""
        if self._admission_waiting and len(self._last_two) == 2:
            older = self._last_two[0]
            cv = self._fetch_cv
            while not (_finished_on_device(older) or self._stop.is_set()):
                self._emit_landed()
                with cv:
                    if not self._fetched_q:
                        # woken by the landing; the timeout covers a
                        # transfer that outlasts the program behind it
                        with self._phase("sample_fetch"):
                            cv.wait(timeout=0.005)
        self._drain_emit_q(keep=self.pipeline_depth)

    def _drain_emit_q(self, keep: int = 0) -> None:
        """Stream out every dispatch output the fetcher has landed, bringing
        the host mirrors up to date; block until at most ``keep`` outputs
        remain un-emitted. ``keep=0`` is the full barrier every dev-state
        re-upload and abort needs; ``keep=pipeline_depth`` is the
        steady-state call (``_throttle``) that only holds the loop when it
        runs too far ahead. Admission never calls it: a blocked request
        takes what has landed (``_retry_landed``) and waits in
        ``_pending``, and while one does the throttle holds the loop one
        program ahead of the device instead of ``pipeline_depth``."""
        if self._fetch_thread is None:
            # engine not started (unit tests drive internals directly):
            # fetch the oldest beyond ``keep`` synchronously on this thread
            self._fetch_sync(keep)
        cv = self._fetch_cv
        while True:
            self._emit_landed()
            with cv:
                if (len(self._emit_q) + self._fetch_inflight
                        + len(self._fetched_q) <= keep):
                    return
            fetcher_dead = (self._fetch_thread is not None
                            and not self._fetch_thread.is_alive())
            if self._stop.is_set() or fetcher_dead:
                # the fetcher exits on stop() even with entries queued — or
                # died on a BaseException (its finally requeued the batch
                # and zeroed inflight); finish the drain synchronously so
                # the loop thread can observe _stop / keep serving instead
                # of waiting out the timeout.
                # FIFO: if the fetcher still owns an older in-flight batch,
                # wait for it to land rather than fetching newer entries
                # past it (out-of-order emission corrupts the mirrors); the
                # queue grab happens under the SAME cv hold as the inflight
                # check so the fetcher cannot pop a batch in between
                with cv:
                    if self._fetch_inflight:
                        with self._phase("sample_fetch"):
                            cv.wait(timeout=0.2)
                        continue
                    # respect ``keep``: a dead fetcher must not turn the
                    # steady-state drain into a full barrier that stalls
                    # on just-dispatched device work
                    n = len(self._emit_q) - keep
                    batch = [self._emit_q.popleft()
                             for _ in range(max(0, n))]
                    epoch = self._fetch_epoch
                if batch:
                    with self._phase("sample_fetch"):
                        fetched = jax.device_get([e[1] for e in batch])
                    self._landed(batch, fetched)
                    with cv:
                        self._fetched_q.extend(
                            (epoch, e, a) for e, a in zip(batch, fetched))
                continue
            with cv:
                if not self._fetched_q and (self._emit_q
                                            or self._fetch_inflight):
                    with self._phase("sample_fetch"):
                        cv.wait(timeout=0.2)

    def _fetch_sync(self, keep: int = 0) -> None:
        """Unthreaded fallback: move queued outputs beyond ``keep`` (oldest
        first) to _fetched_q — the pre-fetcher-thread drain semantics."""
        with self._fetch_cv:
            n = len(self._emit_q) - keep
            batch = [self._emit_q.popleft() for _ in range(max(0, n))]
            epoch = self._fetch_epoch
        if not batch:
            return
        with self._phase("sample_fetch"):
            fetched = jax.device_get([e[1] for e in batch])
        self._landed(batch, fetched)
        with self._fetch_cv:
            self._fetched_q.extend(
                (epoch, e, a) for e, a in zip(batch, fetched))

    def _emit_entry(self, entry, arrs) -> None:
        kind, _payload, tail = entry[:3]
        if kind in ("step", "spec"):
            for slot, gen in tail:
                # a finalized+reused slot zeroed its counter: stale
                # decrements for the old request must not starve the new
                if self._slot_gen[slot] == gen:
                    self._inflight_tok[slot] = max(
                        0, self._inflight_tok[slot] - entry[3])
        # dispatch-time weight version tag (last tuple element): the chunk
        # reports the policy that actually SAMPLED its tokens, not whatever
        # version is live when the fetch lands steps later
        wv = entry[-1]
        if kind == "step":
            token, logp, done, _moe_load = arrs
            self._emit_fetched(token, logp, done, tail, wv=wv)
        elif kind == "spec":
            token, logp, done, emitted = arrs
            self._emit_fetched(token, logp, done, tail, emitted=emitted,
                               wv=wv)
        elif kind == "prefillb":
            # batched admission wave: one output row per real request
            token, logp, done = arrs
            for j, slot_gen in enumerate(tail):
                self._emit_prefill(int(token[j]), float(logp[j]),
                                   bool(done[j]), slot_gen, wv)
        else:
            token, logp, done = arrs
            self._emit_prefill(int(token), float(logp), bool(done), tail, wv)

    def _emit_prefill(self, t: int, lp: float, device_done: bool,
                      tail: tuple[int, int], wv: int) -> None:
        """Deliver an admitted request's first token (deferred from the
        fused prefill dispatch)."""
        slot, gen = tail
        info = self._slots[slot]
        if info is None or self._slot_gen[slot] != gen:
            return
        stop_hit = t in info.stop_set
        fin = device_done or stop_hit
        reason = "stop" if stop_hit else ("length" if fin else "")
        info.req.out.put(StreamLine({
            "token_ids": [t], "logprobs": [lp], "finished": fin,
            "finish_reason": reason, "weight_version": wv}))
        self._last_tokens[slot] = t
        info.emitted.append(t)
        if self._hist is not None:
            self._hist[slot].append(t)
        self.deck.on_first_token(slot)
        self._count_tokens(1)
        if fin:
            # finalize BEFORE the terminal marker: a client that saw
            # STREAM_END may read the flight deck immediately, so both
            # deck sides must already be folded (quiescence invariant).
            # finally: the terminal must reach the client even if finalize
            # raises (a deactivated slot is invisible to _recover's sweep)
            self._active[slot] = False
            try:
                self._finalize(slot)
            finally:
                info.req.out.put(STREAM_END)
            if not device_done:
                # stop token beyond the device table: device active is stale
                self._invalidate_dev_state()

    def _emit_fetched(self, token, logp, done, idxs, emitted=None,
                      wv: int = -1) -> None:
        """Stream one fetched dispatch ([k, slots] token/logp/done rows, one
        per fused step) to the requests; ``idxs`` is a list of (slot,
        generation) pairs and may be a superset of live slots (mirrors lag
        the pipeline by one step) — finished slots, slots that finished in
        an EARLIER row of this same dispatch (pad-token tail of the scan),
        and slots reused by a newer admission (generation mismatch) are all
        filtered. ``emitted`` ([rows, slots] bool, speculative dispatches
        only) masks rows a slot did not actually emit (rejected drafts)."""
        token, logp, done = (np.atleast_2d(np.asarray(a))
                             for a in (token, logp, done))
        if emitted is not None:
            emitted = np.atleast_2d(np.asarray(emitted))
        n_emitted = 0
        finished: list[int] = []
        host_stop_fix = False
        for r in range(token.shape[0]):
            for i, gen in idxs:
                info = self._slots[i]
                if info is None or not self._active[i] or self._slot_gen[i] != gen:
                    continue
                if emitted is not None and not emitted[r, i]:
                    continue
                t = int(token[r, i])
                # host check is authoritative: covers stop tokens beyond the
                # MAX_STOP_TOKENS device table
                fin = bool(done[r, i]) or t in info.stop_set
                reason = ""
                if fin:
                    reason = "stop" if t in info.stop_set else "length"
                info.req.out.put(StreamLine({
                    "token_ids": [t], "logprobs": [float(logp[r, i])],
                    "finished": fin, "finish_reason": reason,
                    "weight_version": wv}))
                n_emitted += 1
                self._seq_lens[i] += 1
                self._last_tokens[i] = t
                self._n_generated[i] += 1
                info.emitted.append(t)
                self.deck.on_decode(i)
                if self._hist is not None:
                    self._hist[i].append(t)
                if fin:
                    # deactivate now (later rows of this dispatch must skip
                    # the finished slot) but defer finalize + STREAM_END
                    self._active[i] = False
                    finished.append(i)
                    if not bool(done[r, i]):
                        # device missed this stop (beyond its table): its
                        # active mask is stale — force a state re-upload. Any
                        # step already in flight writes one garbage token into
                        # the freed pages, which is safe: a later prefill
                        # reusing them is ordered after it by the pools data
                        # dependency.
                        host_stop_fix = True
        if host_stop_fix:
            self._invalidate_dev_state()
        if emitted is not None:
            self.spec_emitted += n_emitted
        self._count_tokens(n_emitted)
        # terminal markers LAST: a client that saw STREAM_END may read the
        # flight deck immediately (quiescence reconciliation), so both the
        # scheduler-side total above and the per-request fold in _finalize
        # must land before the stream visibly ends
        for i in finished:
            info = self._slots[i]
            # finally: the terminal must reach the client even if finalize
            # raises — these slots are already inactive, so _recover's
            # _fail_all sweep would never release them
            try:
                self._finalize(i)
            finally:
                info.req.out.put(STREAM_END)
        self.num_running = int(self._active.sum())

    def _step_once(self) -> None:
        # host-side aborts flip slots inactive BEFORE the next dispatch;
        # mirrors must be current, so drain the pipeline first
        if any(info is not None and self._active[i]
               and info.req.abort is not None and info.req.abort.is_set()
               for i, info in enumerate(self._slots)):
            if self.salvage_partials:
                self._abort_with_salvage()
            else:
                self._abort_fast()

        if not self._active.any():
            self._drain_emit_q()
            return
        # tail cutoff: when every mirror-active slot's remaining budget is
        # already covered by dispatches in flight for that slot, another
        # dispatch could only compute pad rows — park on the fetcher until
        # a result lands instead. Exact for budget-bound streams (RL
        # rollouts with fixed max_new_tokens); stop-token finishes may
        # still run ahead a few dispatches (the device's early-out isn't
        # host-visible yet).
        rem = int(np.max((self._budgets - self._n_generated
                          - self._inflight_tok)[self._active]))
        if rem <= 0:
            out = self._outstanding()
            if out:
                self._drain_emit_q(keep=out - 1)
            return
        with self._phase("accounting"):
            self._grow_rows()
        if not self._active.any():  # the pool had room for none of them
            return
        use_filters = bool(np.any(
            (self._top_ps[self._active] < 1.0) | (self._top_ks[self._active] > 0)))
        if self.spec_tokens > 0:
            self._spec_step_once(use_filters)
            return
        with self._phase("decode_dispatch_device"):
            self._ensure_dev_state()
        st = self._dev_state
        # shared-prefix grouped decode: pack the live group tables (one
        # small int32 upload riding the dispatch — membership churn changes
        # DATA, not the compiled step, as long as the bucketed shape holds)
        gpack, gshape, group_rows = self._decode_group_pack()
        fn = self._get_step(use_filters, self.steps_per_dispatch, gshape)
        args = (self.params, self._pools[0], self._pools[1], self._rng,
                st["page_table"], st["seq_lens"], st["last_tokens"],
                st["n_generated"], st["budgets"], st["active"], st["temps"],
                st["top_ps"], st["top_ks"], st["stop_table"])
        if gshape is not None:
            args = args + (jnp.asarray(gpack),)
            self.grouped_decode_dispatches += 1
        with self._phase("decode_dispatch_device"):
            (kp, vp, self._rng, token, logp, done, st["seq_lens"],
             st["last_tokens"], st["n_generated"], st["active"],
             moe_load) = fn(*args)
        self._pools = (kp, vp)
        with self._phase("accounting"):
            self._account_kv_reads(group_rows, self.steps_per_dispatch)
        self._inflight_tok[self._active] += self.steps_per_dispatch
        self._enqueue_output(("step", (token, logp, done, moe_load),
                             [(int(i), int(self._slot_gen[i]))
                              for i in np.flatnonzero(self._active)],
                             self.steps_per_dispatch, self.weight_version),
                             fused_sample=self._samples_in_head(use_filters))
        with self._phase("accounting"):
            self._deck_dispatch()
        self._throttle()

    def _abort_fast(self) -> None:
        # emit the abort terminal FIRST and bump the slot generation so
        # queued/in-flight results for the aborted stream are dropped at
        # emission — the client is released after one loop iteration,
        # not after the whole run-ahead pipeline streams out
        aborted: list[int] = []
        for i, info in enumerate(self._slots):
            if info is None or not self._active[i]:
                continue
            if info.req.abort is not None and info.req.abort.is_set():
                self._active[i] = False
                self._slot_gen[i] += 1
                self._emit_abort(info.req, emit_line=True)
                aborted.append(i)
        if aborted:
            # full barrier BEFORE freeing pages: in-flight dispatches
            # still write KV through the old device page table; pages
            # may only return to the pool once nothing references them.
            # finally: a raising drain goes to _recover, which rebuilds
            # the pools — the aborted slots must still be finalized or
            # their slots+pages leak (recover's _fail_all only sweeps
            # mirror-ACTIVE slots, and these were just marked inactive)
            try:
                self._drain_emit_q()
            finally:
                for i in aborted:
                    self._finalize(i, cause="abort")
                self._invalidate_dev_state()

    def _abort_with_salvage(self) -> None:
        """Partial-rollout salvage (token-level continuous generation): the
        aborted slots stay active through a full pipeline drain, so every
        token the in-flight dispatches already decoded streams out to the
        client instead of being dropped, THEN the terminal abort (the
        'partial' the manager's continuation and the trainer's salvage
        ledger resume from) is emitted. Same wall cost as the fast path —
        the full barrier was always needed before freeing pages — traded
        against fast-path abort latency (the client waits out the drain).
        Decoded full pages are published to the prefix cache so a
        continuation re-dispatched to THIS engine re-uses the KV."""
        aborted = [i for i, info in enumerate(self._slots)
                   if info is not None and self._active[i]
                   and info.req.abort is not None and info.req.abort.is_set()]
        before = {i: len(self._slots[i].emitted) for i in aborted}
        try:
            self._drain_emit_q()
        finally:
            for i in aborted:
                info = self._slots[i]
                if info is None or not self._active[i]:
                    continue  # finished (stop/budget) during the drain
                # tokens the fast path would have dropped (decoded by
                # in-flight dispatches, streamed out by the drain above)
                self.tokens_salvaged += len(info.emitted) - before[i]
                self._active[i] = False
                self._slot_gen[i] += 1
                # terminal AFTER the fold: the drain above already released
                # every salvaged token, so this costs no client latency —
                # and a client that saw the abort terminal reads a deck
                # whose request side includes this slot (quiescence).
                # finally: the terminal must still reach the client if any
                # of the salvage bookkeeping raises (slot already inactive,
                # so _recover's _fail_all sweep would never release it)
                try:
                    self._salvage_publish(i, info)
                    self.deck.on_salvage(i)
                    self._finalize(i, cause="salvage")
                finally:
                    self._emit_abort(info.req, emit_line=True)
            self._invalidate_dev_state()

    def _salvage_publish(self, slot: int, info: _SlotInfo) -> None:
        """Publish an aborted slot's full pages (prompt + generated tokens)
        into the prefix cache: the continuation request's prompt IS this
        token sequence, so its suffix prefill matches these pages and skips
        recomputing the decoded KV. Decode-written KV equals prefill KV for
        the same tokens/positions under the same weights; a slot admitted
        under an older weight version is skipped (its KV predates the flush
        a weight swap performs)."""
        if (self.prefix_cache is None
                or info.admit_version != self.weight_version
                or not info.emitted):
            return
        seq = list(info.req.input_ids) + [int(t) for t in info.emitted]
        n_full = max(0, (len(seq) - 1) // self.page_size)
        if n_full == 0:
            return
        page_row = [int(p) for p in self._page_table[slot][:n_full]]
        matched_pages, matched_entries = self.prefix_cache.match(seq)
        if self.kvspill is not None and any(e.spilled
                                            for e in matched_entries):
            # salvage must not pay a restore just to dedup its publish:
            # truncate the verified chain at the first spilled entry —
            # publish walks the rest against the existing (spilled)
            # entries by token + parent identity, pages stay slot-private
            cut = next(i for i, e in enumerate(matched_entries)
                       if e.spilled)
            self.prefix_cache.release(matched_entries[cut:])
            matched_pages = matched_pages[:cut]
            matched_entries = matched_entries[:cut]
        published = self.prefix_cache.publish(
            seq, page_row, n_cached=len(matched_pages),
            matched_entries=matched_entries)
        # ownership of published pages moves to the cache; the rest of the
        # slot's private pages are freed by _finalize as usual
        pub_pages = {e.page for _, e in published}
        info.pages = [p for p in info.pages if p not in pub_pages]
        self.salvage_published_pages += len(pub_pages)
        if self.kvledger is not None:
            self.kvledger.on_publish(pub_pages)
        # drop the refs this publish round took (match + publish): the
        # entries stay resident, unreferenced, LRU-evictable — exactly the
        # state admission-published pages reach after their slot finalizes
        self.prefix_cache.release(matched_entries + [e for _, e in published])

    def _spec_step_once(self, use_filters: bool) -> None:
        """One speculative decode dispatch: spec_rounds fused rounds of
        device-side propose→verify→accept. Fully device-resident (the
        token history lives in dev state), so spec dispatches pipeline
        exactly like fused normal steps — outputs drain lazily while the
        device runs ahead."""
        m = self.spec_tokens + 1
        with self._phase("decode_dispatch_device"):
            self._ensure_dev_state()
        st = self._dev_state
        fn = self._get_spec_step(use_filters, m, self.spec_rounds)
        with self._phase("decode_dispatch_device"):
            (kp, vp, self._rng, st["tok_buf"], token, logp, done, emitted,
             st["seq_lens"], st["last_tokens"], st["n_generated"],
             st["active"]) = fn(
                self.params, self._pools[0], self._pools[1], self._rng,
                st["tok_buf"], st["page_table"], st["seq_lens"],
                st["last_tokens"], st["n_generated"], st["budgets"],
                st["active"], st["temps"], st["top_ps"], st["top_ks"],
                st["stop_table"])
        self._pools = (kp, vp)
        # spec verify attends m virtual rows per slot per round, all over
        # the slot's own pages (grouped decode is decode-path only);
        # tokens normalized by the >=1-per-round emission floor
        with self._phase("accounting"):
            self._account_kv_reads((), self.spec_rounds * m,
                                   k_tokens=self.spec_rounds)
        self.spec_dispatches += 1
        # acceptance ceiling: every active slot could emit up to
        # rounds * (spec_tokens+1) tokens from this dispatch
        self.spec_token_ceiling += (int(self._active.sum())
                                    * self.spec_rounds * m)
        # each spec round emits >=1 token per still-active slot
        self._inflight_tok[self._active] += self.spec_rounds
        self._enqueue_output(("spec", (token, logp, done, emitted),
                             [(int(i), int(self._slot_gen[i]))
                              for i in np.flatnonzero(self._active)],
                             self.spec_rounds, self.weight_version))
        with self._phase("accounting"):
            self._deck_dispatch()
        self._throttle()

    def _deck_dispatch(self) -> None:
        """Scheduler step-ledger sample at decode-dispatch time: occupancy,
        page pressure, prefix-cache residency, run-ahead depth."""
        self.deck.on_dispatch(
            int(self._active.sum()), self.allocator.free_count,
            self.prefix_cache.num_entries
            if self.prefix_cache is not None else 0,
            self._outstanding(), len(self._pending))
        if self.kvledger is not None:
            # touch every active slot's page row (the pages this dispatch's
            # attention logically reads — cache-matched prefix included)
            # and re-sweep the hot/warm/cold residency tiers. Page-0
            # padding in the rows is filtered; the reserved role would
            # keep it out of the tier counts anyway.
            rows = self._page_table[self._active].ravel()
            self.kvledger.on_dispatch(rows[rows != 0])
            if self.kvspill is not None:
                # host-RAM spill sweep rides the same off-hot-path seam:
                # page util over the high watermark pages the coldest
                # unreferenced published pages out to host
                self._spill_sweep()

    @property
    def spec_accept_rate(self) -> float:
        """Speculative acceptance: emitted tokens over the dispatches'
        token ceiling (each active slot could emit rounds*(spec_tokens+1)
        per dispatch). 0.0 before any spec dispatch; 1/(spec_tokens+1)
        per round is the no-acceptance floor, 1.0 the perfect-lookup
        ceiling."""
        if self.spec_token_ceiling <= 0:
            return 0.0
        return self.spec_emitted / self.spec_token_ceiling

    def _finalize(self, slot: int, cause: str = "finalize") -> None:
        self.deck.on_finalize(slot)
        # leave the decode group FIRST: the next dispatch must not seat a
        # finalized slot (its freed pages may be reallocated; in-flight
        # dispatches that still carry the old seat only produce garbage for
        # this now-inactive slot, which emission filters)
        self._drop_decode_seat(slot)
        info = self._slots[slot]
        if info is not None:
            self.allocator.free(info.pages)
            if self.kvledger is not None:
                # cause: "finalize" for natural completion, "abort"/
                # "salvage" when the abort paths finalize the slot
                self.kvledger.on_free(info.pages, cause)
            if self.prefix_cache is not None and info.cache_entries:
                self.prefix_cache.release(info.cache_entries)
            # per-request serving telemetry: submit→finalize wall and the
            # request's effective decode rate (continuous batching means
            # every request has its OWN elapsed time, unlike the bucketed
            # engine's shared batch clock)
            dt = time.monotonic() - info.req.t_submit
            n = int(self._n_generated[slot])
            if dt > 0 and n > 0:
                obs.observe("rollout/decode_tok_s", n / dt)
                obs.observe("rollout/request_s", dt)
        self._slots[slot] = None
        self._page_table[slot] = 0
        self._seq_lens[slot] = 0
        self._last_tokens[slot] = self.pad_token_id
        self._n_generated[slot] = 0
        self._budgets[slot] = 0
        self._inflight_tok[slot] = 0
        if self._hist is not None:
            self._hist[slot] = None

    # -- emission helpers ----------------------------------------------------

    def _emit_abort(self, req: _Request, emit_line: bool = True) -> None:
        if emit_line:
            req.out.put({"token_ids": [], "logprobs": [], "finished": True,
                         "finish_reason": "abort"})
        req.out.put(STREAM_END)

    def _emit_error(self, req: _Request, msg: str) -> None:
        req.out.put({"token_ids": [], "logprobs": [], "finished": True,
                     "finish_reason": "error", "error": msg})
        req.out.put(STREAM_END)

    def _fail_all(self, msg: str, finish_reason: str = "error") -> None:
        for i in np.flatnonzero(self._active):
            info = self._slots[i]
            self._active[i] = False
            if info is not None:
                if finish_reason == "abort":
                    self._emit_abort(info.req)
                else:
                    self._emit_error(info.req, msg)
            self._finalize(i, cause="abort")

    def _count_tokens(self, n: int) -> None:
        self.total_tokens_served += n
        if n > 0:
            # scheduler-side emission total (reconciles against per-request
            # decode counts at quiescence — flight-deck invariant)
            self.deck.on_emitted(n)
        now = time.monotonic()
        self._tok_window.append((now, n))
        horizon = now - 10.0
        toks = sum(c for t, c in self._tok_window if t >= horizon)
        t_old = min((t for t, _ in self._tok_window if t >= horizon), default=now)
        dt = now - t_old
        # a burst of emissions after a pipeline stall spans ~0 s; a rate
        # over that sliver is meaningless — only update over a meaningful
        # span
        if dt >= 0.2:
            self.last_gen_throughput = self._tput_ewma.update(toks / dt, now)

    # -- convenience (tests) ------------------------------------------------

    def generate(self, prompt_ids: list[list[int]], sampling: SamplingParams,
                 timeout: float = 300.0, rng=None) -> list[dict]:
        """Synchronous batch generate: submit all, run the loop inline if not
        started, collect full sequences. Returns per-prompt dicts with
        token_ids / logprobs / finish_reason. ``rng`` is accepted for
        interface parity with RolloutEngine; the CB engine owns per-slot
        sampling state (admission order is not deterministic anyway)."""
        outs = [self.submit(f"gen-{i}", p, sampling)
                for i, p in enumerate(prompt_ids)]
        self.start()
        results = []
        deadline = time.monotonic() + timeout
        for out_q in outs:
            toks: list[int] = []
            lps: list[float] = []
            wvs: list[int] = []
            reason = "error"
            while True:
                item = out_q.get(timeout=max(0.0, deadline - time.monotonic()))
                if item is STREAM_END:
                    break
                toks.extend(item["token_ids"])
                lps.extend(item["logprobs"])
                # each chunk carries the version that sampled it; expanded
                # per token here so colocated trainers see the same
                # weight_versions the wire protocol streams (a weight swap
                # mid-request legitimately makes these mixed)
                wvs.extend([int(item.get("weight_version", -1))]
                           * len(item["token_ids"]))
                if item["finished"]:
                    reason = item["finish_reason"]
            results.append({"token_ids": toks, "logprobs": lps,
                            "weight_versions": wvs,
                            "finish_reason": reason})
        return results
