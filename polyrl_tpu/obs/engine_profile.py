"""Engine-loop profiler: exhaustive per-iteration phase attribution for
the CB engine's loop thread (ARCHITECTURE.md "Engine-loop profiler").

PRs 7/12/17/18 piled flight-deck, KV-ledger and spill-sweep bookkeeping
onto the engine loop; the only record of where a dispatch's wall went was
a private cumulative ``_trace`` dict that never left the process. This
module is the rollout-side analogue of the trainer's goodput ledger
(obs/goodput.py): every loop iteration's wall is decomposed into an
exhaustive, NON-OVERLAPPING phase taxonomy whose sum equals the iteration
wall by construction (the residual lands in ``other``), so
``attributed_frac`` reads exactly like the goodput ledger's — the named
phases over the wall, > 1.0 meaning double-counted attribution.

Phase taxonomy (seconds, exclusive self-time):

- ``collect_wave``  — admission wave assembly (slot+page reservation,
  prefix-cache match, group fork bookkeeping)
- ``restore``       — spill readmit: host→device KV restore of spilled
  prefix pages (rollout/kvspill.py restore-then-attach)
- ``prefill_dispatch`` — prefill/attach/chunk dispatch calls (host wall
  spent in the dispatch enqueue + any synchronous device wait inside it)
- ``decode_dispatch_device`` — device-state upload + the fused-k step
  dispatch (the device wait inside the decode hot path)
- ``sample_fetch``  — loop thread blocked on the fetcher's batched
  ``device_get`` (plus the dead-fetcher synchronous fallback)
- ``emit``          — streaming fetched tokens to request queues, host
  mirror updates, finalize folds
- ``accounting``    — deck + KV-ledger + dispatch bookkeeping (the
  PR 7/17/18 overhead the regression budget pins)
- ``spill_sweep``   — watermark sweep page-out (host spill tier writes)
- ``idle``          — no work: queue waits and backoff sleeps
- ``other``         — the unattributed residual (clamped at 0)

Attribution is STACK-BASED with exclusive (self-time) semantics: the
engine nests phases freely (``_drain_emit_q`` runs inside admission,
``_spill_pages`` inside allocation pressure) and a nested phase's wall is
charged to the nested phase, never double-counted against its parent.
Stacks are thread-local, so the fetcher thread (or a unit test driving
engine internals directly) can enter phases without corrupting the loop
thread's iteration; cumulative totals fold under one lock.

The windowed split (``device_frac`` / ``host_overhead_frac`` /
``accounting_frac`` / ``idle_frac``) is computed over a two-bucket flip
window (~``window_s`` of recent loop wall) so a long-lived engine reports
CURRENT behaviour, not a run-lifetime average:

- ``device_frac``          = seconds with device work outstanding / wall,
  from the completion stamps below (NOT host wall spent in dispatch and
  fetch: a loop blocked on a fetch says nothing about the device);
- ``accounting_frac``      = (accounting + spill_sweep) / wall;
- ``host_overhead_frac``   = 1 − (sample_fetch + idle) / wall — what the
  loop thread did between its waits, the residual included;
- ``idle_frac``            = idle / wall.

One seam, one clock. :meth:`phase` is the only place an engine phase is
timed, and every phase also opens a ``jax.profiler.TraceAnnotation``
(``engine/<phase>``), always: outside a profiler session a TraceMe is a
flag test, inside one the phase lies on the device trace's clock beside
the device's own events. The fetcher's blocking ``device_get`` is
annotated the same way on its own thread (:meth:`fetch`, ``engine/fetch``)
and counted outside the loop's partition. Each landing leaves one instant
``engine/landed`` annotation on the thread that lands it, whose own
statistics are the completion stamps as of that landing
(:meth:`on_landed`): the counters' step time and the trace's can then be
taken over the same dispatches, in the same seconds
(``benchmark/lib/account.py``). The dispatch phases also emit
spans into the process tracer ring (obs/trace.py) when that is enabled;
the ring is for cross-process request traces (its wall/monotonic anchor
joins trainer, manager and engine), the device trace is where host phases
meet device time.

Cumulative counters (monotone, flat in ``server_info``; two samples give
a rate over any window, with no profiler session). ``CUMULATIVE_KEYS``
below is their one declaration: ``counters()``, ``/statusz``, ``/metrics``
and the tools take the keys from it. Each moves once a dispatch, landing,
emission or iteration, never once a token, all on this profiler's clock:

- ``decode_dispatches`` / ``decode_steps_done`` — fused decode dispatches
  enqueued, and fused steps whose results have landed on the host;
  ``row_steps_done`` — those steps times the live rows of their dispatch
  (a token each while no row ends: over ``decode_steps_done`` the
  occupancy where the work happens, over ``device_busy_at_s`` the rate at
  the engine's landings);
  ``fused_sample_steps`` — the steps whose program drew its tokens
  inside the output matmul (``decoder.head_and_sample``);
  ``kda_kernel_steps`` — the steps whose program updated its KDA states
  in the one-pass kernel (``ops/kda_state.py``);
  ``mla_proj_kernel_steps`` — the steps whose program multiplied its MLA
  layers' ``wkv_b`` where it lies in the stack (``ops/mla_proj.py``);
  ``moe_gather_kernel_steps`` — the steps whose program's expert calls
  took their rows from the tokens by table and summed them back
  themselves (``ops/grouped_matmul.py::expert_rows``);
  ``ssm_state_rows`` / ``shared_kv_rows_read`` / ``window_rows_read`` —
  for a model of the SambaY family, counted by the landed steps on the
  device (the ``counts`` of its mixers' records, ``models/mixers``): live
  rows times Mamba layers (a state read and written each), keys of the
  one shared K/V pool read, summed over the layers that attend over it,
  and keys of the window layers' rings read (0 for every other model);
  ``paged_rows_read`` — for a model of ``gqa`` layers beside other kinds
  (Laguna: ``mixers/gqa.py``), keys of the full layers' pages read, summed
  over them (its window layers move ``window_rows_read``);
  ``ut_passes`` / ``kv_pass_rows_read`` — for a looped model (``ut_steps``
  passes of one stack a token; ``hybrid.UT_LOAD``), counted by the landed
  steps on the device: live rows times the passes a step ran (over
  ``row_steps_done``: the passes a token, while no row ends), and the keys
  a step's rows attend over times the passes, each of which reads a cache
  of its own (0 for every other model);
  ``sparse_pages_read`` / ``sparse_pooled_scored`` / ``sparse_dense_rows``
  — for a model with block-sparse attention layers (``mixers/sparse.py``),
  counted by the landed steps on the device: the pages its (row, K/V head)s
  attended over, summed over the sparse layers; the pooled keys a row's
  queries were scored against where the row is past ``sparse_dense_len``,
  summed likewise; and the live rows at or under it, which attend every
  block (0 for every other model);
  ``lightning_state_rows`` / ``lightning_kernel_steps`` — live rows times
  linear-attention layers (a float32 state read and written each;
  ``mixers/lightning.py``), and the steps whose program updated those
  states in the one-pass kernel (``ops/lightning_state.py``);
  ``ssd_state_rows`` / ``ssd_kernel_steps`` — live rows times Mamba-2
  layers (a float32 state of 2 MiB read and written each at the published
  sizes; ``mixers/mamba2.py``), and the steps whose program updated those
  states in the one-pass kernel (``ops/ssd_state.py``);
  ``sparse_kernel_steps`` — the steps whose program chose its sparse
  layers' blocks in the kernel that walks a row's own pooled pages
  (``ops/sparse_select.py``);
  ``decode_dispatches_cold`` — those of the dispatches enqueued with
  NOTHING outstanding (the device had run dry: an engine that keeps its
  run-ahead does it once a burst, one that drains before every dispatch
  does it every time);
- ``admission_deferrals`` — loop iterations in which admission left a
  request pending for want of pages or a slot and went on without
  waiting (``CBEngine._admit``);
- ``slot_yields`` — rows that gave up slot and pages because the pool
  had no more and went back to the queue's head
  (``CBEngine._yield_row``);
- ``device_busy_s`` — seconds with device work outstanding: an interval
  opens when a dispatch is enqueued with nothing outstanding and closes
  when a landed result leaves nothing newer outstanding. Seconds are added
  only at a landing (``device_busy_at_s`` is that landing's clock), so
  ``delta(device_busy_s) / delta(decode_steps_done)`` between any two
  samples carries no dispatch quantum;
- ``loop_wall_s`` / ``loop_host_s`` — loop wall, and loop wall less
  ``idle`` and ``sample_fetch`` self-time; ``phase_<phase>_s`` (ten keys,
  ``PHASE_KEYS``) — the loop THREAD's self seconds by phase, folded at
  each iteration's close from that iteration's own partition (``totals``
  also take phases entered on other threads): the eight that are no wait
  sum to ``loop_host_s``, all ten to ``loop_wall_s``;
- ``emit_wait_s`` / ``dispatches_emitted`` — for each landed dispatch, the
  seconds from its landing (fetcher thread) to the start of its ``emit``
  on the loop thread, and how many were emitted;
- ``landing_gap_hist`` — log2 bucket counts (``Histogram.bucket_counts``)
  of the time between consecutive landings while work that lands stayed
  outstanding throughout (about a program's length on a fed device; a
  dispatch that lands nothing, a chunked prefill's mid-chunk, voids the
  gap it falls in, and so does a program's build, which holds the loop
  for the compiler's seconds, and a stopping engine counts none:
  :meth:`on_stop`); ``stalls`` — such gaps longer than ``STALL_GAP_S``,
  each of which the engine logs once, at the landing that ends it, with
  the loop thread's open phase and the queues' lengths
  (``CBEngine._log_stall``). Nothing in the process watches for a stall
  while it lasts: the stacks of an engine that hangs are read from
  outside it (``py-spy dump``, ``gdb``);
- ``programs_built`` / ``build_s`` — jit-cache misses of the engine's
  program tables and the seconds to their first return
  (:meth:`on_build`), the last 32 in ``snapshot()``.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

from polyrl_tpu.obs.histogram import Histogram
from polyrl_tpu.obs.trace import get_tracer

PHASES = ("collect_wave", "restore", "prefill_dispatch",
          "decode_dispatch_device", "sample_fetch", "emit", "accounting",
          "spill_sweep", "idle", "other")
# the loop thread's waits: loop wall less these is what the host did
WAIT_PHASES = frozenset(("sample_fetch", "idle"))
# the bookkeeping overhead the regression budget pins
ACCOUNTING_PHASES = frozenset(("accounting", "spill_sweep"))
# phases worth a tracer span each occurrence (dispatch-scale, not µs-scale)
SPAN_PHASES = frozenset(
    ("prefill_dispatch", "decode_dispatch_device", "sample_fetch",
     "restore"))
# dispatch kinds whose results are fused decode steps
DECODE_KINDS = frozenset(("step", "spec"))
MAX_BUILDS_KEPT = 32
# a phase's cumulative loop-thread seconds in ``server_info``
PHASE_KEYS = {p: f"phase_{p.removesuffix('_device')}_s" for p in PHASES}
# the instant annotation of a landing; its keyword statistics are the
# stamps as of that landing
LANDED_SPAN = "engine/landed"
# a landing gap longer than this is a stall: the longest program of any
# benchmark cell runs 0.18 s and a prefill chunk 0.1 s
STALL_GAP_S = 2.0
# THE declaration of the profiler's cumulative ``server_info`` keys (module
# docstring): ``counters()`` reports exactly these and the clock
# ``device_busy_at_s``; ``statusz.CUMULATIVE_INFO_KEYS`` (and so /statusz's
# counters, /metrics' types and the docs lint), ``tools/engine_report.py``
# and ``tools/check_metric_names.py`` read this tuple. Keys in ``_s`` are
# seconds, ``_hist`` keys ``Histogram.bucket_counts()``, the rest counts.
CUMULATIVE_KEYS = (
    "decode_dispatches", "decode_dispatches_cold", "admission_deferrals",
    "slot_yields", "decode_steps_done", "fused_sample_steps",
    "kda_kernel_steps", "mla_proj_kernel_steps", "moe_gather_kernel_steps",
    "row_steps_done",
    "ssm_state_rows",
    "shared_kv_rows_read",
    "window_rows_read",
    "paged_rows_read",
    "ut_passes",
    "kv_pass_rows_read",
    "sparse_pages_read", "sparse_pooled_scored", "sparse_dense_rows",
    "lightning_state_rows", "lightning_kernel_steps", "sparse_kernel_steps",
    "ssd_state_rows", "ssd_kernel_steps",
    "device_busy_s", "loop_wall_s", "loop_host_s",
    *PHASE_KEYS.values(), "emit_wait_s", "dispatches_emitted",
    "landing_gap_hist", "stalls", "programs_built", "build_s")


def _zero(key: str):
    if key.endswith("_hist"):
        return Histogram()
    return 0.0 if key.endswith("_s") else 0


class EngineLoopProfiler:
    """Exhaustive engine-loop phase attribution (module docstring).

    ``clock`` is injectable for fake-clock tests (the partition pin drives
    it deterministically so ``attributed_frac`` is exactly 1.0)."""

    def __init__(self, window_s: float = 20.0, clock=time.monotonic,
                 tracer=None):
        import jax

        self._clock = clock
        self._tracer = tracer  # None → resolve the process tracer lazily
        self._annotate = jax.profiler.TraceAnnotation
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.window_s = float(window_s)
        self.iters = 0
        self.totals = {p: 0.0 for p in PHASES}
        self.counts = {p: 0 for p in PHASES}
        self.hists = {p: Histogram() for p in PHASES if p != "other"}
        # two-bucket flip window: [wall, device busy, accounting, idle,
        # waits] each; readers sum both buckets → ~window_s/2..window_s of
        # loop wall
        self._win_cur = [0.0] * 5
        self._win_prev = [0.0] * 5
        self._win_busy_mark = 0.0  # device_busy_s at the last iteration close
        # completion stamps: dispatches whose results will land, oldest
        # first, as (their fused decode steps (0 for a prefill), their
        # live rows, the counters each of those steps moves)
        self._landing: collections.deque = collections.deque()
        self._tail_unlanded = False  # dispatched after them, lands nothing
        self._busy_from: float | None = None  # busy not yet counted, since
        # the last landing's clock while what it left outstanding all lands
        self._gap_from: float | None = None
        # landing clocks of the dispatches not yet emitted, oldest first
        self._landed_at: collections.deque = collections.deque()
        self._loop_state: dict | None = None  # the loop thread's _state()
        self._stopping = False  # on_stop: landings count no gap from here
        self._cum = {k: _zero(k) for k in CUMULATIVE_KEYS}
        self.device_busy_at_s = 0.0
        self.fetch_s = 0.0
        self.fetch_n = 0
        self.builds: collections.deque = collections.deque(
            maxlen=MAX_BUILDS_KEPT)

    @property
    def wall_s(self) -> float:
        return self._cum["loop_wall_s"]

    # -- thread-local attribution state --------------------------------------

    def _state(self):
        st = getattr(self._tls, "state", None)
        if st is None:
            # stack of [phase_name, self_seconds]; mark = last event time;
            # iter_phases = per-iteration fold (loop thread only)
            st = self._tls.state = {"stack": [], "mark": None,
                                    "iter_phases": None, "iter_t0": None}
        return st

    def _attr(self, st, now: float) -> None:
        """Charge the wall since the last event to the innermost open
        phase (self-time). Time with an empty stack inside an iteration
        becomes the ``other`` residual at iteration close."""
        mark = st["mark"]
        if mark is not None and st["stack"]:
            st["stack"][-1][1] += now - mark
        st["mark"] = now

    # -- phases ---------------------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str):
        st = self._state()
        self._attr(st, self._clock())
        st["stack"].append([name, 0.0])
        span_cm = None
        if name in SPAN_PHASES:
            tracer = self._tracer if self._tracer is not None \
                else get_tracer()
            if tracer.enabled:
                span_cm = tracer.span("engine/" + name)
                span_cm.__enter__()
        try:
            with self._annotate("engine/" + name):
                yield
        finally:
            if span_cm is not None:
                span_cm.__exit__(None, None, None)
            self._attr(st, self._clock())
            _name, self_s = st["stack"].pop()
            if st["iter_phases"] is not None:
                st["iter_phases"][name] = (
                    st["iter_phases"].get(name, 0.0) + self_s)
            with self._lock:
                self.totals[name] += self_s
                self.counts[name] += 1
                self.hists[name].observe(self_s)

    @contextlib.contextmanager
    def fetch(self):
        """The fetcher thread's blocking ``device_get``: on the device
        trace as ``engine/fetch``, counted beside the loop's partition and
        not in it (the loop's own wait for it is ``sample_fetch``)."""
        t0 = self._clock()
        try:
            with self._annotate("engine/fetch"):
                yield
        finally:
            dt = self._clock() - t0
            with self._lock:
                self.fetch_s += dt
                self.fetch_n += 1

    @contextlib.contextmanager
    def iteration(self):
        """One ``_loop_iter`` window: phases inside fold into the
        iteration's partition; the leftover wall (empty-stack time between
        phases) lands in ``other`` so the sum equals the iteration wall by
        construction."""
        st = self._loop_state = self._state()
        t0 = self._clock()
        st["iter_phases"] = {}
        st["iter_t0"] = t0
        st["mark"] = t0
        try:
            yield
        finally:
            now = self._clock()
            self._attr(st, now)
            phases, st["iter_phases"] = st["iter_phases"], None
            st["iter_t0"] = None
            wall = now - t0
            attributed = sum(phases.values())
            phases["other"] = other = max(0.0, wall - attributed)
            waits = sum(phases.get(p, 0.0) for p in WAIT_PHASES)
            acct = sum(phases.get(p, 0.0) for p in ACCOUNTING_PHASES)
            with self._lock:
                c = self._cum
                self.iters += 1
                c["loop_wall_s"] += wall
                c["loop_host_s"] += max(0.0, wall - waits)
                for name, self_s in phases.items():
                    c[PHASE_KEYS[name]] += self_s
                self.totals["other"] += other
                cur = self._win_cur
                cur[0] += wall
                cur[1] += c["device_busy_s"] - self._win_busy_mark
                cur[2] += acct
                cur[3] += phases.get("idle", 0.0)
                cur[4] += waits
                self._win_busy_mark = c["device_busy_s"]
                if cur[0] >= self.window_s / 2.0:
                    self._win_prev = cur
                    self._win_cur = [0.0] * 5

    def loop_open_phase(self) -> str:
        """The loop thread's innermost open phase right now ("" between
        phases or before its first iteration), from any thread."""
        st = self._loop_state
        top = st["stack"][-1:] if st is not None else []
        return top[0][0] if top else ""

    # -- completion stamps ----------------------------------------------------

    def on_dispatch(self, kind: str, steps: int = 0, lands: bool = True,
                    rows: int = 0, counters: tuple = ()) -> None:
        """A dispatch was just enqueued on the device; ``steps``: the
        decode steps it fuses, over ``rows`` live rows; ``counters``: the
        keys of ``CUMULATIVE_KEYS`` that each of those steps moves by one
        when it lands (``fused_sample_steps``: they sample inside the
        head; a kernel's share counter: their layers take it). ``lands``
        False: it returns nothing the host fetches (a chunked prefill's
        mid-chunk), so a later dispatch's landing stands for it."""
        now = self._clock()
        with self._lock:
            c = self._cum
            cold = self._busy_from is None
            if kind in DECODE_KINDS:
                c["decode_dispatches"] += 1
                if cold:
                    c["decode_dispatches_cold"] += 1
            if cold:
                self._busy_from = now
            if lands:
                self._landing.append((steps, rows, counters))
                self._tail_unlanded = False
            else:
                self._tail_unlanded = True
                self._gap_from = None

    def on_admission_deferred(self) -> None:
        """Admission left a request pending (no pages, no slot) and the
        loop went on to dispatch without waiting for either."""
        with self._lock:
            self._cum["admission_deferrals"] += 1

    def on_slot_yield(self) -> None:
        """A running row gave up its slot and pages for want of pages."""
        with self._lock:
            self._cum["slot_yields"] += 1

    def on_cache_rows(self, rows: dict) -> None:
        """Landed decode steps counted ``rows`` (key -> count) more of
        the cache rows they touch (the entries of ``hybrid.load_names``
        that are keys of ``CUMULATIVE_KEYS``)."""
        with self._lock:
            for key, n in rows.items():
                self._cum[key] += n

    def on_landed(self, n: int) -> float | None:
        """The oldest ``n`` dispatches' results are on the host: the
        device has finished them and everything enqueued before them.
        Returns the landing gap this one closed where it was a stall
        (module docstring), else None. Leaves the stamps as they stand
        after it on the device trace (``engine/landed``: outside a
        profiler session a flag test)."""
        now = self._clock()
        with self._lock:
            c = self._cum
            landed = min(n, len(self._landing))
            for _ in range(landed):
                steps, rows, counters = self._landing.popleft()
                c["decode_steps_done"] += steps
                c["row_steps_done"] += steps * rows
                for key in counters:
                    c[key] += steps
                self._landed_at.append(now)
            gap = None if self._gap_from is None else now - self._gap_from
            if gap is not None:
                c["landing_gap_hist"].observe(gap)
                if gap > STALL_GAP_S:
                    c["stalls"] += 1
                else:
                    gap = None
            still_busy = bool(self._landing) or self._tail_unlanded
            self._count_busy(now, still_busy)
            self._gap_from = (now if self._landing and not self._stopping
                              else None)
            stamps = dict(decode_steps_done=c["decode_steps_done"],
                          device_busy_s=c["device_busy_s"],
                          device_busy_at_s=self.device_busy_at_s,
                          dispatches=landed)
        with self._annotate(LANDED_SPAN, **stamps):
            pass
        return gap

    def on_emit(self, n: int) -> None:
        """The loop thread starts to emit the oldest ``n`` landed
        dispatches: each has waited since its landing."""
        now = self._clock()
        with self._lock:
            c = self._cum
            for _ in range(min(n, len(self._landed_at))):
                c["emit_wait_s"] += max(0.0, now - self._landed_at.popleft())
                c["dispatches_emitted"] += 1

    def drop_outstanding(self, tail_only: bool = False) -> None:
        """Dispatches were abandoned and will never land (an engine reset
        or stop; ``tail_only``: an aborted chunked prefill's mid-chunks):
        count them done as of now."""
        now = self._clock()
        with self._lock:
            self._tail_unlanded = False
            if not tail_only:
                self._landing.clear()
                self._landed_at.clear()
            if not self._landing:
                self._count_busy(now, False)
                self._gap_from = None

    def on_stop(self) -> None:
        """The engine is stopping: what is still on the device lands in
        one get that waits for the newest of it (``pipeline_depth``
        programs), or is dropped. Its gaps are no stalls."""
        with self._lock:
            self._gap_from = None
            self._stopping = True

    def _count_busy(self, now: float, still_busy: bool) -> None:
        if self._busy_from is not None:
            self._cum["device_busy_s"] += max(0.0, now - self._busy_from)
            self.device_busy_at_s = now
        self._busy_from = now if still_busy else None

    # -- program builds -------------------------------------------------------

    def on_build(self, kind: str, key, seconds: float) -> None:
        """A miss of the engine's program tables, after the program's first
        call returned (trace, lower, compile or cache read, enqueue)."""
        with self._lock:
            self._cum["programs_built"] += 1
            self._cum["build_s"] += seconds
            # a landing gap that holds a build has timed the compiler
            self._gap_from = None
            self.builds.append({"kind": kind, "key": str(key),
                                "seconds": round(seconds, 4)})

    # -- export ---------------------------------------------------------------

    def attributed_frac(self) -> float:
        """Named-phase seconds over the iteration wall (goodput-ledger
        semantics): 1.0 when every iteration's wall is inside a phase,
        > 1.0 means double-counted attribution. 1.0 before any
        iteration."""
        with self._lock:
            wall = self._cum["loop_wall_s"]
            if wall <= 0.0:
                return 1.0
            return (wall - self.totals["other"]) / wall

    def window_fracs(self) -> dict:
        """The windowed split over ~window_s of recent loop wall; zeros
        before the first iteration closes."""
        with self._lock:
            wall, busy, acct, idle, waits = (
                c + p for c, p in zip(self._win_cur, self._win_prev))
        if wall <= 0.0:
            return {"wall_s": 0.0, "device_frac": 0.0,
                    "host_overhead_frac": 0.0, "accounting_frac": 0.0,
                    "idle_frac": 0.0}
        return {
            "wall_s": wall,
            # busy seconds are booked at landings, so a window can be
            # credited a sliver that ran before it opened
            "device_frac": min(1.0, busy / wall),
            "host_overhead_frac": max(0.0, 1.0 - waits / wall),
            "accounting_frac": acct / wall,
            "idle_frac": idle / wall,
        }

    def counters(self) -> dict:
        """The cumulative counters (``CUMULATIVE_KEYS``) and the clock of
        the landing up to which ``device_busy_s`` is booked, one
        consistent copy."""
        with self._lock:
            out = {k: (v.bucket_counts() if isinstance(v, Histogram)
                       else round(v, 6) if isinstance(v, float) else v)
                   for k, v in self._cum.items()}
            out["device_busy_at_s"] = round(self.device_busy_at_s, 6)
        return out

    def server_info_fields(self) -> dict:
        """Flat keys merged into ``server_info`` (no ``/`` — the C++
        manager's stats poller indexes them directly; the server's
        time-series feed prefixes them as ``engine/*``)."""
        w = self.window_fracs()
        return {
            "device_frac": round(w["device_frac"], 6),
            "host_overhead_frac": round(w["host_overhead_frac"], 6),
            "accounting_frac": round(w["accounting_frac"], 6),
            "loop_attributed_frac": round(self.attributed_frac(), 6),
            **self.counters(),
        }

    def snapshot(self) -> dict:
        """The /statusz ``engine.loop`` block (both planes carry one; the
        trainer's is the fleet aggregate in rollout/pool.py)."""
        with self._lock:
            totals = dict(self.totals)
            counts = dict(self.counts)
            iters = self.iters
            wall = self._cum["loop_wall_s"]
            fetch = {"seconds": round(self.fetch_s, 4), "n": self.fetch_n}
            builds = list(self.builds)
            hists = {p: {
                "p50": h.percentile(50.0), "p95": h.percentile(95.0),
                "p99": h.percentile(99.0),
                "max": h.vmax if h.count else 0.0,
                "mean": h.mean, "count": float(h.count),
            } for p, h in self.hists.items() if h.count}
        out = {
            "enabled": True,
            "iters": iters,
            "wall_s": round(wall, 3),
            "attributed_frac": round(
                (wall - totals["other"]) / wall if wall > 0 else 1.0, 6),
            "phase_s": {p: round(v, 4) for p, v in totals.items()},
            "phase_frac": {p: round(v / wall, 4) if wall > 0 else 0.0
                           for p, v in totals.items()},
            "phase_n": {p: counts[p] for p in PHASES if counts[p]},
            "window": {k: round(v, 4)
                       for k, v in self.window_fracs().items()},
            "counters": self.counters(),
            "fetch": fetch,
            "builds": builds,
        }
        if hists:
            out["latency"] = hists
        return out
