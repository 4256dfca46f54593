"""HTTP rollout server: the TPU-native replacement for the reference's
patched SGLang server (SURVEY §2.2 L2 surface; launch path §3.2).

Speaks exactly the protocol the C++ manager consumes:
- POST /generate                 — streaming NDJSON, one line per token with
                                   token_ids + logprobs + finish_reason
                                   (reference handlers.rs:152-328)
- GET  /health, /health_generate — registration-time health gate
                                   (instance_manager.rs:5-37)
- GET  /get_server_info          — queue-depth + throughput telemetry
                                   (patches.py:423-425)
- POST /abort_request            — mid-decode abort (local time-slicing,
                                   handlers.rs:500-513)
- POST /update_weights_from_agent— load pushed weights from the receiver
                                   buffer into the live engine
                                   (patches.py:137-357)
- POST /release|resume_memory_occupation, /flush_cache, /shutdown

Serving model: requests land in an admission queue; a batching loop groups
compatible requests (same sampling group) into bucketed batches and drives
``StepDecoder.generate_stream``, fanning tokens out to per-request queues —
a continuous-batching-lite scheduler (full paged/continuous batching is the
planned upgrade, SURVEY §7 step 2).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any

import jax
import numpy as np

from polyrl_tpu import obs
from polyrl_tpu.obs.histogram import bucket_index
from polyrl_tpu.obs.statusz import CUMULATIVE_INFO_KEYS, MOE_INFO_KEYS
from polyrl_tpu.rollout.cb_engine import STREAM_END
from polyrl_tpu.rollout.flightdeck import ThroughputEWMA
from polyrl_tpu.rollout.sampling import SamplingParams
from polyrl_tpu.rollout.stepper import StepDecoder

log = logging.getLogger(__name__)

# one terminal marker shared with the CB engine so either backend can feed
# the same per-request output queues
_SENTINEL = STREAM_END


@dataclasses.dataclass
class _PendingRequest:
    rid: str
    input_ids: list[int]
    sampling: SamplingParams
    out: queue.Queue
    abort: threading.Event


class RolloutServer:
    """Wraps a RolloutEngine + StepDecoder behind the manager protocol."""

    def __init__(self, engine, host: str = "0.0.0.0", port: int = 0,
                 max_batch: int | None = None, batch_wait_s: float = 0.01,
                 advertise_host: str = "127.0.0.1"):
        self.engine = engine
        # backend dispatch: a CBEngine admits requests itself (continuous
        # batching); the v0 RolloutEngine is driven through StepDecoder by
        # this server's grouping batch loop
        self.cb = hasattr(engine, "submit")
        self.stepper = None if self.cb else StepDecoder(engine)
        self.max_batch = max_batch or max(getattr(engine, "batch_buckets", (64,)))
        self.batch_wait_s = batch_wait_s
        # v0 batch-loop throughput smoothing (the CB engine smooths its
        # own): one fast/slow batch must not alias heartbeat samplers
        self._tput_ewma = ThroughputEWMA()
        self._queue: "queue.Queue[_PendingRequest]" = queue.Queue()
        self._aborts: dict[str, threading.Event] = {}
        self._aborts_lock = threading.Lock()
        self._stop = threading.Event()
        self._paused = threading.Event()  # release_memory_occupation
        # graceful preemption (POST /drain): in-flight requests abort into
        # PARTIALS (salvage-enabled engines flush decoded tokens first) and
        # new submissions are refused with an immediate abort terminal so
        # the manager's continuation re-routes them. One-way by design —
        # a drained server is about to lose its host.
        self._draining = threading.Event()
        self.drain_count = 0  # requests aborted by /drain (telemetry)
        # chaos kill switch (pool drills): a "SIGKILLed" engine answers
        # nothing and breaks every open stream mid-chunk — no drain, no
        # partial flush, exactly the wire signature of a dead process.
        # The manager's heartbeat then evicts it and in-flight rids
        # continue on survivors through the salvage path.
        self._killed = threading.Event()
        # manager this server registered with (serve.register_with_manager
        # / PoolManager.add_engine) — the leave/preempt lifecycle notifies
        # it on graceful departure; "" = never registered
        self.manager_endpoint = ""
        # optional FaultInjector (rollout/faults.py): observes admissions
        # and every outgoing stream line; can kill/corrupt/stall/drain
        self.fault = None
        self.receiver = None  # ReceiverAgent, attached by serve.py
        # quantized serving (models/quant.py): the wire format stays the
        # trainer's bf16 tree — weight_template carries that tree's
        # structure for layout/unflatten, weight_preprocess re-quantizes
        # each arriving push before the device swap. weight_apply (LoRA
        # delta sync) instead REPLACES the whole install step: it maps
        # (current engine params, received tree) -> new engine params —
        # adapter pushes touch only the a/b leaves, never the base.
        self.weight_template = None
        self.weight_preprocess = None
        self.weight_apply = None
        # a streamed round's clock starts BEFORE the trainer's pack, so the
        # receive wait gets the combined pack+wire budget (matches the
        # sender's stream_push_timeout_s)
        self.weight_sync_timeout_s = 3600.0
        self._weight_lock = threading.Lock()
        self._loop_thread: threading.Thread | None = None
        # fleet time-series rail (obs/timeseries.py): every server_info()
        # sample lands in the per-key ring under engine/* — the manager's
        # stats poller sets the cadence — and /statusz serves the windowed
        # aggregates + slopes as the "timeseries" section
        self._timeseries = obs.TimeSeriesStore()
        self._ts_samples = 0
        # bursts and lines written to clients, and the seconds each burst
        # took from the engine's put of its first line to the return of
        # the flush: their sum, and their counts by log2 bucket
        # (``obs/histogram``'s) for a window's tail
        self._stream_lock = threading.Lock()
        self.stream_chunks = 0
        self.stream_lines = 0
        self.stream_lag_s = 0.0
        self._stream_lag_buckets: dict[int, int] = {}

        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _send(self, code: int, body: bytes, ctype: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _json(self, code: int, obj: dict) -> None:
                self._send(code, json.dumps(obj).encode(), "application/json")

            def do_GET(self):
                if outer._killed.is_set():
                    self.close_connection = True
                    self.connection.close()
                    return
                if self.path == "/health":
                    self._json(200, {"status": "ok"})
                elif self.path == "/health_generate":
                    # a draining server is alive but must not pass the
                    # manager's serving health gate
                    if outer._draining.is_set():
                        self._json(503, {"status": "draining"})
                    else:
                        self._json(200, {"status": "ok"})
                elif self.path == "/get_server_info":
                    self._json(200, outer.server_info())
                elif self.path == "/statusz":
                    # live health plane: the SAME JSON schema the trainer's
                    # exporter serves (obs/statusz.py), so one parser
                    # sweeps both planes
                    self._json(200, outer.statusz_snapshot())
                elif self.path == "/metrics":
                    # Prometheus text exposition of the same telemetry the
                    # manager polls via /get_server_info
                    self._send(200, outer.metrics_text().encode(),
                               "text/plain; version=0.0.4")
                else:
                    self._json(404, {"error": f"no route {self.path}"})

            def do_POST(self):
                if outer._killed.is_set():
                    self.close_connection = True
                    self.connection.close()
                    return
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                if self.path == "/generate":
                    self.handle_generate(body)
                elif self.path == "/preempt":
                    # preemption notice (the cloud's "you have N seconds"):
                    # ack first, then run the drain + graceful leave off
                    # the handler thread so the notifier is never blocked
                    self._json(200, {"success": True, "draining": True})
                    threading.Thread(target=outer.leave, daemon=True).start()
                elif self.path == "/update_weights_from_agent":
                    ok, err = outer.update_weights_from_agent(
                        int(body.get("weight_version", -1)))
                    self._json(200 if ok else 500,
                               {"success": ok, "error": err})
                elif self.path == "/abort_request":
                    outer.abort_request(body.get("rid"))
                    self._json(200, {"success": True})
                elif self.path == "/drain":
                    self._json(200, outer.drain())
                elif self.path == "/flush_cache":
                    self._json(200, {"success": True})
                elif self.path == "/release_memory_occupation":
                    outer.release_memory()
                    self._json(200, {"success": True})
                elif self.path == "/resume_memory_occupation":
                    outer.resume_memory()
                    self._json(200, {"success": True})
                elif self.path == "/shutdown":
                    self._json(200, {"success": True})
                    threading.Thread(target=outer.stop, daemon=True).start()
                else:
                    self._json(404, {"error": f"no route {self.path}"})

            def handle_generate(self, body: dict) -> None:
                rid = str(body.get("rid", f"req-{time.monotonic_ns()}"))
                input_ids = [int(t) for t in body.get("input_ids", [])]
                sp = SamplingParams.from_dict(body.get("sampling_params", {}))
                # group-shared prefill hint (GRPO: rollout_n samples of one
                # prompt dispatched together): the engine prefills the
                # shared prompt ONCE and batch-attaches the siblings.
                # Optional fields — absent/zero degrades to per-request
                # admission, never corrupts.
                group_id = str(body.get("group_id", "") or "")
                group_size = int(body.get("group_size", 0) or 0)
                # cross-process trace adoption: the manager injects the
                # trainer's (trace_id, span_id) into the forwarded request,
                # so this engine span joins the trainer's trace — the last
                # hop of trainer→manager→engine
                trace_ctx = None
                if body.get("trace_id"):
                    trace_ctx = (str(body["trace_id"]),
                                 str(body.get("parent_span") or ""))
                tracer = obs.get_tracer()
                with tracer.adopt(trace_ctx), \
                        tracer.span("engine/generate", rid=rid):
                    self._stream_generate(rid, input_ids, sp,
                                          group_id, group_size)

            def _stream_generate(self, rid, input_ids, sp,
                                 group_id="", group_size=0) -> None:
                out_q, abort_ev = outer.submit(rid, input_ids, sp,
                                               group_id=group_id,
                                               group_size=group_size)

                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def chunk(line: str) -> None:
                    data = line.encode()
                    self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
                    self.wfile.flush()

                try:
                    done = False
                    while not done:
                        items = [out_q.get()]
                        # drain the burst: a multi-step dispatch fetch
                        # delivers k lines at once — one chunked write per
                        # burst instead of k write+flush syscall pairs
                        try:
                            while True:
                                items.append(out_q.get_nowait())
                        except queue.Empty:
                            pass
                        # truncate at the FIRST sentinel: failure paths can
                        # enqueue lines after a sentinel (e.g. a batch-wide
                        # error after a row already finished) and a
                        # sentinel object must never reach json.dumps
                        for i, it in enumerate(items):
                            if it is _SENTINEL:
                                items = items[:i]
                                done = True
                                break
                        if items:
                            with jax.profiler.TraceAnnotation(
                                    "server/stream_write"):
                                chunk("".join(outer._serialize_line(
                                    rid, i, abort_ev) for i in items))
                            outer._count_stream_chunk(items)
                    self.wfile.write(b"0\r\n\r\n")
                except (BrokenPipeError, ConnectionResetError):
                    outer.abort_request(rid)
                finally:
                    outer._drop_abort(rid, abort_ev)

        # default request_queue_size (listen backlog) is 5: a burst of
        # concurrent clients (the manager fanning a batch out) gets
        # connection resets before accept() ever runs
        server_cls = type("_RolloutHTTPServer", (ThreadingHTTPServer,),
                          {"request_queue_size": 1024})
        self._http = server_cls((host, port), Handler)
        self.port = self._http.server_address[1]
        self.endpoint = f"{advertise_host}:{self.port}"

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "RolloutServer":
        if self.cb:
            self.engine.start()
        else:
            self._loop_thread = threading.Thread(target=self._batch_loop, daemon=True)
            self._loop_thread.start()
        threading.Thread(target=self._http.serve_forever, daemon=True).start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self.cb:
            self.engine.stop()
        if self.receiver is not None:
            self.receiver.stop()
        self._http.shutdown()

    # -- request admission & batching loop ----------------------------------

    def submit(self, rid: str, input_ids: list[int],
               sp: SamplingParams, group_id: str = "",
               group_size: int = 0) -> tuple[queue.Queue, threading.Event]:
        """Admit one request; returns (output queue, abort event). The
        caller that registered the abort event must pass it back to
        ``_drop_abort`` — cleanup is identity-checked so a retry that
        re-used the rid cannot have its fresh event popped by the dying
        first attempt's teardown. ``group_id``/``group_size`` are the
        group-shared-prefill hint forwarded to the CB engine."""
        out: queue.Queue = queue.Queue()
        abort = threading.Event()
        if self._draining.is_set():
            # graceful preemption: refuse with a partial-abort terminal —
            # the manager's continuation layer re-routes the request
            out.put({"token_ids": [], "logprobs": [], "finished": True,
                     "finish_reason": "abort"})
            out.put(_SENTINEL)
            return out, abort
        # Duplicate in-flight rid: usually a manager retry racing the dying
        # first attempt (its handler thread drops the rid only after seeing
        # BrokenPipe on the next write). Abort the stale entry and give it a
        # short grace to clear before rejecting — a second registration
        # sharing the rid would orphan the first request's abort event.
        deadline = time.monotonic() + 2.0
        while True:
            with self._aborts_lock:
                stale = self._aborts.get(rid)
                if stale is None:
                    self._aborts[rid] = abort
                    break
                stale.set()
            if time.monotonic() >= deadline:
                out.put({"token_ids": [], "logprobs": [], "finished": True,
                         "finish_reason": "error",
                         "error": f"duplicate rid {rid!r} in flight"})
                out.put(_SENTINEL)
                return out, abort
            time.sleep(0.01)
        if self.fault is not None:
            self.fault.on_submit(self, rid, abort)
        if self._draining.is_set():
            # drain landed between the admission check and event
            # registration: its abort sweep missed this event — trip it
            # ourselves so the engine aborts the request into a partial
            abort.set()
        if self.cb:
            self.engine.submit(rid, input_ids, sp, out=out, abort=abort,
                               group_id=group_id, group_size=group_size)
        else:
            self._queue.put(_PendingRequest(rid, input_ids, sp, out, abort))
        return out, abort

    def abort_request(self, rid: str | None) -> None:
        """Abort one request, or ALL running requests when rid is None/'' —
        the manager's local time-slice abort (handlers.rs:500-513)."""
        with self._aborts_lock:
            if rid:
                ev = self._aborts.get(rid)
                if ev is not None:
                    ev.set()
            else:
                for ev in self._aborts.values():
                    ev.set()

    def drain(self) -> dict:
        """POST /drain — graceful preemption: stop admitting (new requests
        get an immediate partial-abort terminal), fail the serving health
        gate, and abort every in-flight request. With a salvage-enabled
        engine each abort flushes the tokens decoded so far as a partial,
        so the manager's continuation (or the trainer's salvage ledger)
        resumes them on another instance from the last token instead of
        re-decoding from zero."""
        self._draining.set()
        with self._aborts_lock:
            n = len(self._aborts)
        self.drain_count += n
        self.abort_request(None)
        return {"success": True, "draining": True, "aborted": n}

    def leave(self, grace_s: float = 0.5) -> None:
        """Graceful pool departure (POST /preempt, or a launcher's SIGTERM
        handler): drain — in-flight requests flush salvageable partials
        that re-route to surviving engines — then tell the manager this
        endpoint is gone so the routing set shrinks NOW instead of at the
        next heartbeat tick. Best-effort on the notify: the heartbeat is
        the backstop."""
        self.drain()
        time.sleep(grace_s)  # let abort partials flush through open streams
        if not self.manager_endpoint:
            return
        try:
            import urllib.request

            req = urllib.request.Request(
                f"http://{self.manager_endpoint}/deregister_rollout_instance",
                data=json.dumps({"endpoint": self.endpoint,
                                 "drained": True}).encode(),
                method="POST",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=5.0):
                pass
        except Exception:  # noqa: BLE001 — heartbeat eviction backstops
            log.warning("deregister with manager %s failed",
                        self.manager_endpoint, exc_info=True)

    def kill(self) -> None:
        """Chaos: die WITHOUT notice. No drain, no salvage flush, no
        manager notify — open streams break mid-chunk, new connections are
        dropped, and the listener closes. Recovery is entirely the pool's
        job (heartbeat eviction + manager continuation on survivors).
        ``stop()`` still owns the eventual resource teardown."""
        self._killed.set()
        # wake blocked handler threads: their next queue item hits the
        # killed check in _serialize_line and breaks the connection
        self.abort_request(None)
        threading.Thread(target=self._http.shutdown, daemon=True).start()

    def _serialize_line(self, rid: str, line: dict, abort_ev) -> str:
        """One outgoing NDJSON line; the fault injector may replace it
        (corruption), delay it (stall), or trip the abort event (kill)."""
        if self._killed.is_set():
            # dead engines don't speak: break the stream mid-chunk, exactly
            # where a SIGKILLed process would have
            raise BrokenPipeError("engine killed (chaos)")
        if self.fault is not None:
            replaced = self.fault.on_line(rid, line, abort_ev)
            if replaced is not None:
                return replaced
        return json.dumps(line) + "\n"

    def _count_stream_chunk(self, lines: list) -> None:
        """One burst of ``lines`` reached the socket: its lag runs from the
        engine's put of its first line (``StreamLine.t_put``, never
        serialized)."""
        t_put = getattr(lines[0], "t_put", None)
        if t_put is None:   # a terminal the server or an abort path wrote
            return
        lag = time.monotonic() - t_put
        bucket = bucket_index(lag)   # the logarithm outside the lock
        with self._stream_lock:
            self.stream_chunks += 1
            self.stream_lines += len(lines)
            self.stream_lag_s += lag
            self._stream_lag_buckets[bucket] = \
                self._stream_lag_buckets.get(bucket, 0) + 1

    def _drop_abort(self, rid: str, ev: threading.Event | None = None) -> None:
        with self._aborts_lock:
            if ev is None or self._aborts.get(rid) is ev:
                self._aborts.pop(rid, None)

    def _batch_loop(self) -> None:
        # requests pulled but not matching the current batch's sampling
        # group wait here and are served FIRST next round (no starvation
        # behind a sustained stream of another group)
        held: list[_PendingRequest] = []
        while not self._stop.is_set():
            if held:
                first = held.pop(0)
            else:
                try:
                    first = self._queue.get(timeout=0.2)
                except queue.Empty:
                    continue
            if self._paused.is_set():
                # engine yielded HBM to the trainer: wait for resume
                held.insert(0, first)
                time.sleep(0.05)
                continue
            batch = [first]
            deadline = time.monotonic() + self.batch_wait_s
            key = first.sampling.group_key()
            matched, unmatched = [], []
            for req in held:
                (matched if req.sampling.group_key() == key else unmatched).append(req)
            batch.extend(matched[: self.max_batch - 1])
            held = unmatched + matched[self.max_batch - 1 :]
            while len(batch) < self.max_batch:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                try:
                    req = self._queue.get(timeout=left)
                except queue.Empty:
                    break
                if req.sampling.group_key() == key:
                    batch.append(req)
                else:
                    held.append(req)
            try:
                self._run_batch(batch)
            except Exception as exc:  # noqa: BLE001 — fail the whole batch
                log.exception("batch failed")
                for req in batch:
                    req.out.put({"token_ids": [], "logprobs": [],
                                 "finished": True, "finish_reason": "error",
                                 "error": str(exc)})
                    req.out.put(_SENTINEL)

    def _run_batch(self, batch: list[_PendingRequest]) -> None:
        t0 = time.monotonic()
        self.engine.num_running = len(batch)
        prompts = [r.input_ids for r in batch]
        limits = [r.sampling.max_new_tokens for r in batch]
        flags = [r.abort for r in batch]
        total = 0
        closed = [False] * len(batch)
        with self._weight_lock:
            # tag each chunk with the weight version that sampled it: the
            # whole batch runs under _weight_lock, so one capture suffices
            wv = self.engine.weight_version
            stream = self.stepper.generate_stream(
                prompts, batch[0].sampling, max_new=limits, abort_flags=flags)
            for ev in stream:
                req = batch[ev["row"]]
                if ev["token"] is None:  # abort without a token this step
                    req.out.put({"token_ids": [], "logprobs": [],
                                 "finished": True, "finish_reason": "abort"})
                else:
                    total += 1
                    req.out.put({
                        "token_ids": [ev["token"]],
                        "logprobs": [ev["logprob"]],
                        "finished": ev["done"],
                        "finish_reason": ev["finish_reason"],
                        "weight_version": wv,
                    })
                if ev["done"]:
                    req.out.put(_SENTINEL)
                    closed[ev["row"]] = True
        # defense in depth: every handler MUST see a sentinel or it blocks
        # its HTTP thread forever
        for req, done in zip(batch, closed):
            if not done:
                req.out.put({"token_ids": [], "logprobs": [], "finished": True,
                             "finish_reason": "error",
                             "error": "stream ended without completion"})
                req.out.put(_SENTINEL)
        dt = time.monotonic() - t0
        self.engine.last_gen_throughput = self._tput_ewma.update(
            total / dt if dt > 0 else 0.0)
        self.engine.num_running = 0

    # -- telemetry / weights / memory ---------------------------------------

    def server_info(self) -> dict:
        info = {
            "num_running_reqs": self.engine.num_running,
            "num_queued_reqs": (self.engine.num_queued if self.cb
                                else self._queue.qsize()),
            "last_gen_throughput": self.engine.last_gen_throughput,
            "weight_version": self.engine.weight_version,
            # preemption announcement: the manager's heartbeat reads this
            # and pulls a draining engine out of the routing set before the
            # next batch routes to it
            "draining": self._draining.is_set(),
        }
        pc = getattr(self.engine, "prefix_cache", None)
        if pc is not None:
            info.update(pc.stats())
            # flat request-level hit fraction (length-unbiased, unlike
            # hit_rate which counts pages): flat key so the manager's
            # stats poller can forward it per instance
            info["prefix_hit_frac"] = round(pc.request_hit_frac, 6)
        if hasattr(self.engine, "admit_wave"):
            # admission scheduler geometry + group-shared prefill counters
            # (ARCHITECTURE.md "Group-shared prefill"): the knobs are
            # echoed so statusz records what the scheduler actually ran
            # with; the dispatch count bounds admission throughput
            info["admit_wave"] = self.engine.admit_wave
            info["admit_reorder_window"] = self.engine.admit_reorder_window
            info["group_share"] = bool(self.engine.group_share)
            # shared-prefix decode attention: the kernel-side group-share
            # switch + the pre-ref TTL knob echo (both config-driven, so
            # statusz records what the engine actually ran with), and
            # the grouped-dispatch counter
            info["decode_group_share"] = bool(
                getattr(self.engine, "decode_group_share", False))
            info["group_preref_ttl_s"] = float(
                getattr(self.engine, "group_preref_ttl_s", 0.0))
            info["grouped_decode_dispatches"] = int(
                getattr(self.engine, "grouped_decode_dispatches", 0))
            info["prefill_dispatches"] = self.engine.prefill_dispatches
            info["sibling_attach_dispatches"] = (
                self.engine.sibling_attach_dispatches)
            info["group_forked_requests"] = self.engine.group_forked_requests
        # partial-rollout salvage telemetry (cb engine); drained requests
        # are a server-level count (the /drain preemption path)
        if getattr(self.engine, "salvage_partials", False):
            info["tokens_salvaged"] = self.engine.tokens_salvaged
            info["salvage_published_pages"] = (
                self.engine.salvage_published_pages)
        if self.drain_count:
            info["drained_requests"] = self.drain_count
        if getattr(self.engine, "spec_tokens", 0):
            # speculative acceptance telemetry: emitted/dispatch vs the
            # spec_tokens+1 ceiling says whether the lookup is paying off
            info["spec_emitted"] = self.engine.spec_emitted
            info["spec_dispatches"] = self.engine.spec_dispatches
            info["spec_accept_rate"] = round(
                getattr(self.engine, "spec_accept_rate", 0.0), 4)
        deck = getattr(self.engine, "deck", None)
        if deck is not None:
            # engine flight deck: occupancy / page pressure / server-side
            # TTFT+TPOT tails / token-accounting reconciliation — flat keys
            # the manager's stats poller forwards
            info.update(deck.server_info_fields())
        loop_info = getattr(self.engine, "loop_profile_info", None)
        if loop_info is not None:
            # engine-loop profiler (obs/engine_profile.py): the windowed
            # device-vs-host split as flat keys — the manager's stats
            # poller forwards device_frac / accounting_frac per instance,
            # and the engine/* time-series
            # feed below picks them up ({} when rollout.loop_profile=false)
            info.update(loop_info())
        moe_info = getattr(self.engine, "moe_info", None)
        if moe_info is not None:
            # MoE load of the decode steps (cumulative; {} for a dense
            # model): pairs routed, experts hit, the busiest expert's rows
            info.update(moe_info())
        with self._stream_lock:
            info["stream_chunks"] = self.stream_chunks
            info["stream_lines"] = self.stream_lines
            info["stream_lag_s"] = round(self.stream_lag_s, 6)
            info["stream_lag_hist"] = [
                [i, n] for i, n in sorted(self._stream_lag_buckets.items())]
        kv_info = getattr(self.engine, "kv_memory_info", None)
        if kv_info is not None:
            # KV memory plane (rollout/kvledger.py): residency tiers, the
            # ledger↔pool reconciliation gauge, HBM truth, and the host
            # spill tier's kv_spilled_frac / kv_restore_rate — flat keys so
            # the manager's stats poller forwards kv_cold_page_frac /
            # hbm_headroom_gb / kv_spilled_frac per instance
            # ({} when rollout.kv_ledger=false)
            info.update(kv_info())
        if self.receiver is not None:
            # weight-sync health (transfer/agents.py ReceiverAgent.health):
            # control-channel reconnects, rejected CRC frames, verify
            # failures, resume bytes — a flapping sender or a corrupting
            # link is visible per engine in server_info and /statusz
            health = getattr(self.receiver, "health", None)
            if health is not None:
                info.update(health())
        # time-series sample: the numeric fields land in the engine/* ring
        # (sample index as x — occupancy/queue-depth slopes over the
        # poller's cadence, not the trainer's step clock)
        self._ts_samples += 1
        self._timeseries.observe(self._ts_samples, {
            "engine/" + k: v for k, v in info.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)})
        return info

    def statusz_snapshot(self) -> dict:
        """The rollout plane's side of the shared /statusz schema
        (ARCHITECTURE.md "Goodput & health plane"): engine queue depths,
        decode throughput, weight version, salvage/drain/fault-injection
        counters — one curl answers "what is this engine doing"."""
        from polyrl_tpu.obs import statusz

        info = self.server_info()
        counters = {k: v if isinstance(v, list) else float(v)
                    for k, v in info.items()
                    if k in ("tokens_salvaged", "salvage_published_pages",
                             "drained_requests", "spec_emitted",
                             "spec_dispatches", "prefill_dispatches",
                             "sibling_attach_dispatches",
                             "group_forked_requests",
                             "grouped_decode_dispatches")
                    or k in CUMULATIVE_INFO_KEYS | MOE_INFO_KEYS}
        counters["total_tokens_served"] = float(
            getattr(self.engine, "total_tokens_served", 0))
        if self.fault is not None:
            counters.update(self.fault.counters())
        gauges = {k: float(v) for k, v in info.items()
                  if isinstance(v, (int, float))
                  and not isinstance(v, bool) and k not in counters}
        gauges["draining"] = float(self._draining.is_set())
        gauges["paused"] = float(self._paused.is_set())
        deck = getattr(self.engine, "deck", None)
        engine_section = {}
        if deck is not None:
            engine_section = deck.snapshot(
                active=int(info.get("num_running_reqs", 0)),
                queued=int(info.get("num_queued_reqs", 0)))
            if getattr(self.engine, "spec_tokens", 0):
                engine_section["spec"] = {
                    "accept_rate": float(info.get("spec_accept_rate", 0.0)),
                    "emitted": int(self.engine.spec_emitted),
                    "dispatches": int(self.engine.spec_dispatches),
                }
            if hasattr(self.engine, "admit_wave"):
                # group-shared prefill: scheduler geometry + fork counters
                # (the "did sharing actually happen" answer for one curl)
                engine_section["group"] = {
                    "admit_wave": int(self.engine.admit_wave),
                    "admit_reorder_window": int(
                        self.engine.admit_reorder_window),
                    "group_share": bool(self.engine.group_share),
                    "decode_group_share": bool(
                        getattr(self.engine, "decode_group_share", False)),
                    "group_preref_ttl_s": float(
                        getattr(self.engine, "group_preref_ttl_s", 0.0)),
                    "prefill_dispatches": int(self.engine.prefill_dispatches),
                    "sibling_attach_dispatches": int(
                        self.engine.sibling_attach_dispatches),
                    "group_forked_requests": int(
                        self.engine.group_forked_requests),
                    "grouped_decode_dispatches": int(getattr(
                        self.engine, "grouped_decode_dispatches", 0)),
                    "prefill_reuse_frac": float(
                        info.get("prefill_reuse_frac", 0.0)),
                    "prefix_hit_frac": float(
                        info.get("prefix_hit_frac", 0.0)),
                    # shared-prefix decode attention: streamed-vs-logical
                    # KV page dedup (the bandwidth actually saved)
                    "kv_read_pages_per_token": float(
                        info.get("kv_read_pages_per_token", 0.0)),
                    "shared_prefix_read_frac": float(
                        info.get("shared_prefix_read_frac", 0.0)),
                }
        # engine-loop profiler block: ALWAYS present in the engine section
        # since v8 (even with the deck off / non-cb engines) so consumers
        # never need existence checks — {"enabled": false} when off
        loop_snap = getattr(self.engine, "loop_profile_snapshot", None)
        engine_section["loop"] = (loop_snap() if loop_snap is not None
                                  else {"enabled": False})
        kv_snap = getattr(self.engine, "kv_memory_snapshot", None)
        return statusz.build_snapshot(
            "rollout",
            counters=counters, gauges=gauges,
            queues={"running": float(info.get("num_running_reqs", 0)),
                    "queued": float(info.get("num_queued_reqs", 0))},
            weights={"version": float(self.engine.weight_version)},
            engine=engine_section,
            timeseries=self._timeseries.section(),
            # KV memory plane (v6): per-page roles/tiers/churn + the
            # reconciliation block ({} for non-cb engines / ledger off)
            memory=kv_snap() if kv_snap is not None else None)

    def metrics_text(self) -> str:
        """Prometheus text format for /metrics: server_info fields as
        gauges, cumulative values (tokens served, the engine's
        completion-stamp counters, stream chunks) as counters. Full
        precision — %g-style rounding makes rate() over large counters
        see flat-then-jump."""

        def fmt(v):
            return str(int(v)) if float(v).is_integer() else repr(float(v))

        lines = []
        info = dict(self.server_info())
        info.setdefault("total_tokens_served",
                        getattr(self.engine, "total_tokens_served", 0))
        for k, v in info.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            name = "polyrl_" + k.replace("#", "num_").replace("/", "_")
            kind = ("counter" if k == "total_tokens_served"
                    or k in CUMULATIVE_INFO_KEYS | MOE_INFO_KEYS
                    else "gauge")
            lines.append(f"# TYPE {name} {kind}")
            lines.append(f"{name} {fmt(v)}")
        return "\n".join(lines) + "\n"

    def _flush_engine_prefix_cache(self) -> None:
        """Cached prefix KV was computed under the OLD weights/adapters; any
        disaggregated install path must invalidate it, exactly like
        in-process swaps do (cb_engine.update_weights flushes for the same
        reason). The bucketed v0 engine has no prefix cache — no-op there."""
        flush = getattr(self.engine, "flush_prefix_cache", None)
        if flush is not None:
            flush()

    def update_weights_from_agent(self, version: int) -> tuple[bool, str]:
        """Load weights v``version`` from the receiver buffer into the live
        engine (TPU analogue of the reference's chunked host->GPU broadcast
        load, patches.py:169-241: here one sharded device_put, GSPMD handles
        distribution)."""
        if self.receiver is None:
            # in-process updates (colocated): trainer calls
            # engine.update_weights directly; just ack the version
            self.engine.weight_version = version
            return True, ""
        try:
            from polyrl_tpu.transfer.layout import (
                make_incremental_installer, make_sharded_installer,
                unflatten_like, unpack_params,
            )

            template = (self.weight_template if self.weight_template
                        is not None else self.engine.params)
            if self.weight_apply is None and self.weight_preprocess is None:
                # full-tree bf16 path: upload each tensor AS ITS BYTES LAND
                # (wire || device_put — the receive-side half of the
                # streaming sync pipeline). Delta/int8 installs transform
                # the assembled tree, so they keep the post-wire path.
                # dtype/sharding come from the LIVE tree (template may be
                # abstract ShapeDtypeStructs), matching the serial path's
                # tree_map over engine.params. tp>1 engines take the
                # SHARDED installer: each leaf lands shard-by-shard via
                # per-device device_put + assembly, so the full-size
                # device array never materializes on one chip.
                if getattr(self.engine, "mesh", None) is not None:
                    install, device_named = make_sharded_installer(
                        self.engine.params)
                else:
                    install, device_named = make_incremental_installer(
                        self.engine.params)
                # record the version actually INSTALLED: when a
                # superseding round's bytes landed instead, reporting the
                # older requested version would under-report until the
                # newer push's own update call (advisor r4)
                installed = self.receiver.wait_for_version(
                    version, timeout=self.weight_sync_timeout_s,
                    on_tensor=install)
                if installed is None:  # pre-r5 receiver contract
                    installed = version
                new_params = unflatten_like(template, device_named)
                with self._weight_lock:  # not mid-batch
                    self.engine.params = new_params
                    self.engine.weight_version = installed
                    self._flush_engine_prefix_cache()
                return True, ""
            installed = self.receiver.wait_for_version(
                version, timeout=self.weight_sync_timeout_s)
            if installed is None:  # pre-r5 receiver contract
                installed = version
            named = unpack_params(self.receiver.buffer, self.receiver.layout)
            new_params = unflatten_like(template, named)
            if self.weight_apply is not None:
                # delta sync: the received tree is NOT full params (e.g.
                # LoRA adapters) — the hook installs it into the current
                # tree itself, device-putting only what changed
                with self._weight_lock:
                    self.engine.params = self.weight_apply(
                        self.engine.params, new_params)
                    self.engine.weight_version = installed
                    self._flush_engine_prefix_cache()
                return True, ""
            if self.weight_preprocess is not None:
                new_params = self.weight_preprocess(new_params)
            with self._weight_lock:  # not mid-batch
                old = self.engine.params
                self.engine.params = jax.tree_util.tree_map(
                    lambda o, n: jax.device_put(
                        np.asarray(n).astype(o.dtype), o.sharding), old,
                    new_params)
                self.engine.weight_version = installed
                self._flush_engine_prefix_cache()
            return True, ""
        except Exception as exc:  # noqa: BLE001
            log.exception("weight load failed")
            return False, str(exc)

    def release_memory(self) -> None:
        self._paused.set()
        self.engine.release_memory()

    def resume_memory(self) -> None:
        self.engine.resume_memory()
        self._paused.clear()
