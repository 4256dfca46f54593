"""Benchmark: the SERVING path the manager actually routes to, plus the
subsystem numbers earlier rounds tracked (BASELINE.md: rollout tok/s/chip
at 8B-class, trainer→rollout weight sync seconds).

Runs on the TPU chip, in ONE process, and fails (non-zero exit, traceback)
when a phase fails or the device is not in the peaks table. Prints ONE
JSON line ``{"metric", "value", "unit", "vs_baseline", "extra"}``:

- ``metric``/``value``: CB (paged continuous-batching) SERVING throughput —
  concurrent HTTP requests through ``rollout/server.py`` into ``CBEngine``,
  i.e. production ``rollout/serve.py`` backend="cb".
- ``extra.cb_direct``: same engine driven in-process (no HTTP) — the gap to
  cb_serve isolates dispatch/HTTP overhead from device compute.
- ``extra.bucketed``: the v0 bucketed ``RolloutEngine`` decode number
  (the earliest chip rows measured this engine; kept for continuity).
- ``extra.weight_sync``: the STREAMED sync round for the FULL flagship
  param set — pack ‖ localhost TCP (sender/receiver agents) ‖ per-tensor
  device install, total seconds + effective MB/s
  (reference KPI: sender_agent.py:628-630).
- ``extra.llama3_8b``: 8B-class decode tok/s/chip — bf16 when the chip's
  HBM fits it (8.03 B params = 16.06 GiB, so not on a 16 GiB chip), else
  the int8 weight-only-quantized CB engine (models/quant.py, ≈ 8.6 GiB).

Phases run sequentially (single-chip HBM is reused; the bucketed engine is
freed before the CB pool is allocated, and everything before the 8B
attempt is freed first). The ``--chaos``/``--pool``/... side modes below
are CPU-sized drills of counts, not device measurements.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
import threading
import time
import urllib.request

# phase name → key its result is stored under in extra
PHASE_STORE_KEYS = {"8b": "llama3_8b"}


def _cli_float(flag: str, default: float) -> float:
    """Tiny ``--flag=N`` / ``--flag N`` parser."""
    argv = sys.argv
    for i, a in enumerate(argv):
        if a == flag and i + 1 < len(argv):
            return float(argv[i + 1])
        if a.startswith(flag + "="):
            return float(a.split("=", 1)[1])
    return default


def _note(name: str, result) -> None:
    # progress to stderr: a later phase's failure still shows what ran
    print(f"[bench] {name}: {json.dumps(result)}", file=sys.stderr, flush=True)


def _hbm_limit_gb() -> float:
    import jax

    try:
        stats = jax.devices()[0].memory_stats()
        return stats.get("bytes_limit", 0) / (1 << 30)
    except Exception:  # noqa: BLE001 — CPU backend has no memory_stats
        return 0.0


def bench_bucketed(cfg, params, batch, prompt_len, new_tokens):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.rollout.engine import RolloutEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    engine = RolloutEngine(
        cfg, params, pad_token_id=0,
        batch_buckets=(batch,), prompt_buckets=(prompt_len,),
        kv_cache_dtype=jnp.bfloat16,
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(batch)]
    sp = SamplingParams(temperature=1.0, max_new_tokens=new_tokens,
                        stop_token_ids=())
    engine.generate(prompts, sp, rng=jax.random.PRNGKey(0))  # compile
    # two timed reps: r1→r2 showed a -1.5% drift on single-rep numbers;
    # reporting best-of-2 plus both reps makes run-to-run variance visible
    # instead of reading as a regression
    reps = []
    for i in (1, 2):
        t0 = time.monotonic()
        outs = engine.generate(prompts, sp, rng=jax.random.PRNGKey(i))
        dt = time.monotonic() - t0
        reps.append({"tok_s": round(sum(o.completion_tokens
                                        for o in outs) / dt, 1),
                     "wall_s": round(dt, 2)})
    del engine
    gc.collect()
    # headline = MEAN of the reps (comparable to prior rounds' single-rep
    # numbers); best-of-2 stays visible under its own tagged key so
    # round-over-round BENCH diffs are never apples-to-oranges (advisor r5)
    best = max(reps, key=lambda r: r["tok_s"])
    return {"tok_s": round(sum(r["tok_s"] for r in reps) / len(reps), 1),
            "tok_s_best2": best["tok_s"],
            "wall_s": round(sum(r["wall_s"] for r in reps) / len(reps), 2),
            "reps": reps}


def _http_generate(endpoint: str, rid: str, input_ids,
                   max_new: int) -> tuple[int, float]:
    """One serving request; returns (generated-token count, time-to-first-
    token seconds) — drains the NDJSON stream like the manager's router."""
    body = json.dumps({
        "rid": rid, "input_ids": input_ids,
        "sampling_params": {"temperature": 1.0, "max_new_tokens": max_new,
                            "stop_token_ids": []},
    }).encode()
    req = urllib.request.Request(
        f"http://{endpoint}/generate", data=body, method="POST",
        headers={"Content-Type": "application/json"})
    n = 0
    t0 = time.monotonic()
    ttft = 0.0
    with urllib.request.urlopen(req, timeout=600.0) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line:
                continue
            got = len(json.loads(line).get("token_ids", []))
            if got and not ttft:
                ttft = time.monotonic() - t0
            n += got
    return n, ttft


def make_cb_engine(cfg, params, prompt_len, new_tokens, *, max_slots=64,
                   page_size=64, steps_per_dispatch=8,
                   spec_tokens=0, prompt_buckets=None):
    """Shared CB-engine construction for bench phases AND the knob-sweep
    tool (tools/bench_cb_sweep.py) — one code path so sweep findings
    reproduce in bench.py. ``prompt_buckets`` overrides the single
    prompt_len bucket (phases mixing prompt lengths need per-length
    buckets: admission pads to the NEXT bucket, so one oversized bucket
    would inflate every shorter prompt's timed prefill)."""
    import jax.numpy as jnp

    from polyrl_tpu.rollout.cb_engine import CBEngine

    page_size = min(page_size, prompt_len)  # buckets must be page-aligned
    buckets = tuple(-(-b // page_size) * page_size
                    for b in (prompt_buckets or (prompt_len,)))
    max_seq = buckets[-1] + new_tokens
    max_seq = -(-max_seq // page_size) * page_size
    pages_per = max_seq // page_size
    return CBEngine(
        cfg, params, pad_token_id=0, kv_cache_dtype=jnp.bfloat16,
        max_slots=max_slots, page_size=page_size, max_seq_len=max_seq,
        prompt_buckets=buckets, steps_per_dispatch=steps_per_dispatch,
        num_pages=max_slots * pages_per * 2 + 8,
        spec_tokens=spec_tokens)


def engine_phase_trace(engine) -> dict:
    """Cumulative seconds and ``n_<phase>`` counts per engine-loop phase,
    from the loop profiler (the one seam that times them)."""
    snap = engine.loop_profile_snapshot()
    out = {k: round(v, 3) for k, v in snap.get("phase_s", {}).items()}
    out.update({"n_" + k: n for k, n in snap.get("phase_n", {}).items()})
    return dict(sorted(out.items()))


def warmup_cb(engine, cfg, rng, prompt_len):
    """Deterministic precompile of every admission bucket + decode variant
    (engine.warmup drives each compiled fn against the sink row — a
    generate-based warmup fragmented into prefix-cache suffix hits and left
    batch buckets uncompiled, putting ~15 s XLA compiles in the timed
    window), then tiny generates covering the end-to-end and prefix-suffix
    paths. Benches sample temperature-only → only no-filter variants."""
    from polyrl_tpu.rollout.sampling import SamplingParams

    engine.warmup(filter_variants=(False,))
    warm_prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                    for _ in range(2)]
    warm_sp = SamplingParams(temperature=1.0, max_new_tokens=8,
                             stop_token_ids=())
    engine.generate(warm_prompts, warm_sp, timeout=600.0)
    engine.generate([warm_prompts[0]], warm_sp, timeout=600.0)  # suffix path
    engine.flush_prefix_cache()


def _cb_async_rl_drill(engine, params, cfg, rng, prompt_len, new_tokens,
                       groups=8, g=8, push_period_s=2.0):
    """RL-shaped rollout drill inside the cb phase: GRPO group traffic
    (``groups`` shared prompts × ``g`` siblings — the group-shared prefill
    path, with the engine's dispatch pipelining on) while a background
    thread installs weight versions at the bounded-staleness cadence, so
    sequences legitimately span versions mid-decode exactly as a
    ``staleness_limit>1`` training run produces them. This is the
    post-PR-3/8 ``rollout_decode_tok_s_per_chip`` headline shape the
    ROADMAP bench debt names: decode throughput with pipelining +
    group-share + async-k on, gated by the staleness extras bench_gate
    watches (per-token ``weight_versions`` measure the spread directly)."""
    import numpy as np

    from polyrl_tpu.rollout.cb_engine import STREAM_END
    from polyrl_tpu.rollout.sampling import SamplingParams

    sp = SamplingParams(temperature=1.0, max_new_tokens=new_tokens,
                        stop_token_ids=())
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(groups)]
    outs = []
    for gi, p in enumerate(prompts):
        for si in range(g):
            outs.append(engine.submit(f"rl-{gi}-{si}", p, sp,
                                      group_id=f"rl-{gi}", group_size=g))
    stop = threading.Event()
    installs = [0]

    def pusher() -> None:
        # the async-k cadence: new versions land WHILE decode streams
        # (re-installing the same values, so later phases see identical
        # weights — only the version counter moves)
        while not stop.wait(push_period_s):
            engine.update_weights(params, version=engine.weight_version + 1)
            installs[0] += 1

    pt = threading.Thread(target=pusher, daemon=True)
    t0 = time.monotonic()
    pt.start()
    total = 0
    mixed = 0
    all_vs: list = []
    try:
        for q in outs:
            vs: list = []
            while True:
                item = q.get(timeout=1200)
                if item is STREAM_END:
                    break
                total += len(item["token_ids"])
                vs.extend([int(item.get("weight_version", -1))]
                          * len(item["token_ids"]))
            if len(set(vs)) > 1:
                mixed += 1
            all_vs.extend(vs)
    finally:
        stop.set()
        pt.join(timeout=60.0)
    wall = time.monotonic() - t0
    final_v = int(engine.weight_version)
    lag = final_v - np.asarray([v for v in all_vs if v >= 0], np.int64)
    return {
        "decode_tok_s": round(total / wall, 1) if wall > 0 else 0.0,
        "wall_s": round(wall, 2),
        "groups": groups, "g": g, "new_tokens": new_tokens,
        # shared-prefix decode attention on the grouped traffic: pages the
        # decode kernels streamed per token and the dedup fraction (the
        # deck is cumulative over the cb phase; this drill is its only
        # grouped segment, so a nonzero frac means sharing engaged)
        "kv_read_pages_per_token": round(
            engine.deck.kv_read_pages_per_token(), 3),
        "shared_prefix_read_frac": round(
            engine.deck.shared_prefix_read_frac(), 4),
        "grouped_decode_dispatches": int(
            getattr(engine, "grouped_decode_dispatches", 0)),
        "weight_installs": installs[0],
        "mixed_version_seq_frac": round(mixed / max(len(outs), 1), 4),
        "staleness_p95": round(float(np.percentile(lag, 95)), 2)
        if lag.size else 0.0,
        "staleness_max": int(lag.max()) if lag.size else 0,
    }


def _cb_push_shard_drill(params, streams: int = 4,
                         cap_mb: float | None = None) -> dict:
    """Sharded-push wall of the cb phase's REAL weights: one warm-up round
    plus one timed round over the production fabric (SenderAgent with the
    resharding map engaged → ``streams`` parallel shard-to-shard TCP
    streams into a loopback receiver). assemble_result promotes the result
    as ``extra.transfer_push_streams`` / ``extra.push_shard_wall_s`` so
    real-TPU rounds record the sharded-push wall alongside
    ``rollout_decode_tok_s_per_chip``. Never fails the phase: errors and
    over-cap sizes come back as a skip note."""
    import numpy as np

    from polyrl_tpu.transfer.agents import (ReceiverAgent, SenderAgent,
                                            TransferConfig)
    from polyrl_tpu.transfer.layout import (alloc_buffer, build_layout,
                                            build_shard_spec, pack_params)

    cap_mb = float(os.environ.get("POLYRL_BENCH_PUSH_SHARD_CAP_MB",
                                  cap_mb if cap_mb is not None else 8192))
    sender = None
    rx = None
    try:
        layout = build_layout(params)
        total_mb = layout.total_bytes / (1 << 20)
        if total_mb > cap_mb:
            return {"skipped": f"weights {total_mb:.0f} MB > cap {cap_mb} MB"}
        # generous deadline floor: loopback TCP easily beats 200 Mbps, and
        # a drill timeout must not look like a fabric regression
        tcfg = TransferConfig(min_bandwidth_mbps=200.0,
                              deadline_slack_s=5.0, stream_slack_s=5.0,
                              retry_budget=2, backoff_base_s=0.05,
                              backoff_max_s=0.2)
        buf = alloc_buffer(layout)
        sender = SenderAgent(buf, manager_client=None,
                             listen_host="127.0.0.1", num_streams=streams,
                             poll_s=0.05, advertise_host="127.0.0.1",
                             cfg=tcfg, layout=layout,
                             trainer_spec=build_shard_spec(params,
                                                           axis="fsdp"))
        sender.start()
        rx = ReceiverAgent(layout, "cb-push-shard", sender.endpoint,
                           num_streams=streams, listen_host="127.0.0.1",
                           advertise_host="127.0.0.1",
                           shard_spec=build_shard_spec(params, axis="tp"))
        rx.start()
        time.sleep(0.5)  # registration handshake
        with sender.buffer_write_lock():
            pack_params(params, layout, buf)  # D2H once; both rounds reuse
        v = sender.signal_update()            # warm-up (first-round setup)
        rx.wait_for_version(v, timeout=600.0)
        t0 = time.monotonic()
        v = sender.signal_update()
        rx.wait_for_version(v, timeout=600.0)
        wall = time.monotonic() - t0
        return {
            "push_wall_s": round(wall, 3),
            "push_streams": int(sender.push_streams),
            "stream_bw_mbps_min": round(sender.stream_bw_mbps_min, 1),
            "reshard_bytes": int(sender.reshard_bytes),
            "stream_resumes": int(sender.stream_resumes),
            "total_bytes": int(layout.total_bytes),
            "wire_gbps": round(layout.total_bytes * 8 / wall / 1e9, 2)
            if wall > 0 else 0.0,
            "bitwise_ok": bool(np.array_equal(rx.buffer, buf)),
        }
    except Exception as exc:  # noqa: BLE001 — advisory drill only
        return {"skipped": f"error: {str(exc)[:200]}"}
    finally:
        if rx is not None:
            rx.stop()
        if sender is not None:
            sender.stop()


def bench_cb(cfg, params, batch, prompt_len, new_tokens, max_slots=64,
             page_size=64, steps_per_dispatch=8):
    """CB engine: direct in-process batch, then concurrent HTTP serving
    (FRESH prompts per phase so the serve number isn't inflated by
    prefix-cache hits on the direct phase's pages)."""
    import numpy as np

    from polyrl_tpu.rollout.sampling import SamplingParams
    from polyrl_tpu.rollout.server import RolloutServer

    engine = make_cb_engine(cfg, params, prompt_len, new_tokens,
                            max_slots=max_slots, page_size=page_size,
                            steps_per_dispatch=steps_per_dispatch)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(batch)]
    serve_prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                     for _ in range(batch)]
    sp = SamplingParams(temperature=1.0, max_new_tokens=new_tokens,
                        stop_token_ids=())
    warmup_cb(engine, cfg, rng, prompt_len)

    # direct (no HTTP): device + scheduler, no dispatch layer. Optional
    # on-chip profile of this window (POLYRL_BENCH_PROFILE_DIR): the trace
    # to study when attacking the serving roofline.
    prof_dir = os.environ.get("POLYRL_BENCH_PROFILE_DIR", "")
    if prof_dir:
        import jax as _jax

        prof_cm = _jax.profiler.trace(prof_dir)
    else:
        prof_cm = contextlib.nullcontext()
    t0 = time.monotonic()
    with prof_cm:
        outs = engine.generate(prompts, sp, timeout=1200.0)
    dt_direct = time.monotonic() - t0
    direct_tokens = sum(len(o["token_ids"]) for o in outs)
    engine.flush_prefix_cache()

    # serving: concurrent requests through the production HTTP surface
    from polyrl_tpu.obs.histogram import Histogram

    server = RolloutServer(engine, host="127.0.0.1", port=0).start()
    counts = [0] * batch
    errs: list[str] = []

    ttfts = [0.0] * batch
    # end-to-end request latency distribution under the full concurrent
    # load (obs log2 histogram: the same summary the trainer's step
    # records carry for remote rollout)
    req_hist = Histogram()
    hist_lock = threading.Lock()

    def worker(lo: int, hi: int) -> None:
        for i in range(lo, hi):
            t_req = time.monotonic()
            try:
                counts[i], ttfts[i] = _http_generate(
                    server.endpoint, f"bench-{i}", serve_prompts[i],
                    new_tokens)
                with hist_lock:
                    req_hist.observe(time.monotonic() - t_req)
            except Exception as exc:  # noqa: BLE001
                errs.append(str(exc))

    n_workers = min(64, batch)
    per = -(-batch // n_workers)
    # sample the engine's 10 s-window throughput during the run: the peak is
    # the steady-state number with ramp-up/drain and admission stalls
    # excluded (what a continuous training stream would sustain). Reset the
    # window first or the direct phase's number leaks into the serve peak.
    engine.reset_throughput_window()
    peak = [0.0]
    stop_sampling = threading.Event()

    def sampler() -> None:
        while not stop_sampling.is_set():
            peak[0] = max(peak[0], engine.last_gen_throughput)
            time.sleep(0.5)

    sampler_t = threading.Thread(target=sampler, daemon=True)
    sampler_t.start()
    t0 = time.monotonic()
    threads = [threading.Thread(target=worker,
                                args=(w * per, min((w + 1) * per, batch)))
               for w in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt_serve = time.monotonic() - t0
    stop_sampling.set()
    sampler_t.join(timeout=5.0)  # before del engine: the closure reads it
    serve_tokens = sum(counts)
    ttft_ok = [t for t in ttfts if t]  # failed/zero-token requests excluded
    # server-side flight-deck readout (engine ledger): occupancy, page
    # pressure, cache hit rate, and the server-measured TTFT/TPOT tails —
    # the numbers the client-side ttft_* above cannot see (queue wait vs
    # prefill split, decode interval). Captured before stop() tears the
    # engine down.
    srv_info = server.server_info()
    # RL-shaped sub-phase AFTER the serve flight-deck capture (so the
    # serving numbers stay unpolluted): group-shared GRPO traffic with
    # async-cadence weight installs overlapping decode — the post-PR-3/8
    # rollout-decode headline shape (promoted by assemble_result as
    # extra.rollout_decode_tok_s_per_chip, watched by bench_gate)
    rl = _cb_async_rl_drill(engine, params, cfg, rng, prompt_len,
                            new_tokens,
                            groups=int(os.environ.get("POLYRL_BENCH_RL_GROUPS",
                                                      "8")),
                            g=int(os.environ.get("POLYRL_BENCH_RL_G", "8")))
    server.stop()
    # sharded-push wall of this phase's real weights (4 parallel
    # shard-to-shard streams over the production fabric) — promoted by
    # assemble_result as extra.transfer_push_streams/push_shard_wall_s
    push_shard = _cb_push_shard_drill(params)
    trace = engine_phase_trace(engine)
    del engine
    gc.collect()
    return {
        "rl": rl,        # group-share + async-k rollout drill
        "push_shard": push_shard,  # N-stream sharded push of the real bytes
        "trace": trace,  # cumulative s (and n_*) per engine phase
        "direct_tok_s": round(direct_tokens / dt_direct, 1),
        "serve_tok_s": round(serve_tokens / dt_serve, 1),
        "serve_wall_s": round(dt_serve, 2),
        "dispatch_overhead_pct": round(
            100.0 * (1 - (serve_tokens / dt_serve) /
                     max(direct_tokens / dt_direct, 1e-9)), 1),
        "errors": len(errs),
        "error_sample": errs[0][:200] if errs else "",
        "serve_peak_tok_s": round(peak[0], 1),
        # admission-to-first-token latency under the full concurrent load
        # (includes queueing behind earlier admissions — the serving-side
        # KPI the throughput numbers don't capture)
        "ttft_p50_ms": round(float(np.percentile(ttft_ok, 50)) * 1e3, 1)
        if ttft_ok else 0.0,
        "ttft_p95_ms": round(float(np.percentile(ttft_ok, 95)) * 1e3, 1)
        if ttft_ok else 0.0,
        # full request wall (admission + queue + decode), log2-histogram
        # percentiles — the serving-tail KPI next to the TTFT numbers
        "req_p50_s": round(req_hist.percentile(50.0), 3),
        "req_p95_s": round(req_hist.percentile(95.0), 3),
        "req_p99_s": round(req_hist.percentile(99.0), 3),
        # engine flight deck (server_info): mean decode occupancy over the
        # run's dispatches, peak page-pool utilization, prefix-cache hit
        # rate, server-side latency tails, and the token-accounting
        # reconciliation ratio (1.0 = every scheduled token attributed)
        "engine_occupancy": round(float(srv_info.get("occupancy_mean",
                                                     0.0)), 4),
        "engine_page_util_peak": round(float(srv_info.get("page_util_peak",
                                                          0.0)), 4),
        "engine_cache_hit_rate": round(float(srv_info.get(
            "prefix_cache/hit_rate", 0.0)), 4),
        "engine_ttft_p95_ms": round(1e3 * float(srv_info.get("ttft_p95_s",
                                                             0.0)), 1),
        "engine_tpot_p95_ms": round(1e3 * float(srv_info.get("tpot_p95_s",
                                                             0.0)), 2),
        "engine_queue_wait_p95_ms": round(1e3 * float(srv_info.get(
            "queue_wait_p95_s", 0.0)), 1),
        "engine_attributed_frac": round(float(srv_info.get(
            "attributed_frac", 0.0)), 4),
        # group-shared prefill telemetry (near-zero on this phase's random
        # distinct prompts — the --group-share A/B is the shared-prompt
        # probe; recorded here so TPU rounds track the serving default)
        "engine_prefill_reuse_frac": round(float(srv_info.get(
            "prefill_reuse_frac", 0.0)), 4),
        "engine_prefill_dispatches": int(srv_info.get(
            "prefill_dispatches", 0)),
        "engine_sibling_attach_dispatches": int(srv_info.get(
            "sibling_attach_dispatches", 0)),
        # shared-prefix decode attention (the rl drill is the phase's
        # grouped segment — the read-frac the gate holds across rounds)
        "engine_shared_prefix_read_frac": float(rl.get(
            "shared_prefix_read_frac", 0.0)),
        "engine_kv_read_pages_per_token": float(rl.get(
            "kv_read_pages_per_token", 0.0)),
        # KV memory plane (rollout/kvledger.py via server_info): cold
        # residency at end of run and the device HBM headroom — the two
        # gauges bench_gate holds across rounds (cold creeping up = a
        # residency leak; headroom dropping = something grew into the
        # page pool's margin). Headroom is absent on CPU-sized rounds.
        "engine_kv_cold_page_frac": round(float(srv_info.get(
            "kv_cold_page_frac", 0.0)), 4),
        **({"engine_hbm_headroom_gb": round(float(
            srv_info["hbm_headroom_gb"]), 3)}
           if "hbm_headroom_gb" in srv_info else {}),
        # engine-loop profiler (obs/engine_profile.py via server_info):
        # the windowed device-vs-host split at end of run — device_frac
        # dropping across rounds means the loop thread got host-bound,
        # accounting_frac rising means the deck/ledger/spill bookkeeping
        # started eating the loop (the two gauges bench_gate holds)
        "engine_device_frac": round(float(srv_info.get(
            "device_frac", 0.0)), 4),
        "engine_accounting_frac": round(float(srv_info.get(
            "accounting_frac", 0.0)), 4),
    }


def bench_spec(cfg, params, batch=64, prompt_len=128, new_tokens=128,
               spec_tokens=4):
    """Prompt-lookup speculative decoding A/B, GREEDY decode, on TWO
    workloads so the number is interpretable:

    - ``random``: fresh random prompts — the ADVERSARIAL case (no n-gram
      in the prompt ever predicts the continuation), bounding the
      verify-overhead cost of leaving spec on for the wrong workload.
    - ``continuation``: prompt = original prompt + the first half of the
      model's own greedy output (from the off run). Greedy decode is
      deterministic, so the timed continuation equals the off run's second
      half token-for-token — same compute either way — and whenever the
      model's output is locally repetitive (random-init models loop under
      greedy; real math/code CoT behaves similarly) the lookup actually
      accepts. ``tok_per_dispatch`` reports measured acceptance, making
      the speedup (or its absence) attributable to the workload, not the
      engine.
    """
    import numpy as np

    from polyrl_tpu.rollout.sampling import SamplingParams

    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(batch)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=new_tokens,
                        stop_token_ids=())
    res: dict = {"spec_tokens": spec_tokens, "temperature": 0.0}
    cont_prompts: list | None = None
    cont_sp = SamplingParams(temperature=0.0, max_new_tokens=new_tokens // 2,
                             stop_token_ids=())
    for label, st in (("off", 0), ("on", spec_tokens)):
        # two buckets: random prompts (prompt_len) must not pad into the
        # longer continuation bucket or the adversarial baseline carries
        # 2x prefill FLOPs
        engine = make_cb_engine(
            cfg, params, prompt_len, new_tokens, max_slots=batch,
            spec_tokens=st,
            prompt_buckets=(prompt_len, prompt_len + new_tokens // 2))
        try:
            warmup_cb(engine, cfg, rng, prompt_len)  # greedy uses no-filter
            warm = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                    for _ in range(2)]
            engine.generate(warm, SamplingParams(
                temperature=0.0, max_new_tokens=8, stop_token_ids=()),
                timeout=600.0)  # end-to-end sanity before timing
            engine.flush_prefix_cache()
            # acceptance telemetry must reflect the TIMED run only
            engine.spec_emitted = engine.spec_dispatches = 0
            t0 = time.monotonic()
            outs = engine.generate(prompts, sp, timeout=1800.0)
            dt = time.monotonic() - t0
            engine.flush_prefix_cache()
            if cont_prompts is None:  # off run: build the continuation set
                cont_prompts = [
                    p + o["token_ids"][: new_tokens // 2]
                    for p, o in zip(prompts, outs)]
            engine.spec_emitted = engine.spec_dispatches = 0
            t0c = time.monotonic()
            outs_c = engine.generate(cont_prompts, cont_sp, timeout=1800.0)
            dtc = time.monotonic() - t0c
            total = sum(len(o["token_ids"]) for o in outs)
            total_c = sum(len(o["token_ids"]) for o in outs_c)
            res[label] = {
                "random": {"tok_s": round(total / dt, 1),
                           "wall_s": round(dt, 2)},
                "continuation": {"tok_s": round(total_c / dtc, 1),
                                 "wall_s": round(dtc, 2)},
            }
            if st:
                tpd = engine.spec_emitted / max(engine.spec_dispatches, 1)
                res[label]["continuation"]["tok_per_dispatch"] = round(tpd, 2)
        finally:
            engine.stop()
            del engine
            gc.collect()
    for wl in ("random", "continuation"):
        off = res.get("off", {}).get(wl, {}).get("tok_s")
        if off:
            res[f"speedup_{wl}"] = round(
                res["on"][wl]["tok_s"] / off, 3)
    return res


def bench_weight_sync(params):
    """Full-flagship STREAMED weight sync over the real fabric: pack ‖
    localhost TCP (multi-stream, watermark-gated) ‖ per-tensor device
    install, then the engine hot-swap. Reference KPI
    sender_agent.py:628-630; north star <5 s (BASELINE.md)."""
    import jax

    from polyrl_tpu.transfer import (
        ReceiverAgent, SenderAgent, build_layout, unflatten_like,
    )
    from polyrl_tpu.transfer.layout import alloc_buffer

    layout = build_layout(params)
    buf = alloc_buffer(layout)
    sender = SenderAgent(buf, manager_client=None, listen_host="127.0.0.1",
                         num_streams=8, poll_s=0.05, advertise_host="127.0.0.1")
    sender.start()
    rx = ReceiverAgent(layout, "bench-inst", sender.endpoint, num_streams=8,
                       listen_host="127.0.0.1", advertise_host="127.0.0.1")
    rx.start()
    try:
        import threading as _threading

        from polyrl_tpu.transfer.layout import (
            make_incremental_installer, pack_params_streaming,
        )
        from polyrl_tpu.transfer.tcp_engine import Watermark

        time.sleep(0.5)  # registration handshake
        # STREAMED round (the production path): version first, then pack
        # in place while gated sender streams trail the watermark and the
        # receiver device_puts each tensor as its bytes land — pack (D2H),
        # wire (TCP), and install (H2D) overlap inside the one round.
        t0 = time.monotonic()
        wm = Watermark(layout.total_bytes)
        v = sender.signal_update_streaming(wm)
        # the SAME installer the rollout server's streaming path uses
        _install, device_named = make_incremental_installer(params)
        waiter_exc: list = []

        def _wait() -> None:
            try:
                rx.wait_for_version(v, timeout=2400.0, on_tensor=_install)
            except Exception as exc:  # noqa: BLE001 — re-raised below
                waiter_exc.append(exc)

        waiter = _threading.Thread(target=_wait, daemon=True)
        waiter.start()
        try:
            pack_params_streaming(params, layout, buf, wm.advance)
        except BaseException as exc:
            wm.fail(str(exc))
            raise
        wm.finish()
        t_pack = time.monotonic()
        waiter.join(timeout=2400.0)
        if waiter.is_alive():
            raise TimeoutError("streamed receive still running at 2400s")
        if waiter_exc:
            raise waiter_exc[0]
        t_wire = time.monotonic()
        swapped = unflatten_like(params, device_named)  # engine hot-swap
        jax.block_until_ready(swapped)
        t1 = time.monotonic()
        # int8 workers (WEIGHT_QUANT=int8) re-quantize every bf16 push on
        # arrival (serve.py wires quantize_params as weight_preprocess) —
        # record that extra install cost for the 8B int8 deployment math.
        # Quantize the DEVICE-resident tree (a host tree would re-pay H2D
        # and time the upload, not the kernel);
        # first call compiles (one-time per worker), the per-push cost is
        # the second, compiled call.
        from polyrl_tpu.models.quant import quantize_params

        quant_fn = jax.jit(quantize_params)
        jax.block_until_ready(jax.tree_util.tree_leaves(quant_fn(swapped)))
        t1b = time.monotonic()
        quantized = quant_fn(swapped)
        jax.block_until_ready(jax.tree_util.tree_leaves(quantized))
        t_quant = time.monotonic()
        del quantized, swapped
        gc.collect()
        mb = layout.total_bytes / (1 << 20)
        return {
            "mode": "streamed",  # pack || wire || per-tensor device_put
            "total_s": round(t1 - t0, 3),
            "pack_s": round(t_pack - t0, 3),
            # wire+install run CONCURRENTLY with the pack; the tail is what
            # they still needed after the last byte was packed
            "wire_install_tail_s": round(t_wire - t_pack, 3),
            "assemble_s": round(t1 - t_wire, 3),
            "int8_requantize_s": round(t_quant - t1b, 3),
            "mb": round(mb, 1),
            "eff_mb_s": round(mb / max(t1 - t0, 1e-9), 1),
            # the streamed round makes total ~= max(leg) + tail instead
            # of the legs' sum
        }
    finally:
        rx.stop()
        sender.stop()


def bench_8b_int8(cfg, batch=None, prompt_len=128, new_tokens=128):
    """8B decode on ONE chip via int8 weight-only quantization
    (models/quant.py): matmul weights int8 + bf16 embed ≈ 8.6 GiB, fits a
    16 GiB chip. Measured on the production CB paged serving engine. The
    bf16 8B tree never materializes — params are random-initialized
    directly in quantized form leaf-by-leaf on device.

    ``batch`` (POLYRL_BENCH_8B_BATCH): decode slots = tokens amortizing
    each full weight read; ~8.6 GiB weights + ~34 MB KV/slot at 256 seq
    leaves room for 128 slots in 15.75 GiB HBM (~2.5 GiB headroom). Decode
    is weight-read bound, so width ~doubles tok/s — but the margin is
    unproven per chip generation, so an OOM at the wide setting falls
    back to 64 IN-phase rather than burning the phase's fresh-process
    retries on a deterministic failure."""
    if batch is None:
        env = os.environ.get("POLYRL_BENCH_8B_BATCH", "")
        candidates = [int(env)] if env else [128, 64]
        for b in candidates[:-1]:
            try:
                return bench_8b_int8(cfg, batch=b, prompt_len=prompt_len,
                                     new_tokens=new_tokens)
            except Exception as exc:  # noqa: BLE001 — classify below
                msg = str(exc)
                if not ("RESOURCE_EXHAUSTED" in msg or "OOM" in msg
                        or "out of memory" in msg.lower()):
                    raise  # only a deterministic OOM warrants the retry
                _note("8b_int8", {"batch": b, "error": msg[:200],
                                  "retrying_narrower": True})
            # AFTER the except block: the handled exception's traceback
            # frames (pinning the failed attempt's ~8.6 GiB of device
            # params) are only released once the block exits
            gc.collect()
        return bench_8b_int8(cfg, batch=candidates[-1],
                             prompt_len=prompt_len, new_tokens=new_tokens)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.models.quant import init_quantized_params
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    params = init_quantized_params(jax.random.PRNGKey(0), cfg)
    jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
    page_size = 64
    max_seq = -(-(prompt_len + new_tokens) // page_size) * page_size
    pages_per = max_seq // page_size
    engine = CBEngine(
        cfg, params, pad_token_id=0, kv_cache_dtype=jnp.bfloat16,
        max_slots=batch, page_size=page_size, max_seq_len=max_seq,
        prompt_buckets=(prompt_len,), steps_per_dispatch=8,
        num_pages=batch * pages_per + 8)
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                   for _ in range(batch)]
        sp = SamplingParams(temperature=1.0, max_new_tokens=new_tokens,
                            stop_token_ids=())
        engine.warmup(filter_variants=(False,))  # temp-only sampling below
        warm = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                for _ in range(2)]
        warm_sp = SamplingParams(temperature=1.0, max_new_tokens=8,
                                 stop_token_ids=())
        engine.generate(warm, warm_sp, timeout=1200.0)
        engine.flush_prefix_cache()
        t0 = time.monotonic()
        outs = engine.generate(prompts, sp, timeout=2400.0)
        dt = time.monotonic() - t0
        total = sum(len(o["token_ids"]) for o in outs)
        return {"ran": True, "quant": "int8", "engine": "cb",
                "tok_s": round(total / dt, 1), "batch": batch,
                "wall_s": round(dt, 2)}
    finally:
        engine.stop()
        del engine, params
        gc.collect()


def bench_8b(preset: str):
    """8B-class decode evidence, HBM-gated: bf16 8B params need ~16.1 GB, so
    a 16 GB-HBM chip cannot hold params + KV + workspace single-chip (the
    north star shards over v5e-64) — in that case run the int8
    weight-only-quantized CB engine instead and record the real number."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.engine import RolloutEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg = decoder.get_config(preset, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    param_count = sum(int(np.prod(l.shape))
                      for l in jax.tree_util.tree_leaves(shapes))
    hbm_gb = _hbm_limit_gb()
    # bf16 param bytes + decode KV for a tiny batch + logits workspace
    batch, prompt_len, new_tokens = 8, 128, 64
    kv_per_tok = cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim_ * 2
    need_gb = (param_count * 2
               + batch * (prompt_len + new_tokens) * kv_per_tok
               + cfg.vocab_size * cfg.hidden_size * 2) / (1 << 30)
    if hbm_gb and need_gb > hbm_gb * 0.92:
        out = bench_8b_int8(cfg)
        out["bf16_skipped"] = (f"bf16 needs ~{need_gb:.1f} GiB > "
                               f"{hbm_gb:.1f} GiB HBM")
        return out
    engine = params = None
    oom_note = None
    try:
        params = jax.jit(lambda: decoder.init_params(jax.random.PRNGKey(0),
                                                     cfg))()
        jax.block_until_ready(params)
        engine = RolloutEngine(cfg, params, pad_token_id=0,
                               batch_buckets=(batch,),
                               prompt_buckets=(prompt_len,),
                               kv_cache_dtype=jnp.bfloat16)
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                   for _ in range(batch)]
        sp = SamplingParams(temperature=1.0, max_new_tokens=new_tokens,
                            stop_token_ids=())
        engine.generate(prompts, sp, rng=jax.random.PRNGKey(0))
        t0 = time.monotonic()
        outs = engine.generate(prompts, sp, rng=jax.random.PRNGKey(1))
        dt = time.monotonic() - t0
        total = sum(o.completion_tokens for o in outs)
        del engine, params
        gc.collect()
        return {"ran": True, "tok_s": round(total / dt, 1),
                "batch": batch, "hbm_gb": round(hbm_gb, 1)}
    except Exception as exc:  # noqa: BLE001 — device OOM IS the measurement
        msg = str(exc)
        # TPU OOM surfaces as RESOURCE_EXHAUSTED (allocation-time) or an
        # "Out of memory"/hbm message (compile-time); both mean bf16 no-fit
        if ("memory" not in msg.lower()
                and "resource_exhausted" not in msg.lower()
                and "resourceexhausted" not in msg.lower()):
            raise
        import re

        m = re.search(r"Used ([0-9.]+)G of ([0-9.]+)G hbm", msg)
        used, limit = (m.group(1), m.group(2)) if m else ("?", "?")
        oom_note = (f"bf16 decode OOM: needs {used} GiB, chip "
                    f"HBM {limit} GiB")
        # the int8 fallback must run OUTSIDE this handler: exc.__traceback__
        # pins the engine/params frames (≈16 GiB of device buffers) until
        # the except block exits, and the int8 init needs that HBM back
    # where memory_stats() reports no limit (hbm_gb=0 skips the pre-gate)
    # the OOM above is the bf16 fit result — fall back to the int8
    # quantized engine for a real number
    engine = params = None  # noqa: F841 — drop device buffer refs
    gc.collect()
    out = bench_8b_int8(cfg)
    out["bf16_skipped"] = oom_note
    return out


class FakeAsyncRollout:
    """Engine-shaped stub for the bounded-staleness A/B (``--async-sweep``;
    also driven by tests/test_async_pipeline.py): deterministic tokens
    produced token-by-token over ``gen_delay_s``, each stamped with the
    version INSTALLED at its sample time; ``update_weights_async`` installs
    on a background timer (``push_delay_s``) and exposes the same
    ``push_lag``/``wait_push_lag`` admission-gate surface as the transfer
    fabric — so a push issued mid-generation lands mid-stream and the
    sequence legitimately spans weight versions, exactly like the real
    verify-before-install fabric at ``staleness_limit > 1``."""

    def __init__(self, gen_delay_s: float, push_delay_s: float):
        self.pad_token_id = 0
        self.weight_version = 0       # issued inline (trainer-visible)
        self.installed_version = 0    # what generation samples against
        self.last_gen_throughput = 0.0
        self.gen_delay_s = gen_delay_s
        self.push_delay_s = push_delay_s
        self.mixed_version_batches = 0
        self.gen_during_push = 0      # generations observed mid-push
        self._cv = threading.Condition()
        self._issued = 0
        self._landed = 0

    def generate(self, prompts, sampling, rng=None, **kw):
        n_new = max(sampling.max_new_tokens, 1)
        per_tok = self.gen_delay_s / n_new
        outs = [{"token_ids": [], "logprobs": [], "weight_versions": []}
                for _ in prompts]
        t0 = time.monotonic()
        during_push = False
        for i in range(sampling.max_new_tokens):
            time.sleep(per_tok)
            during_push = during_push or self.push_lag() > 0
            v = self.installed_version
            for j, p in enumerate(prompts):
                outs[j]["token_ids"].append(1 + (len(p) + i) % 200)
                outs[j]["logprobs"].append(-0.5)
                outs[j]["weight_versions"].append(v)
        if during_push:
            self.gen_during_push += 1
        if outs and len(set(outs[0]["weight_versions"])) > 1:
            self.mixed_version_batches += 1
        dt = time.monotonic() - t0
        if dt > 0:
            self.last_gen_throughput = (
                len(prompts) * sampling.max_new_tokens / dt)
        return outs

    def update_weights(self, params, version=None):
        time.sleep(self.push_delay_s)
        self.weight_version += 1
        self.installed_version = self.weight_version

    def update_weights_async(self, params, version=None):
        self.weight_version += 1
        v = self.weight_version
        with self._cv:
            self._issued += 1

        def _land() -> None:
            time.sleep(self.push_delay_s)
            with self._cv:
                self.installed_version = max(self.installed_version, v)
                self._landed += 1
                self._cv.notify_all()

        threading.Thread(target=_land, name="weight-push",
                         daemon=True).start()
        return v

    def push_lag(self) -> int:
        with self._cv:
            return self._issued - self._landed

    def wait_push_lag(self, max_lag: int, timeout: float = 60.0) -> None:
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._issued - self._landed > max_lag:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError("fake push-lag gate timed out")
                self._cv.wait(remaining)

    def wait_pushed(self, timeout: float = 60.0) -> None:
        self.wait_push_lag(0, timeout)


def _microbench_fit(rollout, steps: int, depth: int,
                    staleness_limit: int = 1,
                    correction: bool | None = None,
                    traced: bool = False) -> tuple[float, list]:
    """One tiny CPU fit for the pipeline/async microbenches: the shared
    trainer geometry behind ``--pipeline-microbench`` and
    ``--async-sweep`` (and their tests). ``traced=True`` runs the fit
    under the span tracer so the step records carry the ``critpath/*``
    critical-path gauges (obs/critical_path.py)."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu import obs
    from polyrl_tpu.data.dataset import PromptDataLoader, make_arithmetic_dataset
    from polyrl_tpu.models import decoder
    from polyrl_tpu.rewards.manager import load_reward_manager
    from polyrl_tpu.trainer.actor import ActorConfig, StreamActor
    from polyrl_tpu.trainer.stream_trainer import StreamRLTrainer, TrainerConfig
    from polyrl_tpu.utils.tokenizer import ByteTokenizer

    mcfg = decoder.get_config("tiny", dtype=jnp.float32, vocab_size=512,
                              max_position_embeddings=128)
    params = decoder.init_params(jax.random.PRNGKey(0), mcfg)
    tok = ByteTokenizer()
    tcfg = TrainerConfig(
        train_batch_size=4, rollout_n=2, ppo_mini_batch_size=8,
        micro_batch_size=4, min_stream_batch_size=4,
        max_prompt_length=16, max_response_length=8,
        adv_estimator="grpo", total_steps=steps,
        pipeline_depth=depth, staleness_limit=staleness_limit,
        rollout_is_correction=(depth > 0 if correction is None
                               else correction))
    actor = StreamActor(mcfg, ActorConfig(lr=1e-4, remat=False), params)
    trainer = StreamRLTrainer(
        tcfg, actor, rollout, tok,
        load_reward_manager("naive", tok, num_workers=1),
        PromptDataLoader(make_arithmetic_dataset(64), 4))
    if traced:
        obs.configure(trace=True, max_spans=4096, reset=True)
    try:
        t0 = time.monotonic()
        hist = trainer.fit()
        return time.monotonic() - t0, hist
    finally:
        if traced:
            obs.configure(trace=False, reset=True)


def _hist_tail_mean(hist: list, key: str, tail: slice = slice(1, None)):
    vals = [h[key] for h in hist[tail] if key in h]
    return round(sum(vals) / len(vals), 5) if vals else None


def async_sweep_bench(steps: int = 6, gen_delay_s: float = 0.25,
                      push_delay_s: float = 0.25,
                      depths: tuple = (0, 1, 2, 4)) -> dict:
    """Bounded-staleness async A/B (``python bench.py --async-sweep``; also
    driven by tests/test_async_pipeline.py): the tiny CPU trainer swept
    over pipeline depth {0,1,2,4} with ``staleness_limit = depth`` (>=1) on
    a :class:`FakeAsyncRollout` whose pushes install on a background timer.
    Depth 0 is the serial loop, depth 1 the fenced PR-3 pipeline (the
    ``wait_pushed()`` hard fence — gen and push walls serialize on the
    prefetch lane), depth k>1 the bounded-staleness admission gate with
    mixed-version per-token TIS — the push wall disappears behind
    generation, which is the whole point. Emits the flat ``async_*``
    extras bench_gate watches (speedup + tok/s hold, ``training/staleness``
    p95 bounded, entropy/KL in their PR 9 directions)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    tail = slice(1, None)
    rows: dict[int, dict] = {}
    hists: dict[int, list] = {}
    for depth in depths:
        rollout = FakeAsyncRollout(gen_delay_s, push_delay_s)
        wall, hist = _microbench_fit(rollout, steps, depth,
                                     staleness_limit=max(depth, 1))
        step_s = sum(h["perf/step_time_s"] for h in hist[tail]) / max(
            len(hist[tail]), 1)
        rows[depth] = {
            "depth": depth, "staleness_limit": max(depth, 1),
            "wall_s": round(wall, 2), "step_s": round(step_s, 3),
            "overlap_s_total": round(sum(
                h.get("perf/pipeline_overlap_s", 0.0) for h in hist), 3),
            "gate_wait_s": _hist_tail_mean(hist,
                                           "perf/staleness_gate_wait_s"),
            "staleness_p95": _hist_tail_mean(hist, "training/staleness/p95"),
            "staleness_max": max(h.get("training/staleness_max", 0.0)
                                 for h in hist),
            "mixed_version_batches": rollout.mixed_version_batches,
            "gen_during_push": rollout.gen_during_push,
            "tok_s": _hist_tail_mean(hist, "perf/throughput_tokens_per_s"),
        }
        hists[depth] = hist
    fenced = rows.get(1) or rows[min(d for d in rows if d > 0)]
    async_depths = [d for d in rows if d > 1]
    best_d = (min(async_depths, key=lambda d: rows[d]["step_s"])
              if async_depths else fenced["depth"])
    best = rows[best_d]
    out = {
        "steps": steps, "gen_delay_s": gen_delay_s,
        "push_delay_s": push_delay_s,
        "sweep": {f"d{d}": rows[d] for d in sorted(rows)},
        "async_best_depth": best_d,
        # fenced depth-1 vs best bounded-staleness depth: the win from
        # letting the push wall hide behind generation
        "async_step_speedup": round(
            fenced["step_s"] / max(best["step_s"], 1e-9), 3),
        "async_tok_s": best["tok_s"],
        "async_staleness_p95": best["staleness_p95"],
        "async_staleness_max": best["staleness_max"],
        "async_mixed_version_batches": best["mixed_version_batches"],
    }
    for k in ("entropy", "approx_kl", "tis_clip_frac"):
        v = _hist_tail_mean(hists[best_d], f"training/{k}")
        if v is not None:
            out[f"async_training_{k}"] = v
    return out


def pipeline_microbench(steps: int = 4, gen_delay_s: float = 0.4,
                        push_delay_s: float = 0.15) -> dict:
    """Pipelined-vs-sync A/B on a CPU fake engine (``python bench.py
    --pipeline-microbench``; also driven by tests/test_pipeline_overlap.py).

    The fake rollout sleeps a fixed ``gen_delay_s`` per generation and
    ``push_delay_s`` per weight push — wall time independent of trainer
    compute — so the delta between ``pipeline_depth=0`` and ``=1`` isolates
    exactly the overlap the RolloutPipeline buys (generation hidden behind
    the previous step's update + the async push hidden behind bookkeeping).
    Runs on CPU, never dials the TPU, and prints one JSON line."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    class FakeSlowRollout:
        """Engine-shaped stub: deterministic tokens after a fixed delay,
        plus the async-push surface the pipelined trainer fences on."""

        def __init__(self, delay_s: float, push_s: float):
            self.pad_token_id = 0
            self.weight_version = 0
            self.last_gen_throughput = 0.0
            self.delay_s = delay_s
            self.push_s = push_s
            self._push_thread: threading.Thread | None = None

        def generate(self, prompts, sampling, rng=None, **kw):
            time.sleep(self.delay_s)
            return [{"token_ids": [1 + (len(p) + i) % 200
                                   for i in range(sampling.max_new_tokens)],
                     "logprobs": [-0.5] * sampling.max_new_tokens}
                    for p in prompts]

        def update_weights(self, params, version=None):
            time.sleep(self.push_s)
            self.weight_version += 1

        def update_weights_async(self, params, version=None):
            self.wait_pushed()
            self.weight_version += 1
            self._push_thread = threading.Thread(
                target=time.sleep, args=(self.push_s,), name="weight-push",
                daemon=True)
            self._push_thread.start()
            return self.weight_version

        def wait_pushed(self, timeout=None):
            t, self._push_thread = self._push_thread, None
            if t is not None:
                t.join(timeout)

    def run(depth: int) -> tuple[float, list]:
        # the pipelined leg runs traced so its records carry the
        # critical-path attribution promoted below (the serial leg stays
        # untraced: its wall is the A/B baseline, keep it untouched)
        return _microbench_fit(FakeSlowRollout(gen_delay_s, push_delay_s),
                               steps, depth, traced=depth > 0)

    wall_sync, hist_sync = run(0)
    wall_pipe, hist_pipe = run(1)
    # per-step means over steps >= 2 (step 1 carries jit compiles, step 2
    # the pipelined run's cold prefetch ramp)
    tail = slice(1, None)
    sync_step = sum(h["perf/step_time_s"] for h in hist_sync[tail]) / max(
        len(hist_sync[tail]), 1)
    pipe_step = sum(h["perf/step_time_s"] for h in hist_pipe[tail]) / max(
        len(hist_pipe[tail]), 1)
    overlap = sum(h.get("perf/pipeline_overlap_s", 0.0) for h in hist_pipe)

    def _tail_mean(hist, key):
        vals = [h[key] for h in hist if key in h]
        return round(sum(vals) / len(vals), 5) if vals else None

    # training health plane extras (obs/rlhealth.py gauges from the fit's
    # step records): watched by bench_gate across rounds — an entropy
    # collapse or a degenerate-group surge between rounds is a regression
    # even when tok/s held
    training = {
        f"training_{k}": _tail_mean(hist_pipe[tail], f"training/{k}")
        for k in ("entropy", "approx_kl", "tis_clip_frac",
                  "degenerate_group_frac")}
    # critical-path plane extras (obs/critical_path.py, traced pipelined
    # leg): bottleneck concentration rising, or the wall a 10% bottleneck
    # speedup would buy growing, flags an overlap regression bench_gate
    # watches across rounds even when tok/s held
    critpath = {
        f"critpath_{k}": _tail_mean(hist_pipe[tail], f"critpath/{k}")
        for k in ("bottleneck_frac", "headroom_s")}
    return {
        **{k: v for k, v in training.items() if v is not None},
        **{k: v for k, v in critpath.items() if v is not None},
        "steps": steps, "gen_delay_s": gen_delay_s,
        "push_delay_s": push_delay_s,
        "sync_wall_s": round(wall_sync, 2),
        "pipelined_wall_s": round(wall_pipe, 2),
        "sync_step_s": round(sync_step, 3),
        "pipelined_step_s": round(pipe_step, 3),
        "step_speedup": round(sync_step / max(pipe_step, 1e-9), 3),
        "overlap_s_total": round(overlap, 3),
        "staleness_max": max(h.get("perf/weight_staleness", 0.0)
                             for h in hist_pipe),
    }


def chaos_bench(preset: str = "tiny", batch: int = 8, prompt_len: int = 24,
                new_tokens: int = 48, drain_after: int = 2,
                stream_kills: int = 1) -> dict:
    """Fault-injected recovery drill (``python bench.py --chaos``): two CB
    engines behind a real C++ manager; a FaultInjector /drains engine A
    mid-batch (graceful preemption → abort partials → eviction → manager
    continuation resumes every request on B from its last token) and kills
    the trainer-side stream once at the worst moment (every pending rid has
    progress → the salvage ledger re-issues only suffixes). Reports the
    salvage counters from all three tiers plus completion integrity. Runs
    on whatever backend JAX_PLATFORMS selects (CPU-sized by default)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.manager.client import ManagerClient, spawn_rollout_manager
    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.faults import FaultInjectionConfig, FaultInjector
    from polyrl_tpu.rollout.remote import RemoteRollout
    from polyrl_tpu.rollout.sampling import SamplingParams
    from polyrl_tpu.rollout.server import RolloutServer

    cfg = decoder.get_config(preset, dtype=jnp.float32 if preset == "tiny"
                             else jnp.bfloat16)
    params = jax.jit(lambda: decoder.init_params(jax.random.PRNGKey(0),
                                                 cfg))()
    injector = FaultInjector(FaultInjectionConfig(
        enabled=True, drain_after_requests=drain_after,
        stream_kill_times=stream_kills, stream_kill_min_progress=1))

    def mk_server(fault):
        eng = CBEngine(cfg, params, max_slots=batch, page_size=8,
                       max_seq_len=512, prompt_buckets=(32, 64),
                       num_pages=batch * 16, steps_per_dispatch=4)
        srv = RolloutServer(eng, host="127.0.0.1", port=0)
        srv.fault = fault
        return srv.start()

    srv_a, srv_b = mk_server(injector), mk_server(None)
    proc, port = spawn_rollout_manager(
        "127.0.0.1:0", extra_args=["--health-check-interval-s", "0.1",
                                   "--stats-poll-interval-s", "0.2",
                                   "--schedule-wait-timeout-ms", "10000"])
    mgr = ManagerClient(f"127.0.0.1:{port}")
    try:
        mgr.wait_healthy()
        for srv in (srv_a, srv_b):
            mgr.register_rollout_instance(srv.endpoint)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 15:
            st = mgr.get_instances_status()
            if sum(i["healthy"] for i in st["instances"]) >= 2:
                break
            time.sleep(0.1)
        rr = RemoteRollout(mgr, fault_injector=injector)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                   for _ in range(batch)]
        sp = SamplingParams(temperature=1.0, max_new_tokens=new_tokens,
                            stop_token_ids=())
        t0 = time.monotonic()
        done = sum(len(chunk) for chunk in rr.generate_stream(
            prompts, sp, group_size=2, min_emit=2))
        wall = time.monotonic() - t0
        salvaged = (rr.tokens_salvaged + srv_a.engine.tokens_salvaged
                    + srv_b.engine.tokens_salvaged)
        return {
            "completed": done, "batch": batch,
            "dropped_groups": rr.dropped_groups,
            "wall_s": round(wall, 2),
            "tok_s": round(done * new_tokens / wall, 1) if wall > 0 else 0.0,
            "tokens_salvaged_total": salvaged,
            "client": {k: v for k, v in rr.fault_counters().items()},
            "engine_a": {
                "tokens_salvaged": srv_a.engine.tokens_salvaged,
                "salvage_published_pages":
                    srv_a.engine.salvage_published_pages,
                "drained_requests": srv_a.drain_count,
            },
            "injected": injector.counters(),
        }
    finally:
        proc.kill()
        for srv in (srv_a, srv_b):
            try:
                srv.stop()
            except Exception:  # noqa: BLE001 — A may already be shut down
                pass


def pool_bench(n_engines: int = 2, preset: str = "tiny", batch: int = 8,
               prompt_len: int = 24, new_tokens: int = 48, rounds: int = 2,
               endpoints: tuple = (), spot_trace: str = "") -> dict:
    """Elastic-pool topology bench (``python bench.py --pool N``): N CB
    engines behind one C++ manager + PoolManager. Phase 1 runs ``rounds``
    steady-state generation batches and measures aggregate + per-engine
    tok/s (queue-depth-aware routing should keep the per-engine spread
    tight). Phase 2 is the scale-down/scale-up drill: engine 0 is
    preempted (drain → salvage → graceful leave) MID-BATCH, the batch must
    finish on survivors with zero dropped groups, a replacement joins, and
    ``recovery_s`` is the wall until the pool is back at N.

    Phase 3 (``--spot-trace FILE``, local pools only): replay a scripted
    spot-market schedule (rollout/spotmarket.py JSONL: offers, preemption
    notices, no-notice kills; live engines adopted as ``E0..En-1``) while
    batches keep flowing — the bench plays the controller's role, adding
    offered capacity as it appears. ``spot.completed_frac`` is the share
    of storm-window requests that completed; ``spot.recovery_s`` the wall
    from the first disruption to the pool back at target size.

    CPU-sized by default (the same CB engines the quick tier drives; set
    JAX_PLATFORMS/POLYRL_BENCH_PRESET to scale up). ``--pool-endpoints
    ep1,ep2`` benches REAL engines already serving (TPU hosts) instead of
    building local ones — the preemption drill is skipped there (don't
    preempt engines this process doesn't own), reported as
    ``pool_drill_skipped=1`` so bench_gate never mistakes a skipped drill
    for a passed one; steady-state per-engine tok/s still reports."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.manager.client import ManagerClient, spawn_rollout_manager
    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.pool import PoolConfig, PoolManager
    from polyrl_tpu.rollout.remote import RemoteRollout
    from polyrl_tpu.rollout.sampling import SamplingParams
    from polyrl_tpu.rollout.server import RolloutServer

    cfg = decoder.get_config(preset, dtype=jnp.float32 if preset == "tiny"
                             else jnp.bfloat16)
    params = (None if endpoints else
              jax.jit(lambda: decoder.init_params(jax.random.PRNGKey(0),
                                                  cfg))())

    def mk_server():
        eng = CBEngine(cfg, params, max_slots=batch, page_size=8,
                       max_seq_len=512, prompt_buckets=(32, 64),
                       num_pages=batch * 16, steps_per_dispatch=4)
        return RolloutServer(eng, host="127.0.0.1", port=0).start()

    def tokens_served(ep: str) -> float:
        try:
            with urllib.request.urlopen(f"http://{ep}/statusz",
                                        timeout=3.0) as r:
                snap = json.loads(r.read())
            return float(snap.get("counters", {}).get(
                "total_tokens_served", 0.0))
        except Exception:  # noqa: BLE001 — dead/fake engines count 0
            return 0.0

    servers = [] if endpoints else [mk_server() for _ in range(n_engines)]
    eps = list(endpoints) or [s.endpoint for s in servers]
    proc, port = spawn_rollout_manager(
        "127.0.0.1:0", extra_args=["--health-check-interval-s", "0.1",
                                   "--stats-poll-interval-s", "0.2",
                                   "--heartbeat-failures", "3",
                                   "--schedule-wait-timeout-ms", "10000"])
    mgr = ManagerClient(f"127.0.0.1:{port}")
    pool = PoolManager(mgr, PoolConfig(drain_grace_s=0.2))
    replacement = None
    market = None
    try:
        mgr.wait_healthy()
        for ep in eps:
            mgr.register_rollout_instance(ep)
        pool.wait_for_size(len(eps), deadline_s=60.0)
        rr = RemoteRollout(mgr)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                   for _ in range(batch)]
        sp = SamplingParams(temperature=1.0, max_new_tokens=new_tokens,
                            stop_token_ids=())

        def run_batch() -> int:
            return sum(len(chunk) for chunk in rr.generate_stream(
                prompts, sp, group_size=2, min_emit=2))

        # phase 1: steady state — aggregate + per-engine throughput
        served0 = {ep: tokens_served(ep) for ep in eps}
        t0 = time.monotonic()
        completed = sum(run_batch() for _ in range(rounds))
        steady_s = time.monotonic() - t0
        engine_tok_s = {
            ep: round((tokens_served(ep) - served0[ep]) / steady_s, 1)
            for ep in eps}
        tok_s = round(completed * new_tokens / steady_s, 1) if steady_s \
            else 0.0

        # phase 2: preemption drill + replacement join (local pools only)
        recovery_s = None
        drill_completed = 0
        if not endpoints:
            drill_t0 = time.monotonic()
            timer = threading.Timer(
                min(0.2, steady_s / max(rounds, 1) / 4),
                lambda: pool.preempt(eps[0]))
            timer.start()
            try:
                drill_completed = run_batch()
            finally:
                timer.cancel()
            replacement = mk_server()
            pool.add_engine(endpoint=replacement.endpoint, wait=False)
            pool.wait_for_size(len(eps), deadline_s=60.0)
            recovery_s = round(time.monotonic() - drill_t0, 2)

        # phase 3: spot-market storm (local pools only) — scripted offers/
        # notices/kills replayed while batches keep flowing; the bench
        # plays the AutoscaleController's role on offered capacity
        spot = None
        if spot_trace and not endpoints:
            from polyrl_tpu.rollout.spotmarket import (SpotMarket,
                                                       SpotMarketConfig,
                                                       load_trace)

            market = SpotMarket(
                pool, SpotMarketConfig(enabled=True, grace_s=0.2),
                engine_factory=mk_server, events=load_trace(spot_trace))
            live_eps = {e["endpoint"] for e in pool.engines(refresh=True)}
            live = servers + ([replacement] if replacement else [])
            for i, srv in enumerate(s for s in live
                                    if s.endpoint in live_eps):
                market.adopt(f"E{i}", srv)
            target = pool.active_count(refresh=True)
            market.start()
            storm_submitted = storm_completed = 0
            while not market.done.is_set():
                storm_submitted += batch
                storm_completed += run_batch()
                while True:   # controller stand-in: add offered capacity
                    offered = market.acquire()
                    if offered is None:
                        break
                    pool.add_engine(endpoint=offered, wait=False)
            pool.wait_for_size(target, deadline_s=120.0)
            spot_recovery = (
                round(time.monotonic() - market.first_disruption_t, 2)
                if market.first_disruption_t is not None else 0.0)
            spot = {
                "completed_frac": round(
                    storm_completed / storm_submitted, 3)
                if storm_submitted else 1.0,
                "recovery_s": spot_recovery,
                "submitted": storm_submitted,
                "completed": storm_completed,
                "offers": market.offers,
                "notices": market.notices,
                "kills": market.kills,
            }

        counters = pool.counters()
        out = {
            "pool_engines": len(eps),
            "pool_evictions": int(counters["pool/evictions"]),
            "pool_drain_departures": int(counters["pool/drain_departures"]),
            "pool_joins": int(counters["pool/joins"]),
            "engine_tok_s": engine_tok_s,
            "tok_s": tok_s,
            "completed": completed,
            "drill_completed": drill_completed,
            "dropped_groups": rr.dropped_groups,
            "recovery_s": recovery_s,
            # real endpoints are never preempted — flag the skipped drill
            # so bench_gate can tell "skipped" from "passed"
            "pool_drill_skipped": 1 if endpoints else 0,
            "steady_s": round(steady_s, 2),
        }
        if spot is not None:
            out["spot"] = spot
        return out
    finally:
        proc.kill()
        pool.close()
        if market is not None:
            market.stop()   # also stops the engines its offers built
        for srv in servers + ([replacement] if replacement else []):
            try:
                srv.stop()
            except Exception:  # noqa: BLE001 — preempted one already down
                pass


def push_chaos_bench(buffer_mb: float = 2.0, streams: int = 2,
                     stall_s: float = 3.0) -> dict:
    """Weight-fabric fault drill (``python bench.py --push-chaos``): one
    sender, two fake-engine receivers over real localhost TCP. Round 1 is
    the clean catch-up baseline. Round 2 runs with injected faults: one
    frame to engine 0 is corrupted on the wire (CRC rejection →
    ``verify_failed`` → partial re-push of exactly that range) and engine
    1's stream stalls past its bandwidth-keyed deadline once (timeout →
    backoff → clean retry). Reports ``transfer_{verify_failures,
    resumed_bytes,recovery_s}`` — watched by tools/bench_gate.py — plus a
    bitwise integrity check of both landed buffers."""
    import numpy as np

    from polyrl_tpu.rollout.faults import (TransferFaultConfig,
                                           TransferFaultInjector)
    from polyrl_tpu.transfer.agents import (ReceiverAgent, SenderAgent,
                                            TransferConfig)
    from polyrl_tpu.transfer.layout import alloc_buffer, build_layout
    from polyrl_tpu.transfer.tcp_engine import STREAM_STRIPE

    rng = np.random.default_rng(0)
    n = max(1, int(buffer_mb * (1 << 20)) // 4 // 4)
    params = {f"w{i}": rng.standard_normal(n).astype(np.float32)
              for i in range(4)}
    layout = build_layout(params)
    total = layout.total_bytes
    # deadline ~= total/bw + slack; the stall must overshoot it so the
    # stalled attempt fails by TIMEOUT, not by verify
    tcfg = TransferConfig(min_bandwidth_mbps=max(buffer_mb, 1.0),
                          deadline_slack_s=1.0, stream_slack_s=1.0,
                          retry_budget=2, backoff_base_s=0.05,
                          backoff_max_s=0.2)
    buf = alloc_buffer(layout)
    sender = SenderAgent(buf, manager_client=None, listen_host="127.0.0.1",
                         num_streams=streams, poll_s=0.05,
                         advertise_host="127.0.0.1", cfg=tcfg)
    injector = None
    rxs = []
    try:
        sender.start()
        rxs = [ReceiverAgent(layout, f"push-chaos-eng-{i}", sender.endpoint,
                             num_streams=streams, listen_host="127.0.0.1",
                             advertise_host="127.0.0.1")
               for i in range(2)]
        for rx in rxs:
            rx.start()
        from polyrl_tpu.transfer.layout import pack_params

        # round 1: clean catch-up push to both engines (baseline)
        with sender.buffer_write_lock():
            pack_params(params, layout, buf)
        t0 = time.monotonic()
        v1 = sender.signal_update()
        for rx in rxs:
            rx.wait_for_version(v1, timeout=120.0)
        clean_push_s = time.monotonic() - t0

        # round 2: corruption on engine 0 + one stalled stream on engine 1
        injector = TransferFaultInjector(TransferFaultConfig(
            enabled=True,
            corrupt_frames=1, corrupt_instance="push-chaos-eng-0",
            stall_s=stall_s, stall_streams=1,
            stall_instance="push-chaos-eng-1"))
        sender.fault = injector
        t0 = time.monotonic()
        v2 = sender.signal_update()
        for rx in rxs:
            rx.wait_for_version(v2, timeout=120.0)
        recovery_s = time.monotonic() - t0

        bitwise_ok = all(bool(np.array_equal(rx.buffer, buf)) for rx in rxs)
        return {
            "transfer_verify_failures": int(sender.verify_failures),
            "transfer_resumed_bytes": int(sender.resumed_bytes),
            "transfer_recovery_s": round(recovery_s, 3),
            "transfer_push_failures": int(sender.push_failures),
            "transfer_push_retries": int(sender.push_retries),
            "transfer_rounds_verified": int(sender.rounds_verified),
            "clean_push_s": round(clean_push_s, 3),
            "total_bytes": int(total),
            "resumed_frac": round(sender.resumed_bytes / total, 4),
            "stream_stripe": int(STREAM_STRIPE),
            "receiver_crc_failures": sum(
                rx.sockets.crc_failures for rx in rxs),
            "receiver_reconnects": sum(
                rx.control_reconnects for rx in rxs),
            "bitwise_ok": bitwise_ok,
            "injected": injector.counters(),
            "engines": len(rxs),
        }
    finally:
        for rx in rxs:
            rx.stop()
        sender.stop()


def push_shard_bench(buffer_mb: float = 8.0, streams: int = 4,
                     rounds: int = 3, tp: int = 2) -> dict:
    """Sharded weight-fabric A/B (``python bench.py --push-shard``): the
    SAME fixed byte total pushed twice over real localhost TCP — once with
    a single stream, once with ``streams`` parallel shard-to-shard streams
    driven by the resharding map against a tp=``tp`` receiver. Each config
    gets a registration warm-up round, then ``rounds`` timed rounds (min
    wall — robust on a noisy shared box). Reports
    ``push_shard.{speedup,bytes_per_stream,stream_resumes}`` — watched by
    tools/bench_gate.py (speedup low-direction) — plus the per-config
    walls, the map's resharded bytes, and a bitwise integrity check."""
    import numpy as np

    from polyrl_tpu.transfer.agents import (ReceiverAgent, SenderAgent,
                                            TransferConfig)
    from polyrl_tpu.transfer.layout import (ShardSpec, alloc_buffer,
                                            build_layout,
                                            build_resharding_map,
                                            pack_params)

    rng = np.random.default_rng(0)
    # fixed total bytes across both configs: four tp-shardable matrices
    # (alternating shard axes, 256 columns — divisible by any sane tp)
    # plus a deliberately misaligned tail vector exercising the POOL path
    rows = max(2 * tp, int(buffer_mb * (1 << 20)) // 4 // 4 // 256)
    rows -= rows % (2 * tp)
    params = {f"w{i}": rng.standard_normal((rows, 256)).astype(np.float32)
              for i in range(4)}
    params["tail"] = rng.standard_normal(257).astype(np.float32)
    engine_spec = ShardSpec(tp, {"w0": 1, "w1": 0, "w2": 1, "w3": 0})
    trainer_spec = ShardSpec(1, {})
    layout = build_layout(params)
    total = layout.total_bytes
    rmap = build_resharding_map(layout, trainer_spec, engine_spec)
    per_stream = [sum(ln for _, ln in ranges)
                  for ranges in rmap.stream_assignments(streams)]
    tcfg = TransferConfig(min_bandwidth_mbps=max(buffer_mb, 1.0),
                          deadline_slack_s=2.0, stream_slack_s=2.0,
                          retry_budget=2, backoff_base_s=0.05,
                          backoff_max_s=0.2)

    def one_config(n_streams: int) -> dict:
        buf = alloc_buffer(layout)
        sender = SenderAgent(buf, manager_client=None,
                             listen_host="127.0.0.1",
                             num_streams=n_streams, poll_s=0.05,
                             advertise_host="127.0.0.1", cfg=tcfg,
                             layout=layout, trainer_spec=trainer_spec)
        rx = None
        try:
            sender.start()
            rx = ReceiverAgent(layout, f"push-shard-s{n_streams}",
                               sender.endpoint, num_streams=n_streams,
                               listen_host="127.0.0.1",
                               advertise_host="127.0.0.1",
                               shard_spec=engine_spec)
            rx.start()
            time.sleep(0.3)  # registration handshake
            with sender.buffer_write_lock():
                pack_params(params, layout, buf)
            v = sender.signal_update()  # warm-up: first-round setup costs
            rx.wait_for_version(v, timeout=120.0)
            walls = []
            for _ in range(rounds):
                t0 = time.monotonic()
                v = sender.signal_update()
                rx.wait_for_version(v, timeout=120.0)
                walls.append(time.monotonic() - t0)
            return {
                "wall_s": round(min(walls), 4),
                "walls_s": [round(w, 4) for w in walls],
                "push_streams": int(sender.push_streams),
                "stream_bw_mbps_min": round(sender.stream_bw_mbps_min, 1),
                "reshard_bytes": int(sender.reshard_bytes),
                "stream_resumes": int(sender.stream_resumes),
                "verify_failures": int(sender.verify_failures),
                "bitwise_ok": bool(np.array_equal(rx.buffer, buf)),
            }
        finally:
            if rx is not None:
                rx.stop()
            sender.stop()

    # sequential pairs — never two fabrics (or jax procs) at once
    single = one_config(1)
    multi = one_config(streams)
    return {
        "speedup": round(single["wall_s"] / max(multi["wall_s"], 1e-9), 3),
        "bytes_per_stream": int(max(per_stream)),
        "stream_resumes": int(multi["stream_resumes"]),
        "total_bytes": int(total),
        "streams": int(streams), "tp": int(tp), "rounds": int(rounds),
        "reshard_bytes_per_round": int(rmap.reshard_bytes()),
        "single": single,
        "multi": multi,
        "bitwise_ok": bool(single["bitwise_ok"] and multi["bitwise_ok"]),
    }


def group_share_bench(preset: str = "tiny", g: int = 8, groups: int = 4,
                      prompt_len: int = 128, new_tokens: int = 32) -> dict:
    """Group-shared prefill A/B (``python bench.py --group-share``): the
    same GRPO-shaped workload (``groups`` prompts × ``g`` samples each)
    through two CB engines — group sharing ON (one prompt prefill + one
    batched sibling attach per group) vs FORCED-INDEPENDENT
    (``group_share=False``: the pre-group-share engine, where the leader
    prefills and every sibling admits as a SERIALIZED singleton suffix
    dispatch — admission dispatch count linear in g). Reports prefill
    dispatch counts (the admission bottleneck on dispatch-latency-bound
    links), the engine's prefill_reuse_frac, and wall/throughput. Each
    engine takes one untimed warm pass first so XLA compiles stay out of
    the timed window. CPU-sized by default; scale via env/flags on a real
    chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.cb_engine import STREAM_END, CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg = decoder.get_config(preset, dtype=jnp.float32 if preset == "tiny"
                             else jnp.bfloat16)
    params = jax.jit(lambda: decoder.init_params(jax.random.PRNGKey(0),
                                                 cfg))()
    page_size = min(64, prompt_len)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(groups)]
    sp = SamplingParams(temperature=1.0, max_new_tokens=new_tokens,
                        stop_token_ids=())

    def run(share: bool) -> dict:
        from polyrl_tpu.rollout.flightdeck import EngineFlightDeck

        eng = CBEngine(
            cfg, params, max_slots=max(g * 2, 16), page_size=page_size,
            max_seq_len=-(-(prompt_len + new_tokens) // page_size)
            * page_size, prompt_buckets=(prompt_len,),
            num_pages=groups * g * 4 * (-(-(prompt_len + new_tokens)
                                          // page_size)),
            group_share=share, steps_per_dispatch=4)

        def drive(batch_prompts: list, tag: str) -> tuple[float, int]:
            outs = []
            for gi, p in enumerate(batch_prompts):
                for si in range(g):
                    outs.append(eng.submit(
                        f"{tag}{gi}-{si}", p, sp,
                        group_id=f"{tag}{gi}", group_size=g))
            eng.start()
            t0 = time.monotonic()
            total = 0
            for q in outs:
                while True:
                    item = q.get(timeout=600)
                    if item is STREAM_END:
                        break
                    total += len(item["token_ids"])
            return time.monotonic() - t0, total

        # untimed warm pass (compiles every variant this traffic shape
        # touches), then reset cache/counters so the timed window is clean
        warm = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()]
        drive(warm, "warm")
        eng.flush_prefix_cache()
        eng.prefill_dispatches = 0
        eng.sibling_attach_dispatches = 0
        eng.group_forked_requests = 0
        eng.deck = EngineFlightDeck(eng.max_slots, eng.num_pages,
                                    eng.page_size)

        wall, total = drive(prompts, "grp")
        deck = eng.deck
        res = {
            "wall_s": round(wall, 3),
            "tok_s": round(total / wall, 1) if wall > 0 else 0.0,
            "prefill_dispatches": eng.prefill_dispatches,
            "sibling_attach_dispatches": eng.sibling_attach_dispatches,
            "group_forked_requests": eng.group_forked_requests,
            "dispatches_per_group": round(
                eng.prefill_dispatches / groups, 2),
            "prefill_reuse_frac": round(deck.prefill_reuse_frac(), 4),
            "attributed_frac": round(deck.attributed_frac(), 6),
        }
        eng.stop()
        return res

    shared = run(True)
    independent = run(False)
    return {
        "g": g, "groups": groups, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "shared": shared, "independent": independent,
        # headline fields bench_gate watches: reuse must hold, the
        # per-group dispatch count must stay <= 2 (1 prefill + 1 attach)
        "engine_prefill_reuse_frac": shared["prefill_reuse_frac"],
        "dispatches_per_group": shared["dispatches_per_group"],
        "dispatch_reduction": round(
            independent["prefill_dispatches"]
            / max(shared["prefill_dispatches"], 1), 2),
        "speedup": round(independent["wall_s"]
                         / max(shared["wall_s"], 1e-9), 2),
    }


def loop_profile_bench(preset: str = "tiny", batch: int = 16,
                       prompt_len: int = 64, new_tokens: int = 32,
                       reps: int = 3) -> dict:
    """Engine-loop profiler self-overhead A/B (``python bench.py
    --loop-profile``): the same concurrent workload through two CB
    engines — profiler ON (the serving default: per-iteration phase
    attribution, clock reads + fold locks on the loop thread) vs OFF
    (``loop_profile=False``, the pre-profiler loop and the bitwise
    baseline). Best-of-``reps`` timed walls on each side so one scheduler
    hiccup doesn't read as profiler overhead. Extras carry the ON
    engine's own verdict on itself — ``attributed_frac`` (must stay ~1.0
    under real churn), the windowed ``device_frac`` and the
    ``accounting_frac`` the overhead budget pins. CPU-sized by default;
    scale via env/flags on a real chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.cb_engine import STREAM_END, CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg = decoder.get_config(preset, dtype=jnp.float32 if preset == "tiny"
                             else jnp.bfloat16)
    params = jax.jit(lambda: decoder.init_params(jax.random.PRNGKey(0),
                                                 cfg))()
    page_size = min(64, prompt_len)
    seq_pages = -(-(prompt_len + new_tokens) // page_size)
    rng = np.random.default_rng(7)
    sp = SamplingParams(temperature=1.0, max_new_tokens=new_tokens,
                        stop_token_ids=())

    def run(profile: bool) -> dict:
        eng = CBEngine(
            cfg, params, max_slots=min(batch, 16), page_size=page_size,
            max_seq_len=seq_pages * page_size, prompt_buckets=(prompt_len,),
            num_pages=batch * seq_pages * 2, steps_per_dispatch=4,
            loop_profile=profile)
        eng.start()

        def drive(tag: str) -> tuple[float, int]:
            outs = [eng.submit(
                f"{tag}-{i}",
                rng.integers(1, cfg.vocab_size, prompt_len).tolist(), sp)
                for i in range(batch)]
            t0 = time.monotonic()
            total = 0
            for q in outs:
                while True:
                    item = q.get(timeout=600)
                    if item is STREAM_END:
                        break
                    total += len(item["token_ids"])
            return time.monotonic() - t0, total

        drive("warm")  # untimed: XLA compiles stay out of the timed reps
        walls, total = [], 0
        for r in range(reps):
            wall, tok = drive(f"r{r}")
            walls.append(wall)
            total = tok
        res = {
            "loop_profile": profile,
            "wall_s_best": round(min(walls), 3),
            "wall_s": [round(w, 3) for w in walls],
            "tok_s": round(total / min(walls), 1) if min(walls) > 0 else 0.0,
        }
        if profile:
            res.update({k: round(float(v), 4)
                        for k, v in eng.loop_profile_info().items()})
        eng.stop()
        return res

    on = run(True)
    off = run(False)
    overhead = (on["wall_s_best"] / max(off["wall_s_best"], 1e-9) - 1.0)
    return {
        "batch": batch, "prompt_len": prompt_len, "new_tokens": new_tokens,
        "reps": reps, "on": on, "off": off,
        # headline: profiler wall cost as a fraction of the unprofiled
        # loop (negative = measurement noise; the gate bounds the rise)
        "overhead_pct": round(100.0 * overhead, 2),
        "engine_device_frac": on.get("device_frac", 0.0),
        "engine_accounting_frac": on.get("accounting_frac", 0.0),
        "engine_loop_attributed_frac": on.get("loop_attributed_frac", 0.0),
    }


def kv_spill_bench(preset: str = "tiny", sessions: int = 12,
                   prompt_len: int = 64, new_tokens: int = 16,
                   page_size: int = 16, max_slots: int = 4) -> dict:
    """Host-RAM KV spill oversubscription A/B (``python bench.py
    --kv-spill``): a session-resume workload (``sessions`` prompts
    established then resumed — the multi-turn shape where each session's
    published prefix KV must SURVIVE between turns) through two engines
    at the SAME HBM-capped page budget (sized to hold the active decode
    set plus only a couple of idle sessions): spill ON pages cold
    published KV out to pinned host RAM and restores it on the resume
    hit, spill OFF (the PR 17 engine) capacity-evicts it — destroyed KV
    means the resume re-prefills from scratch. A session counts as
    surviving when its resume prefill is served from cached pages. The
    headline is the survival multiplier; a big-pool never-spilled
    reference engine pins the resumed greedy outputs bitwise (restore at
    a new physical index must be invisible to decode). Extras carry the
    abort count (must be 0 — oversubscription is not allowed to shed
    load), the ledger's quiescent ``attributed_frac`` with the spilled
    tier counted, and the restore-rate thrash signal bench_gate watches.
    CPU-sized by default; scale via env/flags on a real chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    jax.config.update("jax_default_matmul_precision", "highest")
    cfg = decoder.get_config(preset, dtype=jnp.float32 if preset == "tiny"
                             else jnp.bfloat16)
    params = jax.jit(lambda: decoder.init_params(jax.random.PRNGKey(0),
                                                 cfg))()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(sessions)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=new_tokens,
                        stop_token_ids=())
    pages_per = -(-(prompt_len + new_tokens) // page_size)
    max_seq = pages_per * page_size
    # the fixed page budget: the active decode set + ~2 idle sessions.
    # Far less than ``sessions`` worth of KV — the oversubscription shape.
    capped_pages = (max_slots + 2) * pages_per + 4
    big_pages = sessions * pages_per * 2 + 8

    def run(spill: bool, num_pages: int) -> dict:
        eng = CBEngine(
            cfg, params, pad_token_id=0, kv_cache_dtype=jnp.float32,
            max_slots=max_slots, page_size=page_size, max_seq_len=max_seq,
            prompt_buckets=(prompt_len,), num_pages=num_pages,
            steps_per_dispatch=4, kv_ledger=True,
            kv_cold_after_dispatches=4, kv_spill=spill,
            kv_spill_host_gb=1.0)
        aborted = 0
        t0 = time.monotonic()
        est = eng.generate(prompts, sp, timeout=600.0)
        aborted += sum(1 for r in est
                       if r["finish_reason"] in ("abort", "error"))
        # resume one session at a time so the deck's cached-token delta
        # attributes survival per session (a full-prefix hit means the
        # session's KV was still addressable — resident or restored)
        hot = 0
        resumed = []
        for p in prompts:
            c0 = eng.deck.cached_prompt_tokens
            r = eng.generate([p], sp, timeout=600.0)[0]
            if r["finish_reason"] in ("abort", "error"):
                aborted += 1
            if eng.deck.cached_prompt_tokens - c0 >= prompt_len - page_size:
                hot += 1
            resumed.append(r)
        wall = time.monotonic() - t0
        time.sleep(0.3)  # let the loop settle before the quiescent read
        info = eng.kv_memory_info()
        res = {
            "wall_s": round(wall, 3),
            "sessions_hot": hot,
            "aborted_requests": aborted,
            "attributed_frac": float(info.get("memory/attributed_frac",
                                              1.0)),
            "kv_spilled_frac": float(info.get("kv_spilled_frac", 0.0)),
            "restore_rate": float(info.get("kv_restore_rate", 0.0)),
            "pages_spilled": int(info.get("memory/pages_spilled", 0)),
            "pages_restored": int(info.get("memory/pages_restored", 0)),
        }
        if eng.kvspill is not None:
            s = eng.kvspill.stats()
            res["spill_host"] = {k: s[k] for k in
                                 ("resident_pages", "bytes_spilled",
                                  "bytes_restored", "copy_batches",
                                  "sync_fetches")}
        res["_resumed"] = resumed
        eng.stop()
        return res

    spill_on = run(True, capped_pages)
    baseline = run(False, capped_pages)
    reference = run(False, big_pages)
    bitwise = all(
        a["token_ids"] == b["token_ids"]
        for a, b in zip(spill_on.pop("_resumed"), reference["_resumed"]))
    baseline.pop("_resumed")
    reference.pop("_resumed")
    return {
        "sessions": sessions, "prompt_len": prompt_len,
        "new_tokens": new_tokens, "page_size": page_size,
        "capped_pages": capped_pages, "big_pages": big_pages,
        "spill": spill_on, "baseline": baseline, "reference": reference,
        # headline + gate fields: the survival multiplier at the fixed
        # page budget, the thrash signal, and the correctness pins
        "sessions_speedup": round(
            spill_on["sessions_hot"] / max(baseline["sessions_hot"], 1), 2),
        "restore_rate": spill_on["restore_rate"],
        "aborted_requests": (spill_on["aborted_requests"]
                             + baseline["aborted_requests"]
                             + reference["aborted_requests"]),
        "bitwise_identical": bool(bitwise),
        "attributed_frac": spill_on["attributed_frac"],
    }


def decode_attn_bench(preset: str = "tiny", gs: tuple = (1, 8),
                      prefixes: tuple = (512, 2048), slots: int = 16,
                      suffix: int = 64, page_size: int = 64,
                      iters: int = 10) -> dict:
    """Shared-prefix decode attention A/B (``python bench.py
    --decode-attn``): the grouped two-phase kernel vs the production
    ungrouped paged-attention path at the OPS level — the same pools,
    page tables and queries, with ``slots`` decode rows arranged as
    groups of G siblings sharing a ``prefix``-token prompt KV plus a
    private ``suffix``. G=1 measures the grouped kernel's overhead floor
    (no sharing to exploit); G=8 × prefix=2048 is the GRPO shape where
    the prompt KV dominates and the per-slot kernel re-streams it G
    times. Reports wall per call, speedup, the analytic
    ``kv_read_pages_per_token`` both paths pay, and the max output error
    vs the ungrouped oracle (a broken merge must be loud in the field).
    CPU-sized by default (jnp reference impls — the read-page accounting
    is exact either way); on a real chip run with JAX_PLATFORMS unset to
    A/B the Pallas kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.models import decoder
    from polyrl_tpu.ops.paged_attention import (
        grouped_paged_attention,
        paged_attention,
    )

    cfg = decoder.get_config(preset)
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_
    hq = cfg.num_heads
    rng = np.random.default_rng(0)
    cases: dict = {}
    headline: dict = {}
    for prefix in prefixes:
        n_pre = -(-prefix // page_size)
        for g in gs:
            n_groups = max(1, slots // g)
            s = n_groups * g
            sfx_pages = -(-(suffix + 1) // page_size)
            n_pool = 1 + n_groups * n_pre + s * sfx_pages
            k_pool = jnp.asarray(rng.standard_normal(
                (hkv, n_pool, page_size, hd)), jnp.bfloat16)
            v_pool = jnp.asarray(rng.standard_normal(
                (hkv, n_pool, page_size, hd)), jnp.bfloat16)
            q = jnp.asarray(rng.standard_normal((s, hq, hd)), jnp.bfloat16)
            free = list(range(1, n_pool))
            table = np.zeros((s, n_pre + sfx_pages), np.int32)
            lens = np.full((s,), prefix + suffix + 1, np.int32)
            g_slots = np.full((n_groups, g), -1, np.int32)
            g_pages = np.zeros((n_groups, n_pre), np.int32)
            g_lens = np.full((n_groups,), prefix, np.int32)
            for gi in range(n_groups):
                pre = [free.pop() for _ in range(n_pre)]
                g_pages[gi] = pre
                for si in range(g):
                    row = gi * g + si
                    g_slots[gi, si] = row
                    table[row, :n_pre] = pre
                    table[row, n_pre:] = [free.pop()
                                          for _ in range(sfx_pages)]
            args = (q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lens))
            gargs = args + (jnp.asarray(g_slots), jnp.asarray(g_pages),
                            jnp.asarray(g_lens))

            def timed(fn, fargs):
                fn_j = jax.jit(fn)  # one traced graph per path (the CPU
                # ref impls are otherwise eager op-by-op — unfair timing)
                out = jax.block_until_ready(fn_j(*fargs))  # compile/warm
                t0 = time.monotonic()
                for _ in range(iters):
                    out = jax.block_until_ready(fn_j(*fargs))
                return (time.monotonic() - t0) / iters, out

            t_ung, out_u = timed(paged_attention, args)
            t_grp, out_g = timed(grouped_paged_attention, gargs)
            err = float(jnp.max(jnp.abs(
                out_g.astype(jnp.float32) - out_u.astype(jnp.float32))))
            # analytic read accounting: every slot logically attends
            # n_pre + sfx_pages pages; grouped streams each group's
            # prefix ONCE
            logical = s * (n_pre + sfx_pages)
            grouped_pages = n_groups * n_pre + s * sfx_pages
            case = {
                "ungrouped_ms": round(t_ung * 1e3, 3),
                "grouped_ms": round(t_grp * 1e3, 3),
                "speedup": round(t_ung / max(t_grp, 1e-9), 3),
                "kv_read_pages_per_token_ungrouped": round(logical / s, 2),
                "kv_read_pages_per_token": round(grouped_pages / s, 2),
                "read_reduction": round(logical / grouped_pages, 2),
                "max_abs_err": round(err, 5),
                "slots": s, "groups": n_groups,
            }
            cases[f"g{g}_p{prefix}"] = case
            if g == max(gs) and prefix == max(prefixes):
                headline = case
    return {
        "preset": preset, "page_size": page_size, "suffix": suffix,
        "iters": iters, "backend": jax.default_backend(),
        "cases": cases,
        # bench_gate watches: the G-max/prefix-max A/B speedup must not
        # regress and the grouped read cost must hold (~G× below the
        # ungrouped pages/token on the prefix segment)
        "speedup": headline.get("speedup", 0.0),
        "kv_read_pages_per_token": headline.get(
            "kv_read_pages_per_token", 0.0),
        "read_reduction": headline.get("read_reduction", 0.0),
    }


def _chip_peaks(device_kind: str) -> tuple[float, float]:
    """(bf16 FLOP/s, HBM bytes/s) of one chip; a device that is not in
    the table (utils/flops.py CHIP_PEAKS) is an error, not v5e."""
    from polyrl_tpu.utils.flops import CHIP_PEAKS

    if device_kind not in CHIP_PEAKS:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}")
    tflops, gb_s = CHIP_PEAKS[device_kind]
    return tflops * 1e12, gb_s * 1e9


def _utilization(tok_s: float, param_count: int, param_bytes: int,
                 eff_batch: int, device_kind: str) -> dict:
    """Decode-phase roofline fields: MFU (2*N FLOPs/token) and the HBM
    weight-read bandwidth implied by steps/s = tok_s / effective batch."""
    peak_flops, peak_bw = _chip_peaks(device_kind)
    mfu = tok_s * 2.0 * param_count / peak_flops
    steps_per_s = tok_s / max(eff_batch, 1)
    hbm = steps_per_s * param_bytes / peak_bw
    return {"mfu_pct": round(100 * mfu, 2),
            "hbm_weight_read_util_pct": round(100 * hbm, 1),
            "chip": device_kind}


def assemble_result(state: dict) -> dict:
    """Build the final JSON line from the phase state (pure, no jax)."""
    extra = dict(state.get("extra") or {})
    # v0-vs-CB-vs-spec shootout table: one place to
    # read the engine comparison once the phases have real numbers.
    shootout: dict = {}
    if (extra.get("bucketed") or {}).get("tok_s"):
        shootout["v0_bucketed_tok_s"] = extra["bucketed"]["tok_s"]
    cb = extra.get("cb") or {}
    if cb.get("direct_tok_s"):
        shootout["cb_direct_tok_s"] = cb["direct_tok_s"]
        shootout["cb_serve_tok_s"] = cb.get("serve_tok_s")
        shootout["cb_serve_peak_tok_s"] = cb.get("serve_peak_tok_s")
    spec_on = ((extra.get("spec") or {}).get("on") or {}).get(
        "continuation") or {}
    if spec_on.get("tok_s"):
        shootout["cb_spec_continuation_tok_s"] = spec_on["tok_s"]
        shootout["spec_speedup_continuation"] = (
            extra["spec"].get("speedup_continuation"))
    if len(shootout) > 1:
        extra["shootout"] = dict(
            shootout, note="v0/cb at the headline workload; spec at b64; "
                           "v0 is BEST-OF-2 reps (drift diagnosis), cb/spec "
                           "single-rep — per-phase entries carry configs")
    # promote the serving plane's flight-deck readout to top-level
    # extra.engine_* so bench_gate watches it across rounds
    for k in ("engine_occupancy", "engine_page_util_peak",
              "engine_cache_hit_rate", "engine_ttft_p95_ms",
              "engine_tpot_p95_ms", "engine_attributed_frac",
              "engine_prefill_reuse_frac", "engine_shared_prefix_read_frac",
              "engine_kv_read_pages_per_token",
              "engine_kv_cold_page_frac", "engine_hbm_headroom_gb",
              "engine_device_frac", "engine_accounting_frac"):
        v = cb.get(k)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            extra[k] = v
    meta = state.get("meta") or {}
    # promote the cb phase's RL-shaped drill (group-share + async-cadence
    # weight installs): the post-PR-3/8 rollout decode headline plus the
    # staleness spread the gate bounds
    rl = cb.get("rl") or {}
    if rl.get("decode_tok_s"):
        extra["rollout_decode_tok_s_per_chip"] = round(
            rl["decode_tok_s"] / max(meta.get("n_chips", 1), 1), 1)
        extra["rl_staleness_p95"] = rl.get("staleness_p95", 0.0)
    # promote the cb phase's sharded-push drill: the N-stream push wall of
    # the REAL weights lands next to the decode headline, so real-TPU
    # rounds track the sharded fabric across the trajectory
    ps = cb.get("push_shard") or {}
    if ps.get("push_wall_s"):
        extra["transfer_push_streams"] = ps.get("push_streams", 0)
        extra["push_shard_wall_s"] = ps["push_wall_s"]
    preset = meta.get("preset", "qwen3-1.7b")
    batch = meta.get("batch", 256)
    prompt_len = meta.get("prompt_len", 128)
    new_tokens = meta.get("new_tokens", 128)
    n_chips = max(meta.get("n_chips", 1), 1)
    cb_serve = (extra.get("cb") or {}).get("serve_tok_s")
    b8 = extra.get("llama3_8b") or {}
    if cb_serve:
        name, primary = "cb_serving_tok_s_per_chip", cb_serve
    elif b8.get("tok_s"):
        # narrow-window case the 8b-first phase order exists for: the 8B
        # number IS the north-star headline (BASELINE: ≥2k tok/s/chip at 8B)
        preset = meta.get("preset_8b", "llama3-8b")
        batch = b8.get("batch", batch)
        name = f"decode_tok_s_per_chip_{b8.get('quant', 'bf16')}"
        primary = b8["tok_s"]
    else:  # metric label must say what was actually measured
        name = "rollout_decode_tok_s_per_chip"
        primary = (extra.get("bucketed") or {}).get("tok_s", 0.0)
    return {
        "metric": f"{name}[{preset},b{batch},p{prompt_len},g{new_tokens}]",
        "value": round(primary / n_chips, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(primary / n_chips / 2000.0, 3),
        "extra": extra,
    }


def main() -> None:
    """The bench: every phase in order, in this process. A phase that
    raises fails the run — a dead backend or a kernel the compiler refuses
    must not end as a JSON line and exit 0."""
    from polyrl_tpu.utils.xla_cache import configure_compile_cache

    configure_compile_cache()
    state: dict = {"extra": {}, "meta": {}}
    extra: dict = state["extra"]

    preset = os.environ.get("POLYRL_BENCH_PRESET", "qwen3-1.7b")
    preset_8b = os.environ.get("POLYRL_BENCH_8B_PRESET", "llama3-8b")
    batch = int(os.environ.get("POLYRL_BENCH_BATCH", "256"))
    prompt_len = int(os.environ.get("POLYRL_BENCH_PROMPT", "128"))
    new_tokens = int(os.environ.get("POLYRL_BENCH_NEW", "128"))
    phases = os.environ.get(
        "POLYRL_BENCH_PHASES", "8b,cb,weight_sync,spec,bucketed").split(",")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.models import decoder

    cfg = decoder.get_config(preset, dtype=jnp.bfloat16)
    kind = jax.devices()[0].device_kind
    _chip_peaks(kind)  # an unknown device (CPU included) fails here
    state["meta"] = {
        "preset": preset, "preset_8b": preset_8b, "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "n_chips": len(jax.devices()), "device_kind": kind,
    }
    extra["hbm_gb"] = round(_hbm_limit_gb(), 1)
    _note("device", {"kind": kind, "count": len(jax.devices())})

    shapes = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    param_count = sum(int(np.prod(l.shape))
                      for l in jax.tree_util.tree_leaves(shapes))
    max_slots = int(os.environ.get("POLYRL_BENCH_SLOTS", "128"))

    # Flagship params build LAZILY so the 8B phase (which allocates its own
    # ~8.6 GiB int8 tree) can run first without the 1.7B bf16 tree also
    # resident; they build once at the first flagship phase and are freed
    # before any later 8B attempt.
    _params_cell: list = []

    def get_params():
        if not _params_cell:
            p = jax.jit(lambda: decoder.init_params(
                jax.random.PRNGKey(0), cfg))()
            jax.block_until_ready(p)
            _params_cell.append(p)
        return _params_cell[0]

    def free_params() -> None:
        if _params_cell:
            _params_cell.clear()
            gc.collect()

    def _with_util(res: dict, key: str, eff_batch: int,
                   pcount: int, pbytes: int) -> dict:
        if isinstance(res, dict) and res.get(key):
            res["util"] = _utilization(res[key], pcount, pbytes,
                                       eff_batch, kind)
        return res

    def _run_8b():
        free_params()
        return bench_8b(preset_8b)

    phase_table: dict = {
        "bucketed": (lambda: _with_util(
            bench_bucketed(cfg, get_params(), batch, prompt_len, new_tokens),
            "tok_s", batch, param_count, param_count * 2), None),
        "cb": (lambda: _with_util(
            bench_cb(cfg, get_params(), batch, prompt_len, new_tokens,
                     max_slots=max_slots,
                     steps_per_dispatch=int(os.environ.get("POLYRL_BENCH_K",
                                                           "8"))),
            "serve_tok_s", min(max_slots, batch), param_count,
            param_count * 2), None),
        "spec": (lambda: bench_spec(
            cfg, get_params(), batch=min(batch, 64), prompt_len=prompt_len,
            new_tokens=new_tokens,
            spec_tokens=int(os.environ.get("POLYRL_BENCH_SPEC", "4"))), None),
        "weight_sync": (lambda: bench_weight_sync(get_params()), None),
        "8b": (_run_8b, PHASE_STORE_KEYS["8b"]),
    }
    for name in phases:
        if name not in phase_table:
            continue
        fn, store_key = phase_table[name]
        key = store_key or name
        extra[key] = fn()
        _note(key, extra[key])
    free_params()

    print(json.dumps(assemble_result(state)))
    _maybe_run_gate()


def _maybe_run_gate() -> None:
    """Bench post-step (``POLYRL_BENCH_GATE=1``): run tools/bench_gate.py
    over the repo's ``BENCH_*.json`` trajectory after the driver line is
    emitted. stderr-only and best-effort — the gate must never alter the
    driver JSON line or the bench exit code."""
    if os.environ.get("POLYRL_BENCH_GATE", "") != "1":
        return
    try:
        import importlib.util

        here = os.path.dirname(os.path.abspath(__file__))
        spec = importlib.util.spec_from_file_location(
            "bench_gate", os.path.join(here, "tools", "bench_gate.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        paths = mod.find_rounds(here)
        if not paths:
            return
        _, report = mod.run(paths, mod.DEFAULT_THRESHOLD)
        print(f"[bench] gate: {json.dumps(report)}",
              file=sys.stderr, flush=True)
    except Exception as exc:  # noqa: BLE001 — the gate is advisory here
        print(f"[bench] gate failed: {exc}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    if "--chaos" in sys.argv:
        # fault-injected recovery drill (token-level continuous generation):
        # its own entry — CPU-sized by default (set JAX_PLATFORMS/preset
        # env to scale it up)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        res = chaos_bench(
            preset=os.environ.get("POLYRL_BENCH_PRESET", "tiny"),
            batch=int(_cli_float("--batch", 8)),
            new_tokens=int(_cli_float("--new-tokens", 48)),
            drain_after=int(_cli_float("--drain-after", 2)),
            stream_kills=int(_cli_float("--stream-kills", 1)))
        print(json.dumps({"metric": "chaos_tokens_salvaged",
                          "value": res["tokens_salvaged_total"],
                          "unit": "tokens", "extra": res}))
    elif "--pool" in sys.argv:
        # elastic-pool topology bench: N engines, one manager, a steady
        # round + a preemption/rejoin drill. CPU-sized by default; real
        # engines via --pool-endpoints (never preempted).
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        eps = ()
        spot_trace = ""
        for i, a in enumerate(sys.argv):
            if a == "--pool-endpoints" and i + 1 < len(sys.argv):
                eps = tuple(e for e in sys.argv[i + 1].split(",") if e)
            elif a.startswith("--pool-endpoints="):
                eps = tuple(e for e in a.split("=", 1)[1].split(",") if e)
            elif a == "--spot-trace" and i + 1 < len(sys.argv):
                spot_trace = sys.argv[i + 1]
            elif a.startswith("--spot-trace="):
                spot_trace = a.split("=", 1)[1]
        try:
            n_engines = int(_cli_float("--pool", 2))
        except ValueError:  # bare --pool with another flag following
            n_engines = 2
        res = pool_bench(
            n_engines=n_engines,
            preset=os.environ.get("POLYRL_BENCH_PRESET", "tiny"),
            batch=int(_cli_float("--batch", 8)),
            new_tokens=int(_cli_float("--new-tokens", 48)),
            rounds=int(_cli_float("--rounds", 2)),
            endpoints=eps, spot_trace=spot_trace)
        print(json.dumps({"metric": "pool_tok_s", "value": res["tok_s"],
                          "unit": "tok/s", "extra": {"pool": res}}))
    elif "--push-chaos" in sys.argv:
        # weight-fabric fault drill: injected frame corruption + a stalled
        # stream on a 2-receiver push topology; the headline is the
        # recovery wall, extras carry the verify/resume counters watched
        # by bench_gate. CPU-only.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        res = push_chaos_bench(
            buffer_mb=_cli_float("--buffer-mb", 2.0),
            streams=int(_cli_float("--streams", 2)),
            stall_s=_cli_float("--stall-s", 3.0))
        print(json.dumps({"metric": "push_chaos_recovery_s",
                          "value": res["transfer_recovery_s"], "unit": "s",
                          "extra": {"push_chaos": res}}))
    elif "--push-shard" in sys.argv:
        # sharded weight-fabric A/B: 1 vs N parallel shard-to-shard push
        # streams at fixed total bytes against a tp-sharded receiver; the
        # headline is the wall-clock speedup, extras carry the per-stream
        # byte cap and resume counters watched by bench_gate. CPU-only.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        res = push_shard_bench(
            buffer_mb=_cli_float("--buffer-mb", 8.0),
            streams=int(_cli_float("--streams", 4)),
            rounds=int(_cli_float("--rounds", 3)),
            tp=int(_cli_float("--tp", 2)))
        print(json.dumps({"metric": "push_shard_speedup",
                          "value": res["speedup"], "unit": "x",
                          "extra": {"push_shard": res}}))
    elif "--group-share" in sys.argv:
        # group-shared prefill A/B: shared vs forced-independent admission
        # on the GRPO traffic shape — its own entry, CPU-sized by default
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        res = group_share_bench(
            preset=os.environ.get("POLYRL_BENCH_PRESET", "tiny"),
            g=int(_cli_float("--g", 8)),
            groups=int(_cli_float("--groups", 4)),
            prompt_len=int(_cli_float("--prompt-len", 128)),
            new_tokens=int(_cli_float("--new-tokens", 32)))
        print(json.dumps({"metric": "group_share_dispatch_reduction",
                          "value": res["dispatch_reduction"], "unit": "x",
                          "extra": {"group_share": res}}))
    elif "--loop-profile" in sys.argv:
        # engine-loop profiler self-overhead A/B: profiler ON vs OFF at
        # the same concurrent workload — its own entry, CPU-sized by
        # default; the headline is the profiler's wall cost in percent
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        res = loop_profile_bench(
            preset=os.environ.get("POLYRL_BENCH_PRESET", "tiny"),
            batch=int(_cli_float("--batch", 16)),
            prompt_len=int(_cli_float("--prompt-len", 64)),
            new_tokens=int(_cli_float("--new-tokens", 32)),
            reps=int(_cli_float("--reps", 3)))
        print(json.dumps({"metric": "loop_profile_overhead_pct",
                          "value": res["overhead_pct"], "unit": "%",
                          "extra": {"loop_profile": res}}))
    elif "--kv-spill" in sys.argv:
        # host-RAM KV spill oversubscription A/B: session-resume workload
        # at a fixed HBM-capped page budget, spill vs capacity-evict, with
        # a big-pool reference pinning resumed greedy outputs bitwise —
        # its own entry, CPU-sized by default
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        res = kv_spill_bench(
            preset=os.environ.get("POLYRL_BENCH_PRESET", "tiny"),
            sessions=int(_cli_float("--sessions", 12)),
            prompt_len=int(_cli_float("--prompt-len", 64)),
            new_tokens=int(_cli_float("--new-tokens", 16)),
            page_size=int(_cli_float("--page-size", 16)),
            max_slots=int(_cli_float("--slots", 4)))
        print(json.dumps({"metric": "kv_spill_sessions_speedup",
                          "value": res["sessions_speedup"], "unit": "x",
                          "extra": {"kv_spill": res}}))
    elif "--decode-attn" in sys.argv:
        # shared-prefix decode attention A/B: grouped two-phase kernel vs
        # the per-slot kernel at the GRPO traffic shape — its own entry,
        # CPU-sized by default (set JAX_PLATFORMS/preset env for a real
        # chip, where the Pallas kernels are what gets timed)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        res = decode_attn_bench(
            preset=os.environ.get("POLYRL_BENCH_PRESET", "tiny"),
            slots=int(_cli_float("--slots", 16)),
            suffix=int(_cli_float("--suffix", 64)),
            page_size=int(_cli_float("--page-size", 64)),
            iters=int(_cli_float("--iters", 10)))
        print(json.dumps({"metric": "decode_attn_speedup",
                          "value": res["speedup"], "unit": "x",
                          "extra": {"decode_attn": res}}))
    elif "--async-sweep" in sys.argv:
        # bounded-staleness async A/B over pipeline depth {0,1,2,4} with
        # staleness_limit=depth — CPU-only, its own entry
        res = async_sweep_bench(
            steps=int(_cli_float("--steps", 6)),
            gen_delay_s=_cli_float("--gen-delay-s", 0.25),
            push_delay_s=_cli_float("--push-delay-s", 0.25))
        print(json.dumps({"metric": "async_step_speedup",
                          "value": res["async_step_speedup"], "unit": "x",
                          "extra": res}))
    elif "--pipeline-microbench" in sys.argv:
        # CPU-only A/B of the trainer's pipelined mode — its own entry
        res = pipeline_microbench(
            steps=int(_cli_float("--steps", 4)),
            gen_delay_s=_cli_float("--gen-delay-s", 0.4),
            push_delay_s=_cli_float("--push-delay-s", 0.15))
        print(json.dumps({"metric": "pipeline_step_speedup",
                          "value": res["step_speedup"], "unit": "x",
                          "extra": res}))
    else:
        main()
