#!/usr/bin/env python3
"""Bench regression gate: diff the newest ``BENCH_*.json`` against the
round trajectory and FAIL on regressions (ARCHITECTURE.md "Goodput &
health plane").

Earlier rounds drifted into rc=124 deaths with nobody noticing between
rounds — the trajectory was recorded but never read. This gate reads it:

- **rc**: the newest round must have exited 0 (a rc=124/SIGTERM round is
  a regression even when a partial JSON landed);
- **headline**: ``parsed.value`` must not drop more than ``--threshold``
  (default 15%) below the median of the prior successful rounds;
- **goodput/phase fields**: watched ``extra`` paths (serving tok/s, MFU,
  weight-sync seconds, TTFT tails, ...) are diffed the same way, in the
  direction that matters per key.

Input formats: the driver wrapper ``{"n", "rc", "tail", "parsed": {...}}``
or a bare bench line ``{"metric", "value", ...}`` (rc assumed 0). Rounds
sort by their ``n`` field, falling back to filename order.

Run standalone::

    python tools/bench_gate.py               # gates ./BENCH_*.json
    python tools/bench_gate.py --dir /runs --threshold 0.10 --json

or as a bench post-step: ``POLYRL_BENCH_GATE=1 python bench.py`` runs the
gate after the bench line is emitted (report to stderr; never changes the
bench's own exit code). Exit status: 0 = ok (or not enough history),
1 = regression, 2 = usage/input error.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

# the per-key direction semantics are SHARED with the FlightRecorder's
# direction-aware watch (polyrl_tpu/obs/recorder.py) — one definition of
# "which way is bad", used by both the live anomaly detector and this
# offline gate
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
from polyrl_tpu.obs.recorder import direction_violates  # noqa: E402

DEFAULT_THRESHOLD = 0.15

# watched extra.* paths: (dotted path, direction-that-is-bad) — "low"
# fails when the value DROPS beyond the threshold (throughput, rates),
# "high" when it RISES (latencies, clip/degeneracy fractions). Missing
# paths are skipped — rounds measure what their phases reached.
WATCHED_EXTRA = (
    ("cb.serve_tok_s", "low"),
    ("cb.direct_tok_s", "low"),
    ("cb.serve_peak_tok_s", "low"),
    ("cb.util.mfu_pct", "low"),
    ("cb.ttft_p95_ms", "high"),
    ("cb.req_p95_s", "high"),
    ("llama3_8b.tok_s", "low"),
    ("llama3_8b.util.mfu_pct", "low"),
    ("bucketed.tok_s", "low"),
    ("bucketed.util.mfu_pct", "low"),
    ("weight_sync.eff_mb_s", "low"),
    ("weight_sync.total_s", "high"),
    ("spec.speedup_continuation", "low"),
    # elastic-pool topology (bench.py --pool N): aggregate throughput must
    # hold, the preemption/rejoin drill must not slow down, and a round
    # that silently shrank its pool is a regression
    ("pool.tok_s", "low"),
    ("pool.pool_engines", "low"),
    ("pool.recovery_s", "high"),
    # spot-market chaos drill (bench.py --pool --spot-trace FILE): the
    # fraction of requests that complete THROUGH the scripted offer/
    # notice/kill storm must hold, and the wall from first disruption to
    # the pool being back at target must not blow up
    ("pool.spot.completed_frac", "low"),
    ("pool.spot.recovery_s", "high"),
    # engine flight deck (server-side ledger, promoted from the cb phase):
    # decode occupancy and prefix-cache hit rate must hold; the
    # server-measured TTFT/TPOT tails must not blow up
    ("engine_occupancy", "low"),
    ("engine_cache_hit_rate", "low"),
    ("engine_ttft_p95_ms", "high"),
    ("engine_tpot_p95_ms", "high"),
    # group-shared prefill (bench.py --group-share A/B, and the cb phase's
    # serving default): the reuse fraction must hold, the per-group
    # admission dispatch count must stay collapsed (1 prefill + ≤1 attach
    # ⇒ reduction ~G/2), and sharing must keep paying off wall-clock
    ("engine_prefill_reuse_frac", "low"),
    ("group_share.engine_prefill_reuse_frac", "low"),
    ("group_share.dispatch_reduction", "low"),
    # shared-prefix decode attention (bench.py --decode-attn A/B + the cb
    # phase's rl drill): the fraction of logical KV page reads the grouped
    # kernel deduplicates must hold, the grouped-vs-ungrouped speedup must
    # not regress, and the grouped path's HBM pages per decoded token must
    # not creep back up toward the ungrouped cost
    ("engine_shared_prefix_read_frac", "low"),
    ("decode_attn.speedup", "low"),
    ("decode_attn.kv_read_pages_per_token", "high"),
    # weight-fabric fault drill (bench.py --push-chaos): the recovery wall
    # after injected corruption + a stalled stream must not blow up, the
    # resume must stay PARTIAL (resumed bytes climbing toward the full
    # buffer means the range ledger degraded to full re-pushes), and the
    # verify-rejection count must stay at the injected number (a rise
    # means the fabric rejects clean rounds)
    ("push_chaos.transfer_recovery_s", "high"),
    ("push_chaos.transfer_resumed_bytes", "high"),
    ("push_chaos.transfer_verify_failures", "high"),
    # sharded weight fabric (bench.py --push-shard A/B, and the cb phase's
    # real-weights drill promoted as push_shard_wall_s): the 1-vs-N-stream
    # wall-clock speedup must hold, a clean loopback round growing resumes
    # means streams started missing their bandwidth-keyed deadlines, and
    # the real-weights sharded-push wall must not blow up between rounds
    ("push_shard.speedup", "low"),
    ("push_shard.stream_resumes", "high"),
    ("push_shard_wall_s", "high"),
    # training health plane (bench.py --pipeline-microbench fit records,
    # obs/rlhealth.py): entropy collapsing between rounds is a regression
    # even when tok/s held; KL, TIS clipping and degenerate-group
    # fraction must not blow up
    ("training_entropy", "low"),
    ("training_approx_kl", "high"),
    ("training_tis_clip_frac", "high"),
    ("training_degenerate_group_frac", "high"),
    # critical-path plane (bench.py --pipeline-microbench traced leg,
    # obs/critical_path.py): the bottleneck segment's share of the step
    # wall concentrating upward, or the wall a 10% bottleneck speedup
    # would buy growing, means the pipeline is hiding less work —
    # an overlap regression even when tok/s held
    ("critpath_bottleneck_frac", "high"),
    ("critpath_headroom_s", "high"),
    # bounded-staleness async pipeline (bench.py --async-sweep): the
    # async-vs-fenced step speedup and the async run's tok/s must hold,
    # the training/staleness p95 must stay bounded by staleness_limit
    # (a rise means the admission gate stopped gating), and the async
    # run's RL dynamics must keep their PR 9 directions
    ("async_step_speedup", "low"),
    ("async_tok_s", "low"),
    ("async_staleness_p95", "high"),
    ("async_training_entropy", "low"),
    ("async_training_approx_kl", "high"),
    ("async_training_tis_clip_frac", "high"),
    # cb phase RL-shaped drill (group-share + async-cadence installs
    # overlapping decode): the post-PR-3/8 rollout decode headline the
    # ROADMAP bench debt names, and its per-token staleness spread
    ("rollout_decode_tok_s_per_chip", "low"),
    ("rl_staleness_p95", "high"),
    # KV memory plane (rollout/kvledger.py, promoted from the cb phase):
    # the resident set going cold between rounds means the cache is
    # accumulating pages nobody reads (a leak or an eviction regression);
    # the device HBM headroom dropping means something else grew into
    # the page pool's margin
    ("engine_kv_cold_page_frac", "high"),
    ("engine_hbm_headroom_gb", "low"),
    # host-RAM KV spill tier (bench.py --kv-spill A/B): the
    # sessions-per-chip multiplier over the HBM-capped baseline must
    # hold, and the restore rate must not climb (pages thrashing between
    # host and HBM means the watermarks are fighting the workload)
    ("kv_spill.sessions_speedup", "low"),
    ("kv_spill.restore_rate", "high"),
    # engine-loop profiler (obs/engine_profile.py, promoted from the cb
    # phase): the loop's device fraction dropping between rounds means
    # the loop thread got host-bound (the chip is starving); the
    # accounting fraction rising means deck/ledger/spill bookkeeping is
    # eating the loop. The --loop-profile A/B's own overhead headline
    # rides the standard value check when that entry runs.
    ("engine_device_frac", "low"),
    ("engine_accounting_frac", "high"),
)


def _dig(obj, dotted: str):
    for part in dotted.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return obj if isinstance(obj, (int, float)) \
        and not isinstance(obj, bool) else None


def load_round(path: str) -> dict | None:
    """One BENCH file → ``{"n", "rc", "value", "metric", "extra", "path"}``
    (None when unparseable — the gate reports it, not a traceback)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict):
        return None
    parsed = data.get("parsed") if isinstance(data.get("parsed"), dict) \
        else data if "metric" in data else {}
    n = data.get("n")
    if n is None:
        m = re.search(r"(\d+)", os.path.basename(path))
        n = int(m.group(1)) if m else 0
    return {
        "path": path,
        "n": int(n),
        "rc": int(data.get("rc", 0)),
        "metric": str(parsed.get("metric", "")),
        "value": float(parsed.get("value") or 0.0),
        "extra": parsed.get("extra") or {},
    }


def _median(vals: list[float]) -> float:
    srt = sorted(vals)
    mid = len(srt) // 2
    return srt[mid] if len(srt) % 2 else 0.5 * (srt[mid - 1] + srt[mid])


def gate(rounds: list[dict], threshold: float = DEFAULT_THRESHOLD) -> dict:
    """Diff the newest round against the prior trajectory. Baselines are
    per-field MEDIANS over the prior successful rounds (robust to one
    lucky/unlucky round)."""
    rounds = sorted(rounds, key=lambda r: r["n"])
    newest = rounds[-1]
    prior = [r for r in rounds[:-1] if r["rc"] == 0 and r["value"] > 0]
    failures: list[str] = []
    checks: list[dict] = []

    if newest["rc"] != 0:
        failures.append(
            f"newest round (n={newest['n']}) exited rc={newest['rc']} — "
            f"the run died before finishing (metric {newest['metric'] or 'none'!r})")
    if not prior:
        return {"ok": not failures, "failures": failures, "checks": checks,
                "newest_n": newest["n"], "history": 0,
                "note": "no successful prior rounds to gate against"}

    def check(name: str, new, base, direction: str) -> None:
        if new is None or base is None or base == 0:
            return
        ratio = new / base
        # shared direction semantics with the FlightRecorder watch: the
        # excursion is the relative move (ratio − 1); it only fails when
        # it is BOTH beyond the threshold AND in the bad direction
        bad = (abs(ratio - 1.0) > threshold
               and direction_violates(direction, ratio - 1.0))
        checks.append({"field": name, "new": new, "baseline": round(base, 4),
                       "ratio": round(ratio, 4), "ok": not bad})
        if bad:
            moved = "rose" if ratio > 1.0 else "dropped"
            failures.append(
                f"{name} {moved} beyond {threshold:.0%}: "
                f"{new:.4g} vs baseline {base:.4g} "
                f"(ratio {ratio:.3f})")

    if newest["rc"] == 0:
        base = _median([r["value"] for r in prior])
        if newest["value"] <= 0:
            # rc=0 with no headline number (a failed round that still exited 0):
            # the run "succeeded" but measured nothing — a regression
            failures.append(
                f"newest round (n={newest['n']}) recorded no headline "
                f"value (baseline {base:.4g})")
        else:
            check("value", newest["value"], base, "low")
    for path, direction in WATCHED_EXTRA:
        base_vals = [v for v in (_dig(r["extra"], path) for r in prior)
                     if v is not None]
        if not base_vals:
            continue
        check(f"extra.{path}", _dig(newest["extra"], path),
              _median(base_vals), direction)

    return {"ok": not failures, "failures": failures, "checks": checks,
            "newest_n": newest["n"], "history": len(prior)}


def find_rounds(dirpath: str) -> list[str]:
    return sorted(glob.glob(os.path.join(dirpath, "BENCH_*.json")))


def run(paths: list[str], threshold: float) -> tuple[int, dict]:
    rounds = []
    broken = []
    for p in paths:
        r = load_round(p)
        (rounds if r is not None else broken).append(r if r is not None else p)
    if not rounds:
        return 2, {"ok": False,
                   "failures": [f"no parseable BENCH rounds in {paths!r}"],
                   "checks": [], "history": 0}
    report = gate(rounds, threshold=threshold)
    if broken:
        report["unparseable"] = broken
    return (0 if report["ok"] else 1), report


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Fail when the newest BENCH round regresses vs the "
                    "trajectory")
    ap.add_argument("files", nargs="*",
                    help="BENCH json files (default: --dir/BENCH_*.json)")
    ap.add_argument("--dir", default=".", help="directory to glob")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="allowed relative regression (default 0.15)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON line")
    args = ap.parse_args(argv)
    paths = args.files or find_rounds(args.dir)
    if len(paths) < 1:
        print("bench_gate: no BENCH_*.json rounds found", file=sys.stderr)
        return 2
    code, report = run(paths, args.threshold)
    if args.json:
        print(json.dumps(report))
    else:
        for c in report["checks"]:
            mark = "ok  " if c["ok"] else "FAIL"
            print(f"[{mark}] {c['field']}: {c['new']:.4g} vs "
                  f"{c['baseline']:.4g} (x{c['ratio']:.3f})")
        for fmsg in report["failures"]:
            print(f"REGRESSION: {fmsg}")
        if report.get("note"):
            print(report["note"])
        print(f"bench_gate: {'OK' if report['ok'] else 'FAILED'} "
              f"(newest n={report.get('newest_n')}, "
              f"history {report['history']})")
    return code


if __name__ == "__main__":
    sys.exit(main())
