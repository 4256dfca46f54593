#!/usr/bin/env python3
"""Lint literal metric keys against the ``area/name`` naming convention.

Convention (ARCHITECTURE.md "Observability"): every step-record metric key
is ``area/name`` — lowercase ``[a-z0-9_]`` segments joined by ``/`` (later
segments may also contain ``.``), i.e. ``^[a-z0-9_]+(/[a-z0-9_.]+)+$``.
A key that breaks the convention fragments dashboards and defeats the
``manager/*`` / ``fault/*`` / ``timing_s/*`` prefix grouping.

Static coverage (AST, literals only — dynamic keys can't be checked):

- first string argument of the metric APIs ``observe``/``incr``
  (full-key check) and ``add_timing``/``marked_timer`` (checked with the
  ``timing_s/`` prefix they are emitted under);
- literal string keys containing ``/`` in dicts passed to
  ``.update(...)`` / ``.update_gauge(...)`` / ``.log(...)`` calls;
- literal string keys containing ``/`` in any dict literal with two or
  more such keys (metric-dict heuristic — catches returned metric dicts
  like ``fault_counters``);
- the literal head of f-string keys in the above positions (prefix check).

Covered key families include the pipelined trainer's ``perf/pipeline_*``
(``perf/pipeline_overlap_s``, ``perf/pipeline_queue_depth``),
``perf/weight_staleness`` and the bounded-staleness admission-gate
``perf/staleness_*`` gauges (``perf/staleness_lag`` — in-flight pushes at
stream start, ``perf/staleness_limit`` — the configured bound echo,
``perf/staleness_gate_wait_s`` — time blocked on the gate) plus the
``actor/tis_*`` correction metrics (trainer/pipeline.py,
stream_trainer.py); the mixed-version TIS breakdown
``training/tis_unknown_version_tokens`` (masked tokens excluded from
correction because their sampling version is unknown) and the
per-version-lag ``training/tis_weight_mean/lag<k>`` /
``training/tis_clip_frac/lag<k>`` gauges (obs/rlhealth.py); the token-level
salvage counters — ``fault/tokens_salvaged``, ``fault/suffix_resumes``,
``fault/resume_prefill_tokens`` (rollout/remote.py ``fault_counters``)
and the injector's ``fault/injected_*`` (rollout/faults.py ``counters``)
— and the goodput/health plane's ``goodput/*`` phase attribution plus the
``obs/*`` self-telemetry (``obs/scrape_failed``, ``obs/scrape_partial`` —
sample-looking /metrics lines that failed to parse — ``obs/anomalies``,
``obs/bundles``, ``obs/log_errors``) and the scrape-latency histogram
``manager/scrape_s``. The critical-path plane (obs/critical_path.py)
emits ``critpath/*`` — ``critpath/bottleneck`` (segment index),
``critpath/bottleneck_frac``, per-segment ``critpath/<seg>_frac``
critical-time fractions, ``critpath/slack_s`` and the 10%-speedup
``critpath/headroom_s``. The engine flight deck
(rollout/flightdeck.py) emits ``engine/*`` — per-request lifecycle
distributions (``engine/ttft_s``, ``engine/tpot_s``,
``engine/queue_wait_s``, ``engine/prefill_s``) into the global histogram
registry and fleet aggregates (``engine/occupancy``, ``engine/page_util``,
``engine/ttft_p95_s``, ...) via PoolManager.counters — including the
shared-prefix decode-attention KV-read ledger:
``engine/kv_read_pages_per_token`` (HBM pages the decode kernels actually
stream per decoded token) and ``engine/shared_prefix_read_frac`` (the
fraction of logically-attended pages the grouped prefix phase
deduplicated), fed per engine from ``EngineFlightDeck.on_kv_read`` via
``server_info`` and aggregated fleet-wide in ``rollout/pool.py``.
The engine-loop profiler (obs/engine_profile.py) extends the same
``engine/*`` namespace with the windowed device-vs-host split —
``engine/device_frac`` (share of loop wall with device work outstanding,
by the engine's completion stamps; fleet MIN: the engine whose chip
waits most), ``engine/accounting_frac`` (fleet MAX: the worst
deck/ledger/spill bookkeeping share), ``engine/host_overhead_frac``
(loop wall outside its two waits) and ``engine/loop_attributed_frac`` —
riding the flat ``server_info`` fields the manager forwards per
instance, plus the balancer-side ``pool/balance_device_frac`` windowed
median. The same fields carry the cumulative counters that
``obs/engine_profile.py::CUMULATIVE_KEYS`` declares (with the server's
stream counters: ``statusz.CUMULATIVE_INFO_KEYS``); the numeric ones the
server's time-series feed also lands as ``engine/<key>``, so this lint
holds each declared key to the flat form (:func:`check_flat_keys`).
The training health
plane (obs/rlhealth.py) emits ``training/*`` — distribution summaries
(``training/adv_abs``, ``training/tis_weight``, ``training/staleness``,
...), GRPO group diagnostics (``training/degenerate_group_frac``,
``training/effective_batch_frac``), per-source reward gauges
(``training/reward_mean/<src>``) and actor mirrors
(``training/{entropy,approx_kl,grad_norm}``) — sharing the pre-existing
``training`` namespace with the trainer's step counter and balancer
budget. The sharded weight fabric (transfer/agents.py ``counters``)
emits ``transfer/push_streams`` (stream fan-out width of the last
round), ``transfer/stream_bw_mbps_min`` (slowest stream's wire
bandwidth — the round's critical stream), ``transfer/reshard_bytes``
(cumulative bytes routed shard→shard by the resharding map) and
``transfer/stream_resumes`` (per-stream transport-failure re-pushes,
distinct from whole-round ``transfer/push_retries``). The KV memory
plane (rollout/kvledger.py) emits ``memory/*`` — the ledger↔pool
reconciliation ratio ``memory/attributed_frac``, churn counters
(``memory/page_allocs``, ``memory/page_frees``, ``memory/page_publishes``)
and the per-cause free split ``memory/freed_<cause>`` — alongside the
``engine/kv_{hot,warm,cold}_page_frac`` residency tiers and
``engine/hbm_{used,headroom,unaccounted}_gb`` HBM-truth gauges, all
riding ``server_info`` and aggregated fleet-wide in rollout/pool.py
(worst-case: max cold fraction, min headroom). The host-RAM KV spill
tier (rollout/kvspill.py) extends the same namespace with
``memory/spilled_pages`` (current host-resident pages),
``memory/{pages_spilled,pages_restored,spill_drops}`` (cumulative
spill/restore/drop traffic) and ``memory/{spill,restore}_bytes``,
next to the ``engine/kv_spilled_frac`` + ``engine/kv_restore_rate``
gauges the manager forwards per instance. New metric
emitters in
``polyrl_tpu/`` are linted automatically; nothing needs registering —
EXCEPT a new top-level namespace, which must be added to ``NAMESPACES``
below and documented in ARCHITECTURE.md in the same change (an
emitted-but-undocumented namespace fails the lint).

Run: ``python tools/check_metric_names.py [root ...]`` — exits 1 and lists
violations. Wired into the quick test tier (tests/test_obs_tracing.py).
"""

from __future__ import annotations

import ast
import os
import re
import sys

KEY_RE = re.compile(r"^[a-z0-9_]+(/[a-z0-9_.]+)+$")
# a literal f-string head like "timing_s/" must be a valid key prefix
PREFIX_RE = re.compile(r"^[a-z0-9_]+(/[a-z0-9_.]*)*$")

# Documented metric namespaces — the leading ``/``-segment of every
# literal key (ARCHITECTURE.md "Observability" table). Adding a namespace
# here without documenting it there defeats the point of the lint.
NAMESPACES = frozenset({
    "actor",         # policy losses / entropy / TIS correction
    "critic",        # value losses / KL
    "reward",        # reward manager scores + REMAX baselines
    "val",           # validation scores
    "perf",          # step wall / throughput / MFU / pipeline gauges
    "goodput",       # per-step wall-time phase attribution (obs/goodput.py)
    "training",      # step counter / balancer budget + the training
                     # health plane: RL-dynamics distributions, GRPO
                     # group diagnostics, staleness (obs/rlhealth.py)
    "fault",         # control-plane + salvage fault counters
    "manager",       # scraped manager gauges + client RTT
    "pool",          # elastic-pool membership + balance estimator gauges
    "engine",        # engine flight deck: occupancy / TTFT / TPOT /
                     # page-pool + fleet aggregates (rollout/flightdeck.py)
    "rollout",       # rollout-plane latency/throughput distributions
    "transfer",      # weight-fabric pack/push timings + supervision
                     # gauges (transfer/{push_failures,push_retries,
                     # verify_failures,resumed_bytes,rounds_verified,
                     # laggard_escalations,catchup_pushes}, the sharded-
                     # push plane transfer/{push_streams,stream_bw_mbps_
                     # min,reshard_bytes,stream_resumes}, and the
                     # min_bandwidth_mbps/retry_budget knob echo —
                     # transfer/agents.py, ARCHITECTURE.md "Sharded
                     # weight fabric")
    "prefix_cache",  # engine prefix-cache hit telemetry
    "timing_s",      # marked_timer phase timings
    "obs",           # observability self-telemetry (scrape/log/anomaly/
                     # partial-parse counters)
    "critpath",      # per-step critical-path attribution: bottleneck
                     # segment, per-segment critical fractions, slack and
                     # 10%-speedup headroom (obs/critical_path.py)
    "autoscale",     # closed-loop autoscaling: per-tick decision gauges
                     # (action/reason/suppressions), action totals, the
                     # degradation tier, and the admission-gate wait
                     # (rollout/autoscale.py)
    "memory",        # KV memory plane: ledger reconciliation
                     # (memory/attributed_frac), page churn + free-cause
                     # counters, and the host-RAM spill tier's
                     # memory/{spilled_pages,pages_spilled,pages_restored,
                     # spill_drops,spill_bytes,restore_bytes} — riding
                     # server_info next to the engine/kv_{hot,warm,cold}_
                     # page_frac residency tiers, HBM truth gauges, and
                     # engine/{kv_spilled_frac,kv_restore_rate}
                     # (rollout/kvledger.py, rollout/kvspill.py)
})

# APIs whose first positional string argument IS a metric key
_FULL_KEY_APIS = {"observe", "incr"}
# APIs whose first argument is emitted under the timing_s/ prefix
_TIMING_APIS = {"add_timing", "marked_timer"}
# APIs taking a metrics dict as the first argument
_DICT_APIS = {"update", "update_gauge", "log"}


def _check_key(key: str, where: str, violations: list[str]) -> None:
    if not KEY_RE.match(key):
        violations.append(f"{where}: metric key {key!r} does not match "
                          f"{KEY_RE.pattern}")
        return
    ns = key.split("/", 1)[0]
    if ns not in NAMESPACES:
        violations.append(
            f"{where}: metric key {key!r} uses undocumented namespace "
            f"{ns!r} — add it to NAMESPACES (tools/check_metric_names.py) "
            f"AND the ARCHITECTURE.md Observability table")


def _check_fstring_head(node: ast.JoinedStr, where: str,
                        violations: list[str]) -> None:
    if not node.values or not isinstance(node.values[0], ast.Constant):
        return  # no literal head to check
    head = node.values[0].value
    if not isinstance(head, str) or not head:
        return
    if not PREFIX_RE.match(head):
        violations.append(f"{where}: metric key prefix {head!r} does not "
                          f"match {PREFIX_RE.pattern}")
        return
    if "/" in head and head.split("/", 1)[0] not in NAMESPACES:
        violations.append(
            f"{where}: metric key prefix {head!r} uses undocumented "
            f"namespace {head.split('/', 1)[0]!r} — add it to NAMESPACES "
            f"AND the ARCHITECTURE.md Observability table")


def _dict_slash_keys(node: ast.Dict):
    for key in node.keys:
        if isinstance(key, ast.Constant) and isinstance(key.value, str) \
                and "/" in key.value:
            yield key.value


def check_file(path: str) -> list[str]:
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as exc:
        return [f"{path}: syntax error: {exc}"]
    violations: list[str] = []
    metric_dicts: set[int] = set()

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args:
            name = (node.func.attr if isinstance(node.func, ast.Attribute)
                    else node.func.id if isinstance(node.func, ast.Name)
                    else "")
            arg0 = node.args[0]
            where = f"{path}:{node.lineno}"
            if name in _FULL_KEY_APIS or name in _TIMING_APIS:
                prefix = "timing_s/" if name in _TIMING_APIS else ""
                if isinstance(arg0, ast.Constant) and isinstance(arg0.value, str):
                    _check_key(prefix + arg0.value, where, violations)
                elif isinstance(arg0, ast.JoinedStr) and not prefix:
                    _check_fstring_head(arg0, where, violations)
            elif name in _DICT_APIS and isinstance(arg0, ast.Dict):
                metric_dicts.add(id(arg0))
                for key in _dict_slash_keys(arg0):
                    _check_key(key, where, violations)
                for key in arg0.keys:
                    if isinstance(key, ast.JoinedStr):
                        _check_fstring_head(key, where, violations)
        elif isinstance(node, ast.Dict) and id(node) not in metric_dicts:
            # metric-dict heuristic: >= 2 literal slash keys
            keys = list(_dict_slash_keys(node))
            if len(keys) >= 2:
                for key in keys:
                    _check_key(key, f"{path}:{node.lineno}", violations)
    return violations


def check_flat_keys() -> list[str]:
    """The declared cumulative ``server_info`` keys are flat: no ``/`` (the
    C++ manager's poller indexes them bare and the time-series feed
    prefixes ``engine/``), lower case, declared once."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from polyrl_tpu.obs.statusz import CUMULATIVE_INFO_KEYS, STREAM_INFO_KEYS
    from polyrl_tpu.obs.engine_profile import CUMULATIVE_KEYS

    declared = CUMULATIVE_KEYS + STREAM_INFO_KEYS
    violations = [f"cumulative server_info key {k!r} is not flat "
                  f"([a-z0-9_]+)" for k in sorted(CUMULATIVE_INFO_KEYS)
                  if not re.fullmatch(r"[a-z0-9_]+", k)]
    violations += [f"cumulative server_info key {k!r} is declared twice"
                   for k in sorted(set(declared))
                   if declared.count(k) > 1]
    return violations


def check_tree(roots: list[str]) -> list[str]:
    violations: list[str] = []
    for root in roots:
        if os.path.isfile(root):
            violations += check_file(root)
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    violations += check_file(os.path.join(dirpath, fn))
    return violations


def default_roots() -> list[str]:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return [os.path.join(repo, "polyrl_tpu"),
            os.path.join(repo, "tools")]


def main(argv: list[str] | None = None) -> int:
    roots = (argv if argv else default_roots())
    violations = check_tree(roots) + check_flat_keys()
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} metric-name violations", file=sys.stderr)
        return 1
    print("metric names ok")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or None))
