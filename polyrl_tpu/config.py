"""Config system: dataclass tree + YAML + dotted CLI overrides.

Equivalent of the reference's three config planes (SURVEY.md §5.6 / C18):
Hydra/OmegaConf trainer tree with CLI overrides (``ppo_stream_trainer.yaml``
composed over verl defaults, overridden in recipes), TOML for the
manager/fabric, env vars for point toggles. Hydra/OmegaConf are not in the
TPU image, so this is a self-contained equivalent: nested dataclasses are
the schema + defaults, a YAML file overlays them, and ``key.sub=value``
dotted CLI args overlay that (override order CLI > file > default, the
reference's order, config.rs:6).
"""

from __future__ import annotations

import dataclasses
import json
import typing
from dataclasses import dataclass, field
from typing import Any

from polyrl_tpu.rollout.autoscale import AutoscaleConfig
from polyrl_tpu.rollout.faults import FaultInjectionConfig
from polyrl_tpu.rollout.pool import PoolConfig
from polyrl_tpu.rollout.spotmarket import SpotMarketConfig
from polyrl_tpu.trainer.actor import ActorConfig
from polyrl_tpu.trainer.critic import CriticConfig
from polyrl_tpu.trainer.stream_trainer import TrainerConfig
from polyrl_tpu.transfer.agents import TransferConfig


@dataclass
class ModelSection:
    preset: str = "tiny"                  # any decoder.PRESETS key (tiny, qwen3-1.7b/8b, qwen2.5-0.5b/7b/32b, llama3-8b/70b)
    dtype: str = "bfloat16"
    # local HF checkpoint dir (config.json + safetensors): when set, the
    # architecture comes from the checkpoint's config.json and the weights
    # load pretrained instead of random-init (models/hf_loader.py)
    hf_path: str = ""
    # raw ModelConfig field overrides (vocab_size, num_layers, ...)
    overrides: dict = field(default_factory=dict)


@dataclass
class TokenizerSection:
    kind: str = "byte"                    # byte | hf
    name_or_path: str = ""                # hf repo/dir when kind == "hf"


@dataclass
class DataSection:
    train_path: str = "arithmetic"        # .jsonl/.parquet path, or "arithmetic"
    val_path: str = ""
    prompt_key: str = "prompt"
    shuffle: bool = True
    seed: int = 0
    arithmetic_size: int = 512            # synthetic task size


@dataclass
class RolloutSection:
    mode: str = "colocated"               # colocated | disaggregated
    backend: str = "cb"                   # cb (paged continuous batching) | step (bucketed)
    batch_buckets: tuple = ()             # step backend
    prompt_buckets: tuple = ()
    max_slots: int = 64                   # cb backend
    page_size: int = 64
    max_seq_len: int = 16384
    kv_cache_dtype: str = ""              # "" → model dtype
    # chunked prefill (cb backend): prompts longer than this prefill one
    # page-aligned chunk per engine iteration, interleaved with decode.
    # 0 = off (whole-prompt dispatches).
    prefill_chunk: int = 0
    # prompt-lookup speculative decoding (cb backend): N ngram-proposed
    # draft tokens verified per decode dispatch — up to N+1 tokens per
    # weight read, distribution-exact rejection sampling. 0 = off.
    spec_tokens: int = 0
    spec_rounds: int = 2                  # fused device-side rounds/dispatch
    # admission scheduler geometry (cb backend; ARCHITECTURE.md
    # "Group-shared prefill"): admit_wave = max admissions fused into one
    # batched prefill dispatch; admit_reorder_window = how many blocked
    # queue heads admission may skip past while forming a wave (0 =
    # strict FIFO head-of-line); group_share = prefill a GRPO group's
    # shared prompt once and batch-attach the siblings (False restores
    # per-request singleton suffix admission — the bench A/B baseline).
    admit_wave: int = 8
    admit_reorder_window: int = 8
    group_share: bool = True
    # shared-prefix decode attention (cb backend; ARCHITECTURE.md
    # "Shared-prefix decode attention"): decode dispatches with live GRPO
    # groups route through the two-phase grouped paged-attention kernel —
    # ONE HBM stream of the group's shared prompt KV serves all siblings
    # (phase 1), each slot's own suffix pages merge in via the flash LSE
    # (phase 2). False restores the per-slot kernel for every dispatch
    # (the --decode-attn A/B baseline; singletons always take that path).
    decode_group_share: bool = True
    # sibling-wait pre-ref expiry: how long a leader's pre-taken prefix
    # refs survive waiting for siblings that never arrive (dropped
    # groups, mis-sized hints) before the TTL sweep releases them
    group_preref_ttl_s: float = 30.0
    # KV memory plane (ARCHITECTURE.md "KV memory plane"): per-page
    # residency/lifetime ledger feeding the ``memory`` statusz section,
    # ``engine/kv_{hot,warm,cold}_page_frac`` gauges and HBM attribution.
    # False restores the pre-ledger engine, bit for bit.
    kv_ledger: bool = True
    # idle age (in decode dispatches since last touch) past which a
    # resident page counts as COLD (warm = a quarter of this)
    kv_cold_after_dispatches: int = 256
    # host-RAM KV spill tier (rollout/kvspill.py; ARCHITECTURE.md "KV
    # spill tier"): cold unreferenced published prefix-cache pages page
    # out of HBM into pinned host memory under watermark pressure and
    # restore on a prefix hit — sessions oversubscribe HBM instead of
    # losing their KV to eviction. Requires kv_ledger (candidate ranking
    # + reconciliation); kv_ledger=false disables the sweep entirely.
    kv_spill: bool = True
    # host-side capacity of the spill tier, in GB
    kv_spill_host_gb: float = 4.0
    # page-util watermarks with hysteresis: the sweep arms at >= high and
    # spills down toward low; the gap is what keeps demand restores from
    # re-arming the sweep page-by-page (spill/restore thrash)
    kv_spill_high_watermark: float = 0.92
    kv_spill_low_watermark: float = 0.80
    # engine-loop profiler (obs/engine_profile.py; ARCHITECTURE.md
    # "Engine-loop profiler"): per-iteration phase attribution of the CB
    # engine's loop wall behind the ``engine.loop`` statusz block,
    # ``engine/device_frac`` / ``engine/accounting_frac`` gauges and
    # tools/engine_report.py. False restores the pre-profiler engine,
    # bit for bit.
    loop_profile: bool = True
    # disaggregated plumbing (reference rollout_manager.{port,endpoint},
    # workers/config/rollout.py:95-101)
    manager_endpoint: str = ""            # "" → spawn the C++ manager locally
    manager_args: tuple = ()              # extra CLI args for the spawned manager
    # control-plane fault tolerance (ARCHITECTURE.md "Fault-tolerance
    # layers"): a locally spawned manager runs under a ManagerSupervisor
    # that respawns it with exponential backoff (base doubling to max) and
    # replays registered instances/senders/weight version via /reconcile
    manager_respawn_backoff_s: float = 0.5
    manager_respawn_backoff_max_s: float = 10.0
    # mid-stream transport failures re-issue only the unfinished rids, at
    # most resume_budget times per batch, waiting up to resume_wait_s each
    # time for the manager to come back; past the budget a colocated local
    # engine finishes the batch, else ControlPlaneDown surfaces
    resume_budget: int = 3
    resume_wait_s: float = 60.0
    # token-level continuous generation (ARCHITECTURE.md "Token-level
    # continuous generation"): aborts/preemptions/shutdowns flush partials
    # instead of dropping decoded tokens, the manager forwards per-token
    # progress, and a mid-stream resume re-issues only the SUFFIX
    # (prompt+salvaged re-prefilled, budget decremented) with the stitched
    # sequence re-decoding nothing. False reverts to from-token-0 resume.
    salvage_partials: bool = True
    # fault-injection harness (rollout/faults.py): kill-after-N-tokens,
    # chunk corruption, stalls, /drain triggers, and worst-moment manager
    # stream kills — for chaos tests and `bench.py --chaos`
    fault_injection: FaultInjectionConfig = field(
        default_factory=FaultInjectionConfig)
    transfer_streams: int = 4
    advertise_host: str = "127.0.0.1"
    # multi-NIC weight push (transfer/nic.py): >1 runs one sender agent per
    # CIDR-picked local interface and the manager partitions the pool across
    # them (reference 4 groups × 8 engines, config.toml:19-20)
    sender_groups: int = 1
    sender_nic_cidr: str = ""             # e.g. "10.128.0.0/16,10.129.0.0/16"
    groups_per_sender: int = 1            # manager-side instance sharding
    # hybrid colocated + remote: ALSO serve generation from an in-process
    # engine registered as a LOCAL (time-sliced) instance — the manager
    # aborts it after the balancer's local window and the engine yields its
    # KV HBM back to training (reference sglang_http_async_engine.py:102-113
    # + handlers.rs:500-513)
    colocated_local: bool = False
    # elastic pool (rollout/pool.py; ARCHITECTURE.md "Elastic pool"):
    # fleet membership lifecycle on top of the manager — scale-up join
    # gating, preemption drills, membership sweeps for /statusz, and the
    # progressive train<->rollout balance estimator window
    pool: PoolConfig = field(default_factory=PoolConfig)
    # closed-loop autoscaling (rollout/autoscale.py; ARCHITECTURE.md
    # "Closed-loop autoscaling & degradation tiers"): the policy loop
    # that ACTS on the balance trends + critpath bottleneck — PoolManager
    # add/drain under hysteresis, cooldowns, a fleet envelope, and a rate
    # limiter. Default OFF: the serial trainer stays bitwise pre-PR.
    autoscale: AutoscaleConfig = field(default_factory=AutoscaleConfig)
    # trace-driven spot-market chaos harness (rollout/spotmarket.py):
    # scripted capacity offers / preemption notices / no-notice kills
    # replayed against the pool — the controller's CapacityProvider
    spot_market: SpotMarketConfig = field(default_factory=SpotMarketConfig)


@dataclass
class ParallelSection:
    """Mesh axes for the trainer's GSPMD sharding (parallel/mesh.py). With
    every axis 1, a single process and no device list, no mesh is built
    (single-chip path).
    Multi-host runs (jax.distributed via JAX_COORDINATOR_ADDRESS et al.)
    always build the mesh over the global device set."""
    dp: int = 1
    fsdp: int = 1                         # -1 absorbs remaining devices
    tp: int = 1
    sp: int = 1
    pp: int = 1                           # pipeline parallel (layer stages)
    pp_microbatches: int = 0              # GPipe microbatches (0 → 2·pp)
    ep: int = 1                           # expert parallel (MoE expert axis)
    # sequence-parallel attention flavor when sp > 1 (parallel/sequence.py):
    # ulysses (head all-to-all) | ring (KV ppermute) | dense (GSPMD decides)
    sp_mode: str = "ulysses"
    # indices into jax.devices() the trainer's mesh is built over; () =
    # all of them. One process can hold the trainer and rollout engines on
    # separate chips (rollout.serve's device list is the other half); a
    # list here builds the mesh even when every axis is 1.
    devices: tuple = ()


@dataclass
class RewardSection:
    manager: str = "naive"
    custom_score_path: str = ""           # python file defining compute_score
    num_workers: int = 8
    # remote sandbox-service code execution (rewards/sandbox.py; reference
    # sandbox_fusion, reward.py:95-150). Empty url = local rlimit sandbox.
    sandbox_url: str = ""
    sandbox_max_concurrent: int = 64
    sandbox_timeout_s: float = 30.0
    sandbox_memory_limit_mb: int = 1024


@dataclass
class LoggingSection:
    backends: tuple = ("console",)        # console | jsonl | tensorboard
    path: str = ""                        # jsonl path / tensorboard dir


@dataclass
class ObsSection:
    """Observability knobs (ARCHITECTURE.md "Observability" + "Goodput &
    health plane"): span tracing with cross-process propagation + Perfetto
    export, the per-step manager /metrics scrape, the /statusz health
    exporter, and the anomaly flight recorder."""
    trace: bool = False                   # span tracer on/off
    trace_dir: str = ""                   # spans.jsonl + trace.json dump dir
    trace_buffer: int = 4096              # ring-buffer span capacity
    # live health plane: the trainer serves GET /statusz (shared schema
    # with the rollout server's route — obs/statusz.py). port 0 = ephemeral
    statusz: bool = False
    statusz_host: str = "127.0.0.1"
    statusz_port: int = 0
    # anomaly flight recorder (obs/recorder.py): EWMA/z-score detection
    # over step time + rollout throughput; dumps post-mortem bundles
    # (trace ring, last N step records, thread stacks, fault counters)
    # into recorder_dir on anomaly/crash/SIGTERM
    recorder: bool = False
    recorder_dir: str = ""                # "" -> next to logging.path
    recorder_keep_steps: int = 64         # step records per bundle
    recorder_z: float = 4.0               # z-score anomaly threshold
    recorder_warmup: int = 5              # steps before detection arms
    recorder_max_bundles: int = 4         # bundle budget per run
    # training health plane (obs/rlhealth.py): per-step RL-dynamics
    # ledger — training/* distributions + group diagnostics in every step
    # record, the /statusz training section, and training.json in
    # post-mortem bundles. Default ON (host-side numpy over arrays the
    # step already computed; no device work).
    rlhealth: bool = True
    rlhealth_tail: int = 64               # per-step rows kept for bundles
    rlhealth_group_rows: int = 64         # group-table rows per step


@dataclass
class RunConfig:
    model: ModelSection = field(default_factory=ModelSection)
    tokenizer: TokenizerSection = field(default_factory=TokenizerSection)
    data: DataSection = field(default_factory=DataSection)
    rollout: RolloutSection = field(default_factory=RolloutSection)
    # weight-push fabric supervision (transfer/agents.py TransferConfig;
    # ARCHITECTURE.md "Weight-fabric fault tolerance"): bandwidth-keyed
    # push deadlines, verify/resume toggle, retry budget + backoff, and
    # the transfer-plane fault injector — knobs echoed in step records
    # via the transfer/* gauges
    transfer: TransferConfig = field(default_factory=TransferConfig)
    parallel: ParallelSection = field(default_factory=ParallelSection)
    reward: RewardSection = field(default_factory=RewardSection)
    trainer: TrainerConfig = field(default_factory=TrainerConfig)
    actor: ActorConfig = field(default_factory=ActorConfig)
    critic: CriticConfig = field(default_factory=CriticConfig)
    logging: LoggingSection = field(default_factory=LoggingSection)
    obs: ObsSection = field(default_factory=ObsSection)


# -- dict ⇄ dataclass -------------------------------------------------------


def _build(cls, data: dict):
    """Construct dataclass ``cls`` from a (possibly partial) dict, recursing
    into dataclass-typed fields. Unknown keys raise (typo protection)."""
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(data) - set(fields)
    if unknown:
        raise KeyError(f"unknown config keys for {cls.__name__}: {sorted(unknown)}")
    kwargs = {}
    for name, value in data.items():
        ftype = fields[name].type
        resolved = _resolve_type(cls, ftype)
        if dataclasses.is_dataclass(resolved) and isinstance(value, dict):
            kwargs[name] = _build(resolved, value)
        elif resolved is tuple or typing.get_origin(resolved) is tuple:
            kwargs[name] = tuple(value) if isinstance(value, (list, tuple)) else (value,)
        else:
            kwargs[name] = value
    return cls(**kwargs)


def _resolve_type(cls, ftype):
    """Field types are strings under ``from __future__ import annotations``."""
    if isinstance(ftype, str):
        hints = typing.get_type_hints(cls)
        # get_type_hints resolves the whole class; cache-free but configs are tiny
        for f in dataclasses.fields(cls):
            if f.type == ftype and f.name in hints:
                return hints[f.name]
        return str
    return ftype


def to_dict(cfg: Any) -> dict:
    d = dataclasses.asdict(cfg)

    def clean(x):
        if isinstance(x, dict):
            return {k: clean(v) for k, v in x.items()}
        if isinstance(x, tuple):
            return list(x)
        return x

    return clean(d)


# -- overrides --------------------------------------------------------------


def _coerce(text: str, current: Any) -> Any:
    """Parse a CLI string by the type of the value it replaces."""
    if isinstance(current, bool):
        if text.lower() in ("true", "1", "yes"):
            return True
        if text.lower() in ("false", "0", "no"):
            return False
        raise ValueError(f"not a bool: {text!r}")
    if isinstance(current, int) and not isinstance(current, bool):
        return int(text)
    if isinstance(current, float):
        return float(text)
    if isinstance(current, tuple):
        text = text.strip()
        if text[:1] == "[" and text[-1:] == "]":  # accept [8,16] list syntax
            text = text[1:-1]
        if not text:
            return ()
        items = [t.strip() for t in text.split(",") if t.strip()]
        conv = int if all(i.lstrip("-").isdigit() for i in items) else str
        return tuple(conv(i) for i in items)
    if isinstance(current, dict):
        return json.loads(text)
    if current is None:
        # str|None fields: "null" keeps None, anything else becomes str
        if text.lower() in ("null", "none", ""):
            return None
        for conv in (int, float):
            try:
                return conv(text)
            except ValueError:
                pass
        return text
    return text


def _set_path(obj: Any, parts: list[str], raw: str, full: str) -> Any:
    """Return ``obj`` with the dotted path set; frozen dataclasses are
    rebuilt via ``dataclasses.replace`` instead of mutated."""
    name = parts[0]
    if not dataclasses.is_dataclass(obj) or not hasattr(obj, name):
        raise KeyError(f"no config field {name!r} in {full!r}")
    cur = getattr(obj, name)
    new = _coerce(raw, cur) if len(parts) == 1 else _set_path(cur, parts[1:], raw, full)
    try:
        setattr(obj, name, new)
        return obj
    except dataclasses.FrozenInstanceError:
        return dataclasses.replace(obj, **{name: new})


def apply_overrides(cfg: RunConfig, overrides: list[str]) -> RunConfig:
    """``a.b.c=value`` dotted assignments, validated against the schema."""
    for ov in overrides:
        if "=" not in ov:
            raise ValueError(f"override must be key=value, got {ov!r}")
        key, _, raw = ov.partition("=")
        cfg = _set_path(cfg, key.strip().split("."), raw, key)
    return cfg


def load_config(path: str | None = None,
                overrides: list[str] | None = None) -> RunConfig:
    """YAML file (optional) overlaid on defaults, then dotted overrides.
    TrainerConfig validation (__post_init__ divisibility, the reference's
    main_stream.py:372-389 checks) re-runs on the final values."""
    data: dict = {}
    if path:
        import yaml

        with open(path) as f:
            data = yaml.safe_load(f) or {}
    cfg = _build(RunConfig, data)
    if overrides:
        cfg = apply_overrides(cfg, overrides)
    # re-validate trainer arithmetic after overrides mutated fields
    cfg.trainer.__post_init__()
    return cfg
