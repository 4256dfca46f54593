"""StreamRLTrainer — the streaming PPO/GRPO fit loop.

TPU-native equivalent of the reference's C2 ``StreamRayPPOTrainer.fit``
(``stream_ray_trainer.py:282-707``): per training batch, rollout responses
arrive as micro-batches ("ibatches") of at least ``min_stream_batch_size``;
each ibatch flows reward → old_logprob → ref_logprob → values → advantage,
then actor/critic fwd/bwd with gradient accumulation; the optimizer steps at
cumulative minibatch boundaries (reference :500-568); weights push to the
rollout engine after each step (:571-575); metrics feed the balancer
(:691-704).

Two rollout modes behind one loop:
- **colocated** (reference ``main_ppo`` baseline, SURVEY.md §3.5): an
  in-process engine generates the full batch, then ibatches are slices.
- **disaggregated streaming** (the reference's headline mode): a
  ``RemoteRollout`` yields group-complete ibatches while later groups are
  still generating on the elastic pool — training overlaps generation, the
  trainer-bubble time is measured and fed to the manager's adaptive
  balancer, which returns the next local-generation budget
  (stream_ray_trainer.py:691-704 ⇄ handlers.rs:867-901).
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import time
from typing import Any, Callable

import jax
import numpy as np

from polyrl_tpu import obs
from polyrl_tpu.data.batch import TensorBatch
from polyrl_tpu.models import decoder
from polyrl_tpu.ops import core_algos
from polyrl_tpu.rollout.engine import RolloutEngine
from polyrl_tpu.rollout.remote import RemoteRollout
from polyrl_tpu.rollout.sampling import SamplingParams
from polyrl_tpu.trainer.actor import ActorConfig, ReferencePolicy, StreamActor
from polyrl_tpu.trainer.critic import CriticConfig, StreamCritic
from polyrl_tpu.utils import checkpoint as ckpt_lib
from polyrl_tpu.utils.flops import FlopsCounter, peak_tflops
from polyrl_tpu.utils.metrics import MetricsTracker, marked_timer

log = logging.getLogger(__name__)


class _ResultView:
    """Adapt a manager GenerateResult or a CBEngine output dict to the
    engine-output field names the assembly code consumes. Per-token
    ``weight_versions`` (which push version sampled each token — the
    training health ledger's staleness feed) ride along when the source
    carries them; an empty array means "unknown" and the assembled batch
    marks those tokens −1."""

    __slots__ = ("output_ids", "output_token_logprobs",
                 "output_token_weight_versions")

    def __init__(self, res):
        if isinstance(res, dict):
            ids, lps = res["token_ids"], res["logprobs"]
            wvs = res.get("weight_versions") or []
        else:
            ids, lps = res.output_token_ids, res.output_token_logprobs
            wvs = res.output_token_weight_versions or []
        self.output_ids = np.asarray(ids, np.int32)
        self.output_token_logprobs = np.asarray(lps, np.float32)
        self.output_token_weight_versions = np.asarray(wvs, np.int32)


@dataclasses.dataclass
class TrainerConfig:
    # batch accounting (reference names kept: SURVEY.md C1 batch checks)
    train_batch_size: int = 32            # prompts per step
    rollout_n: int = 4                    # samples per prompt
    ppo_mini_batch_size: int = 64         # trajectories per optimizer step
    micro_batch_size: int = 8             # trajectories per fwd/bwd
    min_stream_batch_size: int = 16       # ibatch granularity
    # lengths
    max_prompt_length: int = 128
    max_response_length: int = 128
    # packed-sequence (remove-padding) training + token-balanced micros
    # (reference use_remove_padding stream_dp_actor.py:41-47 and
    # prepare_dynamic_batch :35,136; recipe 16,384 tok/GPU): actor passes run
    # on fixed [n_rows, pack_len] packed grids instead of [B, Tp+Tr] pads
    use_remove_padding: bool = False
    pack_len: int = 0                     # 0 → max_prompt+max_response
    micro_token_budget: int = 0           # 0 → micro_batch_size rows
    # algorithm
    adv_estimator: str = "grpo"           # grpo | gae | rloo | reinforce_plus_plus | remax
    gamma: float = 1.0
    lam: float = 1.0
    use_kl_in_reward: bool = False
    kl_coef: float = 0.001
    kl_penalty: str = "kl"
    norm_adv_by_std_in_grpo: bool = True
    # weight push payload: "full" pushes the merged/plain tree;
    # "lora_delta" pushes ONLY the LoRA adapters (requires
    # actor.lora_rank > 0 and rollout workers serving --lora-rank) —
    # ~rank/hidden of the bytes per sync
    weight_sync: str = "full"
    # pipelined rollout (trainer/pipeline.py; ARCHITECTURE.md "Pipeline
    # overlap"): 0 = the serial loop, bitwise-identical to the pre-pipeline
    # behavior; N >= 1 lets a background lane generate up to N steps ahead
    # of training — rollouts then arrive weight-version stale
    # (see rollout_is_correction) and the per-step weight push goes async
    pipeline_depth: int = 0
    # bounded-staleness admission gate (ARCHITECTURE.md "Bounded-staleness
    # async training"): a prefetched stream may START while up to
    # staleness_limit-1 weight pushes are still in flight — i.e. against
    # any weight version within staleness_limit of the trainer's current
    # push version; only breaching the bound blocks the lane. 1 (default)
    # = the hard wait_pushed() fence (every push fully landed before the
    # next stream — the PR-3 pipeline, bitwise). >1 lets pushes overlap
    # generation MID-STREAM (the verify-before-install fabric makes a
    # half-landed push unobservable), so sequences legitimately span
    # versions and rollout_is_correction (REQUIRED then) applies
    # mixed-version per-token TIS keyed off rollout_weight_versions.
    staleness_limit: int = 1
    # truncated importance-sampling correction for stale rollouts: scale
    # advantages by min(exp(old_log_probs - rollout_log_probs),
    # rollout_is_cap) per token, keyed off each token's own behavior
    # version; unknown-version tokens (rollout_weight_versions == -1) are
    # excluded — weight 1.0 — and counted in
    # training/tis_unknown_version_tokens
    # (core_algos.mixed_version_importance_weights)
    rollout_is_correction: bool = False
    rollout_is_cap: float = 2.0
    # run
    total_steps: int = 10
    seed: int = 0
    # profiling (reference step-scoped profiling + nsight options,
    # SURVEY.md §5.1; TPU equivalent = jax.profiler traces)
    profile_steps: tuple = ()             # 1-based global steps to trace
    profile_dir: str = "/tmp/polyrl_profile"
    # validation (reference _validate + test_freq/val_before_train gates,
    # stream_ray_trainer.py:304-315,589-603; sample dump :585-587)
    test_freq: int = 0                    # validate every N steps (0 = off)
    val_before_train: bool = False
    val_temperature: float = 0.0          # greedy by default
    val_max_response_length: int = 0      # 0 → max_response_length
    rollout_data_dir: str = ""            # dump val generations as jsonl
    val_generations_to_log: int = 0       # echo first K generations to logger
    # checkpoint/resume (reference _save_checkpoint gating,
    # stream_ray_trainer.py:604-623; SURVEY.md §5.4)
    ckpt_dir: str | None = None
    save_freq: int = 0                    # 0 = only last step (+ESI)
    max_ckpt_keep: int = 3
    resume: str = "auto"                  # auto | disable
    esi_margin_s: float = 300.0
    # sampling
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0

    def __post_init__(self):
        if self.weight_sync not in ("full", "lora_delta"):
            raise ValueError(
                f"weight_sync must be 'full' or 'lora_delta', got "
                f"{self.weight_sync!r}")
        total = self.train_batch_size * self.rollout_n
        if total % self.ppo_mini_batch_size != 0:
            raise ValueError(
                f"total trajectories {total} not divisible by ppo_mini_batch_size"
                f" {self.ppo_mini_batch_size} (reference check main_stream.py:372-389)"
            )
        if self.ppo_mini_batch_size % self.micro_batch_size != 0:
            raise ValueError("mini batch not divisible by micro batch")
        if self.min_stream_batch_size % self.micro_batch_size != 0:
            raise ValueError("stream batch not divisible by micro batch")
        if self.pipeline_depth < 0:
            raise ValueError(
                f"pipeline_depth must be >= 0, got {self.pipeline_depth}")
        if self.staleness_limit < 1:
            raise ValueError(
                f"staleness_limit must be >= 1, got {self.staleness_limit}")
        if self.staleness_limit > 1 and self.pipeline_depth == 0:
            raise ValueError(
                f"staleness_limit={self.staleness_limit} requires the "
                f"pipelined trainer (pipeline_depth >= 1): the serial loop "
                f"has no async push to bound")
        if self.staleness_limit > 1 and not self.rollout_is_correction:
            # k>1 trains k versions off-policy; uncorrected that is
            # silently wrong, not a log line (the depth>0/limit=1 case
            # stays a warning — one version stale is the classic
            # one-step-off-policy regime)
            raise ValueError(
                f"staleness_limit={self.staleness_limit} without "
                f"rollout_is_correction: bounded-staleness rollouts train "
                f"up to {self.staleness_limit} weight versions off-policy "
                f"and MUST be importance-corrected — set "
                f"trainer.rollout_is_correction=true (and rollout_is_cap)")
        if self.rollout_is_cap <= 0:
            raise ValueError(
                f"rollout_is_cap must be > 0, got {self.rollout_is_cap}")
        if self.adv_estimator in ("grpo", "rloo") and (
            self.min_stream_batch_size % self.rollout_n != 0
        ):
            raise ValueError(
                "min_stream_batch_size must be a multiple of rollout_n so prompt"
                " groups are never split across ibatches (group-relative"
                " advantages would silently use partial groups)"
            )


class StreamRLTrainer:
    def __init__(
        self,
        cfg: TrainerConfig,
        actor: StreamActor,
        rollout: RolloutEngine,
        tokenizer,
        reward_manager,
        dataloader,
        critic: StreamCritic | None = None,
        ref_policy: ReferencePolicy | None = None,
        logger=None,
        val_dataset=None,
        recorder=None,
        health=None,
        autoscale=None,
    ):
        self.cfg = cfg
        self.actor = actor
        self.rollout = rollout
        self.tokenizer = tokenizer
        self.reward_manager = reward_manager
        self.dataloader = dataloader
        self.critic = critic
        self.ref_policy = ref_policy
        self.logger = logger
        self.val_dataset = val_dataset
        self.global_step = 0
        # multi-host SPMD: every process runs this same fit loop; process 0
        # owns the control plane (manager streaming, reward scoring, weight
        # fabric, logging) and broadcasts batches/scores to the others
        # (parallel/multihost.py; reference worker-group scatter,
        # stream_fsdp_workers.py:262-546)
        from polyrl_tpu.parallel import multihost
        self._mh = multihost
        self._is_main = multihost.is_main()
        self._multi = multihost.process_count() > 1
        # local-generation budget from the manager's balancer (None until the
        # first update_metrics round trip; manager default applies)
        self._max_local_gen_s: float | None = None
        # weight pushes initiated so far; a prefetched stream records the
        # count at its generation start, so the gap at consume time IS the
        # perf/weight_staleness gauge
        self._push_count = 0
        if cfg.pipeline_depth > 0 and not cfg.rollout_is_correction:
            log.warning(
                "pipeline_depth=%d without rollout_is_correction: rollouts "
                "arrive up to one weight-version stale and advantages are "
                "NOT importance-corrected", cfg.pipeline_depth)
        if cfg.adv_estimator == "gae" and critic is None:
            raise ValueError("GAE requires a critic")
        self._ckpt = (
            ckpt_lib.CheckpointManager(cfg.ckpt_dir, max_to_keep=cfg.max_ckpt_keep)
            if cfg.ckpt_dir
            else None
        )
        self._esi_expiry = ckpt_lib.esi_expiry_from_env()
        # the chips the step's rate and utilization are taken over: the
        # devices the actor's parameters live on, not every device this
        # process can see (rollout engines may own the others)
        actor_devices = set().union(*(
            x.sharding.device_set
            for x in jax.tree_util.tree_leaves(actor.params)))
        self._n_chips = len(actor_devices)
        self._flops = FlopsCounter(
            actor.model_cfg,
            peak_tflops=peak_tflops(next(iter(actor_devices)).device_kind),
            n_chips=self._n_chips)
        self._tracing = False
        # goodput accounting (obs/goodput.py): every step's wall time is
        # decomposed into non-overlapping phases; /statusz reads the
        # cumulative side
        self._goodput = obs.GoodputLedger(flops=self._flops)
        self._last_record: dict = {}
        self._statusz = None
        # critical-path plane (obs/critical_path.py): per-step extraction
        # over the span ring when tracing is on — critpath/* gauges, the
        # last N paths for critical_path.json bundles / fleet_report
        self._critpaths: collections.deque = collections.deque(maxlen=32)
        # fleet time-series rail (obs/timeseries.py): every finished step
        # record folds in; /statusz serves the windowed aggregates and
        # BalanceEstimator.trends() the autoscaling slopes
        self._timeseries = obs.TimeSeriesStore()
        # training health plane (obs/rlhealth.py): per-step RL-dynamics
        # ledger behind training/* step metrics and the /statusz training
        # section. Default-on (pass health=False to disable, or a
        # pre-built TrainingHealthLedger to configure tail sizes).
        if health is None:
            health = obs.TrainingHealthLedger()
        self._health = health or None
        # closed-loop autoscaling (rollout/autoscale.py): ticked once per
        # finished step with the fresh pool counters + the previous step's
        # record; also gates pipeline admission while the fleet is empty.
        # None (the default) is the pre-autoscale trainer, bit for bit.
        self._autoscale = autoscale
        # anomaly flight recorder (obs/recorder.py): fed each finished
        # step record; dumps post-mortem bundles on anomaly/crash
        self._recorder = recorder
        if recorder is not None and self._health is not None:
            # entropy-collapse/KL-blowup bundles carry the RL-dynamics
            # tail + the last batch's GRPO group table as training.json
            recorder.training_fn = self._health.bundle_view
        if recorder is not None:
            # stall/anomaly bundles carry the last N per-step critical
            # paths as critical_path.json (empty until tracing produces
            # one — the recorder then skips the file)
            recorder.critical_path_fn = self._critical_path_view
        if recorder is not None and isinstance(rollout, RemoteRollout):
            recorder.counters_fn = rollout.fault_counters
            # post-mortem bundles carry the fleet flight-deck tail (per-
            # engine occupancy/page pressure at anomaly time); resolved at
            # dump time — the pool may attach after construction
            recorder.engine_fn = (
                lambda: rollout.pool.engine_section()
                if rollout.pool is not None else {})
            # cold-frac / HBM-headroom anomaly bundles carry the fleet KV
            # memory plane (per-engine residency + headroom) as memory.json
            recorder.memory_fn = (
                lambda: rollout.pool.memory_section()
                if rollout.pool is not None else {})
            # device-frac / accounting-frac anomaly bundles carry the
            # fleet engine-loop profiler view (per-engine device-vs-host
            # split at anomaly time) as engine_profile.json; a
            # {"enabled": False} fleet (no engine reporting the profiler)
            # skips the file, mirroring memory_fn's empty-view semantics
            def _loop_profile_view():
                pool = rollout.pool
                if pool is None:
                    return {}
                section = pool.loop_profile_section()
                return section if section.get("enabled") else {}
            recorder.engine_profile_fn = _loop_profile_view

    # -- profiling (reference _start/_stop_profiling with continuous-step
    # logic, stream_ray_trainer.py:356-361,629-641) ----------------------

    def _profile_gate(self, about_to_run: int) -> None:
        """Start/stop jax.profiler traces so that consecutive profiled steps
        share one trace."""
        cfg = self.cfg
        want = about_to_run in cfg.profile_steps
        if want and not self._tracing:
            jax.profiler.start_trace(cfg.profile_dir)
            self._tracing = True
        elif not want and self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False

    # -- checkpoint/resume (reference stream_ray_trainer.py:305,604-623) --

    def _ckpt_state(self) -> dict:
        state = {"actor": {"params": self.actor.params,
                           "opt_state": self.actor.opt_state}}
        if self.critic is not None:
            state["critic"] = {"params": self.critic.params,
                               "opt_state": self.critic.opt_state}
        return state

    def _save_checkpoint(self) -> None:
        meta = {"global_step": self.global_step}
        if hasattr(self.dataloader, "state_dict"):
            meta["dataloader"] = self.dataloader.state_dict()
        self._ckpt.save(self.global_step, self._ckpt_state(), meta)

    def _load_checkpoint(self) -> bool:
        """Restore latest checkpoint if present; returns True on resume.
        Items are restored independently, so a critic-config change (actor-
        only ckpt into a critic trainer, or vice versa) resumes what
        matches instead of failing on pytree-structure mismatch."""
        if self._ckpt is None or self.cfg.resume == "disable":
            return False
        targets = {k: ckpt_lib.abstract_like(v)
                   for k, v in self._ckpt_state().items()}
        out = self._ckpt.restore(targets=targets)
        if out is None:
            return False
        state, meta = out
        if "actor" in state:
            self.actor.params = state["actor"]["params"]
            self.actor.opt_state = state["actor"]["opt_state"]
        if self.critic is not None and "critic" in state:
            self.critic.params = state["critic"]["params"]
            self.critic.opt_state = state["critic"]["opt_state"]
        self.global_step = int(meta.get("global_step", 0))
        if "dataloader" in meta and hasattr(self.dataloader, "load_state_dict"):
            self.dataloader.load_state_dict(meta["dataloader"])
        return True

    # -- rollout → TensorBatch -------------------------------------------

    def _prepare_prompts(self, records: list[dict]):
        """Unroll n samples per prompt (reference preprocess,
        sglang_rollout_remote.py:198-225)."""
        cfg = self.cfg
        prompts, gts, sources = [], [], []
        for rec in records:
            ids = self.tokenizer.encode(rec["prompt"])[: cfg.max_prompt_length]
            for _ in range(cfg.rollout_n):
                prompts.append(ids)
                gts.append(rec.get("ground_truth", ""))
                sources.append(rec.get("data_source", ""))
        return prompts, gts, sources

    def _sampling(self) -> SamplingParams:
        cfg = self.cfg
        return SamplingParams(
            temperature=cfg.temperature, top_p=cfg.top_p, top_k=cfg.top_k,
            max_new_tokens=cfg.max_response_length,
            stop_token_ids=(self.tokenizer.eos_token_id,),
        )

    def _assemble_batch(self, prompts, gts, sources, outs, group_ids) -> TensorBatch:
        """Reassemble fixed-shape arrays (the reference's postprocess,
        sglang_rollout_remote.py:318-391). ``outs`` expose ``output_ids`` and
        ``output_token_logprobs``; ``group_ids`` are batch-local dense ids."""
        cfg = self.cfg
        n = len(prompts)
        tp, tr = cfg.max_prompt_length, cfg.max_response_length
        pad = self.rollout.pad_token_id
        input_ids = np.full((n, tp + tr), pad, np.int32)
        attention_mask = np.zeros((n, tp + tr), np.float32)
        responses = np.full((n, tr), pad, np.int32)
        response_mask = np.zeros((n, tr), np.float32)
        rollout_log_probs = np.zeros((n, tr), np.float32)
        # which push version sampled each response token (−1 = unknown):
        # the health ledger's per-token staleness feed (obs/rlhealth.py)
        weight_versions = np.full((n, tr), -1, np.int32)
        for i, (p, o) in enumerate(zip(prompts, outs)):
            lp = len(p)
            input_ids[i, tp - lp : tp] = p
            attention_mask[i, tp - lp : tp] = 1.0
            r = np.asarray(o.output_ids[:tr])
            input_ids[i, tp : tp + len(r)] = r
            attention_mask[i, tp : tp + len(r)] = 1.0
            responses[i, : len(r)] = r
            response_mask[i, : len(r)] = 1.0
            rollout_log_probs[i, : len(r)] = np.asarray(
                o.output_token_logprobs[: len(r)])
            wv = np.asarray(getattr(o, "output_token_weight_versions", []))
            if len(wv) >= len(r) > 0:
                weight_versions[i, : len(r)] = wv[: len(r)]
        positions = np.maximum(attention_mask.cumsum(axis=-1) - 1, 0).astype(np.int32)

        return TensorBatch.from_dict(
            tensors={
                "input_ids": input_ids,
                "attention_mask": attention_mask,
                "positions": positions,
                "responses": responses,
                "response_mask": response_mask,
                "rollout_log_probs": rollout_log_probs,
                "rollout_weight_versions": weight_versions,
                "group_ids": np.asarray(group_ids, np.int32),
            },
            non_tensors={"ground_truth": list(gts), "data_source": list(sources)},
            meta_info={"global_step": self.global_step},
        )

    def _ibatch_iter(self, records: list[dict], rng, metrics: MetricsTracker):
        """Yield TensorBatch ibatches. Colocated: generate all, slice.
        Remote: stream group-complete chunks while generation continues.
        Multi-host: process 0 streams from the manager and broadcasts each
        ibatch; the other hosts replay the broadcast (their jitted updates
        then shard the same global batch over the mesh)."""
        yield from self._ibatch_fanout(
            lambda: self._ibatch_iter_local(records, rng, metrics), metrics)

    def _ibatch_fanout(self, make_local_iter: Callable, metrics: MetricsTracker):
        """Multi-host fan-out wrapper around a local ibatch source (either
        the direct ``_ibatch_iter_local`` stream or the pipeline's queue in
        pipelined mode — the broadcast collectives always run on THIS
        foreground thread so every process issues them in one order)."""
        if self._multi:
            if self._is_main:
                # error sentinel: if the control plane raises mid-stream the
                # other hosts must be released from their blocking collective
                # (they'd otherwise hang in broadcast_one_to_all forever)
                it = make_local_iter()
                while True:
                    try:
                        ib = next(it)
                    except StopIteration:
                        self._mh.broadcast_batch(("end", None))
                        return
                    except Exception as exc:
                        self._mh.broadcast_batch(("error", repr(exc)))
                        raise
                    with marked_timer("broadcast", metrics):
                        self._mh.broadcast_batch(("batch", ib))
                    yield ib
            else:
                while True:
                    with marked_timer("broadcast", metrics):
                        kind, ib = self._mh.broadcast_batch(None)
                    if kind == "end":
                        return
                    if kind == "error":
                        raise RuntimeError(f"main-process rollout failed: {ib}")
                    yield ib
            return
        yield from make_local_iter()

    def _ibatch_iter_local(self, records: list[dict], rng,
                           metrics: MetricsTracker):
        cfg = self.cfg
        prompts, gts, sources = self._prepare_prompts(records)
        if isinstance(self.rollout, RemoteRollout):
            stream = self.rollout.generate_stream(
                prompts, self._sampling(), group_size=cfg.rollout_n,
                min_emit=cfg.min_stream_batch_size,
                max_local_gen_s=self._max_local_gen_s)
            for chunk in stream:
                idxs = [i for i, _ in chunk]
                outs = [_ResultView(r) for _, r in chunk]
                raw_gids = np.asarray([i // cfg.rollout_n for i in idxs])
                _, dense = np.unique(raw_gids, return_inverse=True)
                yield self._assemble_batch(
                    [prompts[i] for i in idxs], [gts[i] for i in idxs],
                    [sources[i] for i in idxs], outs, dense)
        else:
            with marked_timer("gen", metrics):
                outs = self.rollout.generate(prompts, self._sampling(), rng=rng)
                outs = [o if hasattr(o, "output_ids") else _ResultView(o)
                        for o in outs]
            group_ids = np.repeat(np.arange(len(records), dtype=np.int32),
                                  cfg.rollout_n)
            batch = self._assemble_batch(prompts, gts, sources, outs, group_ids)
            yield from batch.split(cfg.min_stream_batch_size)

    def _push_weights(self, block: bool = True) -> None:
        """Push actor weights to the rollout plane. The push itself is
        control-plane (process 0 / no-op NullRollout elsewhere), but
        GATHERING cross-host-sharded params is collective — every host
        allgathers to host numpy first, or pack_params on process 0 would
        raise on non-addressable shards.

        ``block=False`` (pipelined mode): the version bump and the host
        gather still happen inline (the gather is collective, and the
        host copy detaches the payload from the actor's donated buffers),
        but the pack/wire round completes on a background thread — the
        pipeline's ``wait_pushed()`` fence joins it before the next
        generation stream (ARCHITECTURE.md "Pipeline overlap")."""
        params = self._gather_push_params()
        if not block and hasattr(self.rollout, "update_weights_async"):
            # snapshot to host NOW: the actor's next opt step donates the
            # param buffers, and the background pack must never read a
            # donated (deleted) buffer. Multi-host gathers already
            # produced host numpy; asarray is free there.
            params = jax.tree_util.tree_map(lambda x: np.asarray(x), params)
            self.rollout.update_weights_async(params)
        else:
            if not block:
                # pipelined COLOCATED engine without an async fabric: the
                # engine must own a copy — the prefetch lane generates
                # while the next step's update micros donate the actor's
                # param buffers (same rationale as RemoteRollout's
                # _update_local_copy)
                import jax.numpy as jnp

                params = jax.tree_util.tree_map(jnp.copy, params)
            self.rollout.update_weights(params)
        self._push_count += 1

    def _wait_pushed(self) -> None:
        """Fence on the last ``update_weights_async``: returns when its
        pack round has fully landed (no-op for synchronous rollouts)."""
        fn = getattr(self.rollout, "wait_pushed", None)
        if fn is not None:
            fn()

    def _wait_push_headroom(self, max_lag: int) -> None:
        """Bounded-staleness admission gate (``staleness_limit > 1``):
        block until at most ``max_lag`` async pushes are still in flight.
        Rollouts without a lag surface fall back to the full fence
        (conservative — lag 0 satisfies any bound)."""
        fn = getattr(self.rollout, "wait_push_lag", None)
        if fn is not None:
            fn(max_lag)
        else:
            self._wait_pushed()

    def _push_lag(self) -> int:
        """In-flight async push count (``perf/staleness_lag`` gauge)."""
        fn = getattr(self.rollout, "push_lag", None)
        return int(fn()) if fn is not None else 0

    def _gather_push_params(self):
        if self.cfg.weight_sync == "lora_delta":
            # delta sync: only the adapters ride the wire; workers hold the
            # frozen base and install a/b in place
            from polyrl_tpu.models import lora as lora_mod

            params = lora_mod.extract_adapters(self.actor.params)
            if self._multi:
                # gather ONLY the sharded adapter leaves; the alpha scalar
                # and base_stats are host-local replicated values that
                # process_allgather would stack/concat into wrong shapes
                from jax.experimental import multihost_utils as mhu

                params = dict(
                    params,
                    layers=jax.tree_util.tree_map(
                        lambda x: np.asarray(
                            mhu.process_allgather(x, tiled=True)),
                        params["layers"]),
                    base_stats=np.asarray(params["base_stats"]),
                    alpha=np.asarray(params["alpha"]))
            return params
        else:
            # export: LoRA actors merge adapters into the plain layout here
            # — the wire format and the engines never see wrapper nodes
            params = (self.actor.export_params()
                      if hasattr(self.actor, "export_params")
                      else self.actor.params)
        if self._multi:
            from jax.experimental import multihost_utils as mhu

            params = jax.tree_util.tree_map(
                lambda x: np.asarray(mhu.process_allgather(x, tiled=True)),
                params)
        return params

    def _to_host(self, x) -> np.ndarray:
        """jit output → host numpy. Multi-host: jitted outputs are GLOBAL
        arrays whose shards live on other processes; np.asarray would raise
        (non-addressable) — allgather the global value instead. The host-side
        advantage math then runs identically on every process."""
        if self._multi:
            from jax.experimental import multihost_utils as mhu

            return np.asarray(mhu.process_allgather(x, tiled=True))
        return np.asarray(x)

    # -- per-ibatch pipeline ---------------------------------------------

    def _process_ibatch(self, ibatch: TensorBatch, metrics: MetricsTracker) -> TensorBatch:
        """reward → old_logprob → ref → values → advantage (reference
        stream_ray_trainer.py:406-498)."""
        cfg = self.cfg
        with marked_timer("reward", metrics):
            # reward scoring is control-plane work (python scorers, possibly
            # remote reward endpoints): process 0 only, scores broadcast.
            # Errors broadcast too so non-main hosts fail fast instead of
            # hanging in the collective.
            err: Exception | None = None
            payload = None
            if self._is_main:
                try:
                    reward_out = self.reward_manager(ibatch)
                    payload = ("ok", (reward_out.token_level_scores,
                                      reward_out.metrics))
                except Exception as exc:  # noqa: BLE001 — re-raised below
                    err = exc
                    payload = ("error", repr(exc))
            if self._multi:
                payload = self._mh.broadcast_obj(payload)
            if payload[0] == "error":
                raise err if err is not None else RuntimeError(
                    f"main-process reward failed: {payload[1]}")
            token_level_scores, reward_metrics = payload[1]
            metrics.update(reward_metrics)
        if cfg.use_remove_padding:
            self._packed_logprob_pass(ibatch, metrics)
        else:
            feed = {k: ibatch[k] for k in
                    ("input_ids", "positions", "attention_mask", "responses",
                     "response_mask")}
            with marked_timer("old_log_prob", metrics):
                old_lp, entropy = self.actor.compute_log_prob(feed)
                ibatch.tensors["old_log_probs"] = self._to_host(old_lp)
                metrics.update({"actor/entropy_rollout": float(
                    core_algos.masked_mean(self._to_host(entropy),
                                           ibatch["response_mask"]))})
            if self.ref_policy is not None:
                with marked_timer("ref_log_prob", metrics):
                    ibatch.tensors["ref_log_probs"] = self._to_host(
                        self.ref_policy.compute_log_prob(feed))
        if self.critic is not None:
            with marked_timer("values", metrics):
                if cfg.use_remove_padding:
                    # packed values ride the same packs/gather specs as the
                    # logprob pass (reference packed critic,
                    # stream_dp_critic.py:35,83) — no padded [B, Tp+Tr]
                    # forward is ever built when the actor runs packed
                    vals = np.zeros((len(ibatch), cfg.max_response_length),
                                    np.float32)
                    for pack, spec in ibatch.meta_info["packs"]:
                        feed = {k: pack[k] for k in
                                ("input_ids", "positions", "attention_mask",
                                 "segment_ids", "loss_mask")}
                        spec.gather_into(
                            self._to_host(self.critic.compute_values_packed(feed)),
                            vals)
                    ibatch.tensors["values"] = vals
                else:
                    cfeed = {k: ibatch[k] for k in
                             ("input_ids", "positions", "attention_mask",
                              "responses", "response_mask")}
                    ibatch.tensors["values"] = self._to_host(
                        self.critic.compute_values(cfeed))

        with marked_timer("adv", metrics):
            token_scores = token_level_scores
            if cfg.use_kl_in_reward and "ref_log_probs" in ibatch:
                token_rewards, kl_mean = core_algos.apply_kl_penalty(
                    token_scores, ibatch["old_log_probs"], ibatch["ref_log_probs"],
                    ibatch["response_mask"], cfg.kl_coef, cfg.kl_penalty)
                token_rewards = np.asarray(token_rewards)
                metrics.update({"critic/kl_in_reward": float(kl_mean)})
            else:
                token_rewards = token_scores
            ibatch.tensors["token_level_rewards"] = token_rewards

            est = cfg.adv_estimator
            if est == "grpo":
                adv, ret = core_algos.compute_grpo_outcome_advantage(
                    token_rewards, ibatch["response_mask"], ibatch["group_ids"],
                    norm_adv_by_std=cfg.norm_adv_by_std_in_grpo,
                    num_groups=int(np.max(np.asarray(ibatch["group_ids"]))) + 1)
            elif est == "rloo":
                adv, ret = core_algos.compute_rloo_outcome_advantage(
                    token_rewards, ibatch["response_mask"], ibatch["group_ids"],
                    num_groups=int(np.max(np.asarray(ibatch["group_ids"]))) + 1)
            elif est == "reinforce_plus_plus":
                adv, ret = core_algos.compute_reinforce_plus_plus_outcome_advantage(
                    token_rewards, ibatch["response_mask"], cfg.gamma)
            elif est == "gae":
                adv, ret = core_algos.compute_gae_advantage_return(
                    token_rewards, ibatch["values"], ibatch["response_mask"],
                    cfg.gamma, cfg.lam)
            elif est == "remax":
                # baseline generation + scoring is control-plane (manager
                # stream + reward manager): process 0 computes, broadcasts
                baselines = (self._compute_remax_baselines(ibatch, metrics)
                             if self._is_main else None)
                if self._multi:
                    baselines = self._mh.broadcast_obj(baselines)
                adv, ret = core_algos.compute_remax_outcome_advantage(
                    token_rewards, baselines, ibatch["response_mask"])
            else:
                raise NotImplementedError(est)
            ibatch.tensors["advantages"] = np.asarray(adv)
            ibatch.tensors["returns"] = np.asarray(ret)
            tis_w = None
            tis_stats = None
            if cfg.rollout_is_correction:
                # stale-rollout correction (pipelined mode generates up to
                # staleness_limit weight-versions behind the update):
                # MIXED-VERSION per-token truncated importance reweighting
                # of each token's own behavior policy (rollout_log_probs,
                # captured under the version that sampled the token —
                # rollout_weight_versions) against the recomputed
                # current-policy old_log_probs — OPPO/LlamaRL's
                # bounded-staleness recipe. Unknown-version tokens (−1:
                # degraded local completions) are EXCLUDED (weight 1.0)
                # and counted, not corrected as if version-0.
                tis_w, _ratio, tis_stats = \
                    core_algos.mixed_version_importance_weights(
                        ibatch["old_log_probs"], ibatch["rollout_log_probs"],
                        ibatch["response_mask"],
                        ibatch.tensors.get("rollout_weight_versions"),
                        current_version=int(getattr(self.rollout,
                                                    "weight_version", 0)),
                        cap=cfg.rollout_is_cap)
                ibatch.tensors["advantages"] = (
                    ibatch.tensors["advantages"] * tis_w)
                metrics.update({
                    "actor/tis_weight_mean": tis_stats["mean_weight"],
                    "actor/tis_clip_frac": tis_stats["clip_frac"]})
        if self._health is not None:
            # RL-dynamics ledger feed (obs/rlhealth.py): everything is a
            # host array this pass already produced; the per-token
            # weight-version lag is measured against the rollout plane's
            # CURRENT push version (tokens at −1 = version unknown)
            self._health.observe_ibatch(
                advantages=np.asarray(ibatch["advantages"]),
                response_mask=np.asarray(ibatch["response_mask"]),
                group_ids=np.asarray(ibatch["group_ids"]),
                traj_rewards=np.asarray(token_rewards).sum(axis=-1),
                data_sources=ibatch["data_source"],
                old_log_probs=np.asarray(ibatch["old_log_probs"]),
                rollout_log_probs=np.asarray(ibatch["rollout_log_probs"]),
                tis_weights=tis_w,
                tis_stats=tis_stats,
                weight_versions=ibatch.tensors.get("rollout_weight_versions"),
                current_version=int(getattr(self.rollout,
                                            "weight_version", 0)),
                max_response_length=cfg.max_response_length)
        return ibatch

    # -- packed-sequence (remove-padding) path ---------------------------

    def _pack_geometry(self) -> tuple[int, int]:
        cfg = self.cfg
        pack_len = cfg.pack_len or (cfg.max_prompt_length + cfg.max_response_length)
        mesh = getattr(self.actor, "mesh", None)
        if mesh is not None:
            # packed × SP: the pack columns shard over sp (shard_map needs
            # even slices), and the rows over the batch axes — round both
            # up so any configured budget produces a shardable grid
            sp = mesh.shape.get("sp", 1)
            pack_len = -(-pack_len // sp) * sp
        if cfg.micro_token_budget > 0:
            n_rows = max(1, cfg.micro_token_budget // pack_len)
        else:
            n_rows = cfg.micro_batch_size
        if mesh is not None:
            # round DOWN (floor one full shard): rounding up could exceed
            # micro_token_budget — the HBM guard it exists to be
            rows_div = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
            if cfg.micro_token_budget > 0 and rows_div > n_rows:
                # the one-row-per-batch-shard floor would silently EXCEED
                # the budget (rows_div*pack_len > micro_token_budget):
                # that defeats the HBM guard, so fail loudly (advisor r5)
                raise ValueError(
                    f"micro_token_budget={cfg.micro_token_budget} cannot fit"
                    f" one packed row per batch shard: dp*fsdp={rows_div}"
                    f" rows x pack_len={pack_len} ="
                    f" {rows_div * pack_len} tokens minimum; raise the"
                    f" budget or shrink dp*fsdp/pack_len")
            n_rows = max(rows_div, n_rows // rows_div * rows_div)
        return pack_len, n_rows

    def _packed_logprob_pass(self, ibatch: TensorBatch,
                             metrics: MetricsTracker) -> None:
        """old/ref logprobs + entropy on the packed layout (the padded
        forward wastes FLOPs on pads — reference use_remove_padding), then
        gathered back to [B, Tr] for the (host-side) advantage math. The
        packs are stashed on the ibatch and reused for the update micros."""
        from polyrl_tpu.data import packing

        cfg = self.cfg
        pack_len, n_rows = self._pack_geometry()
        packs = list(packing.iter_packed_micros(
            ibatch, cfg.max_prompt_length, pack_len, n_rows,
            self.rollout.pad_token_id))
        ibatch.meta_info["packs"] = packs
        b, tr = len(ibatch), cfg.max_response_length
        old_lp = np.zeros((b, tr), np.float32)
        ref_lp = np.zeros((b, tr), np.float32) if self.ref_policy is not None else None
        ent_num = ent_den = 0.0
        with marked_timer("old_log_prob", metrics):
            for pack, spec in packs:
                feed = {k: pack[k] for k in
                        ("input_ids", "positions", "attention_mask",
                         "segment_ids", "loss_mask")}
                lp, ent = self.actor.compute_log_prob_packed(feed)
                spec.gather_into(self._to_host(lp), old_lp)
                lm = np.asarray(pack["loss_mask"])
                ent_num += float((self._to_host(ent) * lm).sum())
                ent_den += float(lm.sum())
        ibatch.tensors["old_log_probs"] = old_lp
        metrics.update({"actor/entropy_rollout": ent_num / max(ent_den, 1.0)})
        if ref_lp is not None:
            with marked_timer("ref_log_prob", metrics):
                for pack, spec in packs:
                    feed = {k: pack[k] for k in
                            ("input_ids", "positions", "attention_mask",
                             "segment_ids", "loss_mask")}
                    spec.gather_into(
                        self._to_host(
                            self.ref_policy.compute_log_prob_packed(feed)),
                        ref_lp)
            ibatch.tensors["ref_log_probs"] = ref_lp

    def _packed_micros(self, ibatch: TensorBatch):
        """Yield (packed_feed, n_trajectories) update micros, scattering the
        now-computed advantages/old/ref logprobs into each pack's layout."""
        packs = ibatch.meta_info["packs"]
        adv = np.asarray(ibatch["advantages"])
        old = np.asarray(ibatch["old_log_probs"])
        ref = (np.asarray(ibatch["ref_log_probs"])
               if "ref_log_probs" in ibatch else None)
        ret = (np.asarray(ibatch["returns"])
               if self.critic is not None and "returns" in ibatch else None)
        vals = (np.asarray(ibatch["values"])
                if self.critic is not None and "values" in ibatch else None)
        for pack, spec in packs:
            feed = {k: pack[k] for k in
                    ("input_ids", "positions", "attention_mask",
                     "segment_ids", "loss_mask")}
            feed["advantages"] = spec.scatter(adv)
            feed["old_log_probs"] = spec.scatter(old)
            if ref is not None:
                feed["ref_log_probs"] = spec.scatter(ref)
            if ret is not None:
                feed["returns"] = spec.scatter(ret)
            if vals is not None:
                feed["values"] = spec.scatter(vals)
            yield feed, len(spec.orig_idx)

    def _compute_remax_baselines(self, ibatch: TensorBatch,
                                 metrics: MetricsTracker) -> np.ndarray:
        """REMAX baseline (reference estimator enum stream_ray_trainer.py:50,
        377,387): ONE greedy rollout per prompt group, scored with the same
        reward manager; its score is the per-trajectory reward baseline."""
        cfg = self.cfg
        group_ids = np.asarray(ibatch["group_ids"])
        tp = cfg.max_prompt_length
        input_ids = np.asarray(ibatch["input_ids"])
        attn = np.asarray(ibatch["attention_mask"])
        gts, sources = ibatch["ground_truth"], ibatch["data_source"]
        uniq, first_idx = np.unique(group_ids, return_index=True)
        prompts = [input_ids[i, :tp][attn[i, :tp] > 0].tolist()
                   for i in first_idx]
        sampling = SamplingParams(
            temperature=0.0, top_p=1.0, top_k=0,
            max_new_tokens=cfg.max_response_length,
            stop_token_ids=(self.tokenizer.eos_token_id,))
        with marked_timer("remax_baseline", metrics):
            # nested: the outer generate_stream is still active — the
            # baseline stream must not pause/release the colocated engine
            outs, failed = self._generate_all(prompts, sampling, nested=True)
            base_batch = self._assemble_batch(
                prompts, [gts[i] for i in first_idx],
                [sources[i] for i in first_idx], outs,
                list(range(len(prompts))))
            base_scores = np.asarray(self.reward_manager(base_batch).scores,
                                     np.float32)
        if failed:
            # a greedy baseline hole would otherwise silently become
            # "baseline 0", biasing every advantage in the group upward.
            # Fall back to the group's sampled-reward mean (the RLOO-style
            # estimator) for exactly those groups, and surface a metric.
            log.warning("REMAX: %d/%d greedy baselines failed; substituting "
                        "group sampled-reward means", len(failed), len(prompts))
            traj_scores = np.asarray(
                ibatch["token_level_rewards"].sum(-1)
                if "token_level_rewards" in ibatch else
                self.reward_manager(ibatch).scores, np.float32)
            for fi in failed:
                base_scores[fi] = float(
                    np.mean(traj_scores[group_ids == uniq[fi]]))
        metrics.update({
            "reward/remax_baseline_mean":
                float(np.mean(base_scores)) if len(base_scores) else 0.0,
            "reward/remax_baseline_failed": float(len(failed)),
        })
        # expand group-level baselines to trajectory level
        group_to_score = {int(g): float(s) for g, s in zip(uniq, base_scores)}
        return np.asarray([group_to_score[int(g)] for g in group_ids],
                          np.float32)

    # -- validation (reference _validate, stream_ray_trainer.py:304-315) --

    def _generate_all(self, prompts: list[list[int]], sampling: SamplingParams,
                      nested: bool = False):
        """Generate for every prompt with either rollout flavour; returns
        ``(outputs, failed_indices)`` with outputs aligned with ``prompts``
        (failed slots hold an empty output). ``nested`` marks a call made
        while an outer generate_stream is active (REMAX baselines)."""
        if isinstance(self.rollout, RemoteRollout):
            outs: list = [None] * len(prompts)
            for chunk in self.rollout.generate_stream(
                    prompts, sampling, group_size=1, min_emit=len(prompts),
                    nested=nested):
                for i, res in chunk:
                    outs[i] = _ResultView(res)
            # dropped groups leave holes; substitute empty outputs and tell
            # the caller WHICH — silently zero-scoring them would skew
            # val means / REMAX baselines with no observable signal
            failed = [i for i, o in enumerate(outs) if o is None]
            empty = type("E", (), {"output_ids": np.zeros(0, np.int32),
                                   "output_token_logprobs": np.zeros(0, np.float32)})
            return [o if o is not None else empty for o in outs], failed
        outs = self.rollout.generate(prompts, sampling,
                                     rng=jax.random.PRNGKey(0))
        return [o if hasattr(o, "output_ids") else _ResultView(o) for o in outs], []

    def _validate(self) -> dict:
        """Greedy eval over the val dataset: per-data-source mean score +
        overall; optional generation dump (reference sample dump dir,
        stream_ray_trainer.py:585-587)."""
        cfg = self.cfg
        records = list(self.val_dataset)
        sampling = SamplingParams(
            temperature=cfg.val_temperature, top_p=1.0, top_k=0,
            max_new_tokens=cfg.val_max_response_length or cfg.max_response_length,
            stop_token_ids=(self.tokenizer.eos_token_id,),
        )
        per_source: dict[str, list[float]] = {}
        dump_rows: list[dict] = []
        num_failed = 0
        bs = max(cfg.train_batch_size, 1)
        for lo in range(0, len(records), bs):
            chunk = records[lo : lo + bs]
            prompts = [self.tokenizer.encode(r["prompt"])[: cfg.max_prompt_length]
                       for r in chunk]
            outs, failed = self._generate_all(prompts, sampling)
            num_failed += len(failed)
            gts = [r.get("ground_truth", "") for r in chunk]
            sources = [r.get("data_source", "") for r in chunk]
            batch = self._assemble_batch(prompts, gts, sources, outs,
                                         list(range(len(chunk))))
            reward_out = self.reward_manager(batch)
            failed_set = set(failed)
            for i, (src, sc) in enumerate(zip(sources, reward_out.scores)):
                # a failed generation is a HOLE, not a zero-score sample:
                # excluding it keeps val/test_score comparable across steps
                # with different failure counts (val/num_failed carries the
                # signal instead)
                if i in failed_set:
                    continue
                per_source.setdefault(src or "default", []).append(float(sc))
            if cfg.rollout_data_dir or cfg.val_generations_to_log:
                texts = self.tokenizer.batch_decode(
                    [np.asarray(o.output_ids) for o in outs],
                    skip_special_tokens=True)
                for r, txt, sc in zip(chunk, texts, reward_out.scores):
                    dump_rows.append({
                        "step": self.global_step, "prompt": r["prompt"],
                        "response": txt, "score": float(sc),
                        "ground_truth": r.get("ground_truth", ""),
                        "data_source": r.get("data_source", "")})
        metrics = {f"val/test_score/{src}": float(np.mean(v))
                   for src, v in per_source.items()}
        all_scores = [s for v in per_source.values() for s in v]
        metrics["val/test_score/mean"] = (
            float(np.mean(all_scores)) if all_scores else 0.0)
        metrics["val/num_failed"] = float(num_failed)
        if cfg.rollout_data_dir and dump_rows:
            import json
            import os

            os.makedirs(cfg.rollout_data_dir, exist_ok=True)
            path = os.path.join(cfg.rollout_data_dir,
                                f"val_step{self.global_step}.jsonl")
            with open(path, "w") as f:
                for row in dump_rows:
                    f.write(json.dumps(row) + "\n")
        if cfg.val_generations_to_log and self.logger is not None and dump_rows:
            for row in dump_rows[: cfg.val_generations_to_log]:
                self.logger.log({"val/generation": 0.0, **{
                    k: v for k, v in row.items() if isinstance(v, float)}},
                    step=self.global_step)
        return metrics

    def _maybe_validate(self, metrics: MetricsTracker, *, force: bool = False) -> None:
        cfg = self.cfg
        if self.val_dataset is None or not self._is_main:
            return
        due = force or (cfg.test_freq > 0 and self.global_step > 0
                        and self.global_step % cfg.test_freq == 0)
        if not due:
            return
        with marked_timer("testing", metrics):
            metrics.update(self._validate())

    # -- one training batch (stream → micros → opt steps) -----------------

    def _train_one_batch(self, ibatch_source: Callable,
                         metrics: MetricsTracker) -> dict:
        """Stream ibatches for one training batch through the per-ibatch
        pipeline and the cum-minibatch update micros (reference
        stream_ray_trainer.py:500-568); returns the stream-accounting
        state (``processed`` / ``n_tokens`` / ``bubble``).
        ``ibatch_source`` is a zero-arg callable returning the step's
        ibatch iterator — the direct ``_ibatch_iter`` in the serial loop,
        or the prefetch queue drain in pipelined mode."""
        cfg = self.cfg
        # stream accounting: ibatches arrive (possibly overlapping
        # generation); opt step when the cumulative trajectory count
        # crosses each minibatch boundary, plus a final flush on the last
        # micro so dropped groups never strand accumulated grads
        msize = cfg.ppo_mini_batch_size
        state = {"processed": 0, "n_tokens": 0, "bubble": 0.0}

        def micro_stream():
            it = ibatch_source()
            while True:
                wait_t0 = time.monotonic()
                try:
                    # the wait span is what the critical-path extractor
                    # attributes: covered by nested generation (serial) or
                    # the producer lane's prefetch span → generate;
                    # covered by nothing → a true bubble
                    with obs.span("trainer/ibatch_wait"):
                        ibatch = next(it)
                except StopIteration:
                    return
                # time blocked on rollout = the trainer bubble the
                # balancer minimizes (stream_ray_trainer.py:694-700)
                state["bubble"] += time.monotonic() - wait_t0
                ibatch = self._process_ibatch(ibatch, metrics)
                state["n_tokens"] += int(
                    np.asarray(ibatch["attention_mask"]).sum())
                if cfg.use_remove_padding:
                    yield from self._packed_micros(ibatch)
                else:
                    for m in ibatch.split(cfg.micro_batch_size):
                        yield m, len(m)

        def train_micro(micro, n_traj):
            # boundary-CROSSING, not exact multiples: ragged micro sizes
            # (packed micros, or streaming with adv estimators that allow
            # min_stream_batch_size % rollout_n != 0) may step over an
            # exact multiple and must still trigger the opt step
            prev = state["processed"]
            state["processed"] += n_traj
            is_opt = state["processed"] // msize > prev // msize
            # loss scale = the micro's trajectory share of the minibatch
            # (1/grad_steps for fixed micros; ragged micros still sum to
            # 1 over a full minibatch — reference loss_scale_factor)
            scale = n_traj / msize
            if isinstance(micro, dict):  # packed feed, actor-ready
                feed = micro
            else:
                feed = {k: micro[k] for k in (
                    "input_ids", "positions", "attention_mask", "responses",
                    "response_mask", "advantages", "old_log_probs")}
                if "ref_log_probs" in micro:
                    feed["ref_log_probs"] = micro["ref_log_probs"]
            with marked_timer("update_actor", metrics):
                m = self.actor.update_stream(feed, is_opt, loss_scale=scale)
                metrics.update({k: float(v) for k, v in m.items()})
            if self.critic is not None:
                if isinstance(micro, dict):  # packed feed: critic-ready
                    cfeed = micro
                else:
                    cfeed = {k: micro[k] for k in (
                        "input_ids", "positions", "attention_mask",
                        "responses", "response_mask", "returns", "values")}
                with marked_timer("update_critic", metrics):
                    cm = self.critic.update_stream(
                        cfeed, is_opt, loss_scale=scale)
                    metrics.update({k: float(v) for k, v in cm.items()})

        # micros train the moment they exist (never idle behind the
        # blocking ibatch wait); if a short batch (dropped groups) ends
        # mid-minibatch, flush the accumulated grads afterwards
        for micro, n_traj in micro_stream():
            train_micro(micro, n_traj)
        if state["processed"] % msize != 0 and state["processed"] > 0:
            metrics.update({k: float(v) for k, v in
                            self.actor.flush_opt_step().items()})
            if self.critic is not None:
                metrics.update({k: float(v) for k, v in
                                self.critic.flush_opt_step().items()})
        return state

    # -- live health plane (/statusz; obs/statusz.py) ---------------------

    def start_statusz(self, port: int = 0, host: str = "127.0.0.1"):
        """Mount the shared-schema ``/statusz`` exporter for this trainer
        process; returns the server (``.endpoint`` answers curl)."""
        from polyrl_tpu.obs.statusz import StatuszServer

        self._statusz = StatuszServer(self.statusz_snapshot,
                                      host=host, port=port).start()
        return self._statusz

    def stop_statusz(self) -> None:
        if self._statusz is not None:
            self._statusz.stop()
            self._statusz = None

    def statusz_snapshot(self) -> dict:
        """The trainer's side of the shared /statusz schema: current step,
        cumulative goodput phase breakdown, last-step histogram quantiles,
        fault/anomaly counters, weight staleness, pipeline queue depth."""
        from polyrl_tpu.obs import statusz

        rec = self._last_record
        counters: dict[str, float] = {}
        if isinstance(self.rollout, RemoteRollout):
            counters.update(self.rollout.fault_counters())
        if self._recorder is not None:
            counters.update(self._recorder.counters())
        gauges = {k: float(v) for k, v in rec.items()
                  if k.startswith(("perf/", "training/", "manager/",
                                   "pool/", "engine/", "critpath/",
                                   "autoscale/"))}
        pool = getattr(self.rollout, "pool", None)
        return statusz.build_snapshot(
            "trainer", step=self.global_step,
            goodput=self._goodput.snapshot(),
            histograms=statusz.nest_histograms(rec),
            counters=counters, gauges=gauges,
            queues={"pipeline_depth": float(self.cfg.pipeline_depth),
                    "staleness_limit": float(self.cfg.staleness_limit),
                    "pipeline_queue": float(rec.get(
                        "perf/pipeline_queue_depth", 0.0))},
            weights={"push_count": float(self._push_count),
                     "push_lag": float(self._push_lag()),
                     "version": float(getattr(self.rollout,
                                              "weight_version", 0)),
                     "staleness": float(rec.get(
                         "perf/weight_staleness", 0.0)),
                     # sharded-push plane (PR 15): stream fan-out width,
                     # slowest-stream bandwidth, resharded bytes, and
                     # per-stream resume count for the last rounds
                     "push_streams": counters.get(
                         "transfer/push_streams", 0.0),
                     "stream_bw_mbps_min": counters.get(
                         "transfer/stream_bw_mbps_min", 0.0),
                     "reshard_bytes": counters.get(
                         "transfer/reshard_bytes", 0.0),
                     "stream_resumes": counters.get(
                         "transfer/stream_resumes", 0.0)},
            pool=pool.statusz_section() if pool is not None else None,
            # fleet flight-deck aggregate (the rollout plane serves its own
            # per-engine ledger; the trainer serves the pool-wide view)
            engine=pool.engine_section() if pool is not None else None,
            # training health plane (always present on the trainer role
            # unless explicitly disabled with health=False)
            training=(self._health.snapshot()
                      if self._health is not None else None),
            # fleet time-series rail: windowed aggregates + slopes over
            # the step-record stream (obs/timeseries.py)
            timeseries=self._timeseries.section(),
            # closed-loop autoscaling plane: last decision + totals
            # (rollout/autoscale.py; empty when no controller attached)
            autoscale=(self._autoscale.statusz_section()
                       if self._autoscale is not None else None),
            # KV memory plane: fleet worst-case residency + headroom from
            # the pool sweep (the rollout plane serves its own ledger)
            memory=pool.memory_section() if pool is not None else None)

    def _critical_path_view(self) -> dict:
        """Recorder hook: the last N per-step critical paths, dumped into
        anomaly/stall bundles as ``critical_path.json`` (empty dict until
        tracing has produced one — the recorder then skips the file)."""
        if not self._critpaths:
            return {}
        return {"count": len(self._critpaths),
                "paths": list(self._critpaths)}

    def _wait_pool_admission(self, metrics=None) -> float:
        """Admission backpressure (degradation layer): before launching a
        new rollout stream, hold while the fleet is EMPTY (``active==0``)
        so a collapse window queues work instead of slamming every new
        stream straight into the tier-2 local-completion path. A no-op
        (0.0) without an AutoscaleController — the pre-autoscale trainer
        never waits. Returns seconds waited; gauges the wait when a
        metrics tracker is passed."""
        if self._autoscale is None:
            return 0.0
        waited = self._autoscale.hold_admission()
        if waited and metrics is not None:
            metrics.update_gauge(
                {"autoscale/admission_gate_wait_s": waited})
        return waited

    # -- fit --------------------------------------------------------------

    def fit(self) -> list[dict]:
        """Run ``total_steps`` PPO steps; returns per-step metric dicts."""
        cfg = self.cfg
        history = []
        base_rng = jax.random.PRNGKey(cfg.seed)
        resumed = self._load_checkpoint()
        if resumed and self.logger is not None:
            self.logger.log({"training/resumed_from_step": self.global_step},
                            step=self.global_step)
        # bootstrap weights into the rollout engine (reference fit :340)
        self._push_weights()
        if cfg.val_before_train and self.val_dataset is not None:
            pre = MetricsTracker()
            self._maybe_validate(pre, force=True)
            rec = pre.as_dict()
            history.append(rec)
            if self.logger is not None:
                self.logger.log(rec, step=self.global_step)

        # pipelined mode (cfg.pipeline_depth >= 1): a background lane
        # generates up to depth steps ahead while this thread trains —
        # see trainer/pipeline.py and ARCHITECTURE.md "Pipeline overlap".
        # The lane only runs where local production happens (process 0 /
        # single-host); other hosts keep replaying foreground broadcasts.
        pipeline = None
        if cfg.pipeline_depth > 0 and (not self._multi or self._is_main):
            from polyrl_tpu.trainer.pipeline import RolloutPipeline

            pipeline = RolloutPipeline(self, cfg.pipeline_depth,
                                       base_rng).start(
                self.global_step, cfg.total_steps)
        try:
            while self.global_step < cfg.total_steps:
                self._profile_gate(self.global_step + 1)
                metrics = MetricsTracker()
                step_t0 = time.monotonic()
                if pipeline is None and cfg.pipeline_depth > 0:
                    # non-main host of a pipelined run: ibatches arrive via
                    # the foreground broadcast plane exactly as in the
                    # serial loop
                    source = lambda: self._ibatch_fanout(None, metrics)  # noqa: E731
                elif pipeline is None:
                    records = next(self.dataloader)
                    # per-step rng derived from the step index so a resumed
                    # run replays the same sampling stream (keys need not be
                    # saved)
                    gen_rng = jax.random.fold_in(base_rng, self.global_step)
                    source = lambda: self._ibatch_iter(  # noqa: E731
                        records, gen_rng, metrics)
                else:
                    step = self.global_step
                    source = lambda: self._ibatch_fanout(  # noqa: E731
                        lambda: pipeline.step_ibatches(step, metrics),
                        metrics)

                # root span: every phase span, manager call, engine span,
                # and fabric push within the step shares this trace_id —
                # one step, one Perfetto timeline row group
                # (ARCHITECTURE.md "Observability")
                with obs.span("trainer/step", step=self.global_step + 1,
                              depth=cfg.pipeline_depth):
                    state = self._train_one_batch(source, metrics)
                    with marked_timer("update_weight", metrics):
                        # pipelined: version bump + host gather inline, the
                        # pack/wire round in the background — the pipeline
                        # fences on wait_pushed() before its next stream
                        self._push_weights(block=cfg.pipeline_depth == 0)
                # free optimizer HBM for the generation phase (colocated
                # time-slicing; no-op unless actor.cfg.offload_optimizer)
                self.actor.offload_opt_state()

                self.global_step += 1
                step_time = time.monotonic() - step_t0
                throughput = state["n_tokens"] / step_time if step_time else 0.0
                n_traj = max(state["processed"], 1)
                metrics.update({
                    "training/global_step": self.global_step,
                    "perf/step_time_s": step_time,
                    "perf/trainer_bubble_s": state["bubble"],
                    "perf/throughput_tokens_per_s": throughput,
                    "perf/throughput_tok_s_per_chip":
                        throughput / self._n_chips,
                    "perf/rollout_throughput_tok_s":
                        self.rollout.last_gen_throughput,
                })
                metrics.update(self._flops.step_metrics(
                    state["n_tokens"], state["n_tokens"] / n_traj, step_time))
                if isinstance(self.rollout, RemoteRollout):
                    # control-plane fault counters (supervisor restarts,
                    # client retries, stream resumes): cumulative gauges,
                    # visible every step so a chaos event is observable in
                    # the step record
                    metrics.update_gauge(self.rollout.fault_counters())
                    # balancer feed: raw scalars PLUS the goodput phase
                    # walls the progressive estimator windows over —
                    # generate (colocated gen) and update (actor+critic),
                    # the two walls whose ratio decides how much
                    # generation the trainer's update window can hide
                    timings = metrics.timings()
                    step_stats = dict(
                        step_time_s=step_time,
                        trainer_bubble_s=state["bubble"],
                        throughput=throughput,
                        generate_s=float(timings.get("gen", 0.0)),
                        update_s=float(timings.get("update_actor", 0.0))
                        + float(timings.get("update_critic", 0.0)),
                        # fleet occupancy from the previous step's pool
                        # aggregation: the balance estimator's trend input
                        # (pool/balance_occupancy_slope)
                        occupancy=float(self._last_record.get(
                            "engine/occupancy", 0.0)),
                        # fleet-min engine-loop device fraction (same lag):
                        # host-bound engines must not read as "add more"
                        device_frac=float(self._last_record.get(
                            "engine/device_frac", 0.0)))
                    if pipeline is not None:
                        # scrape + balancer round-trip ride the pipeline
                        # thread (off the hot path); their gauges land in
                        # the next consumed step's record
                        pipeline.submit_step_stats(**step_stats)
                    else:
                        # per-step scrape of the manager's /metrics: pool
                        # health + queue depths + request totals land in the
                        # step record as manager/* gauges (no separate
                        # Prometheus needed)
                        metrics.update_gauge(
                            self.rollout.scrape_manager_metrics())
                        # actuating metrics: the balancer returns the next
                        # local-generation budget (handlers.rs:867-901)
                        resp = self.rollout.update_metrics(**step_stats)
                        if resp.get("max_local_gen_s"):
                            self._max_local_gen_s = float(
                                resp["max_local_gen_s"])
                            metrics.update({
                                "training/max_local_gen_s":
                                    self._max_local_gen_s,
                                "training/num_rollout_instances":
                                    float(resp.get("num_instances", 0))})
                    # what the balancer actually saw (windowed medians +
                    # offload fraction) and, with a PoolManager attached,
                    # the pool membership counters — pool/* gauges in
                    # every step record
                    metrics.update_gauge(self.rollout.balance.metrics())
                    if self.rollout.pool is not None:
                        pool_counters = self.rollout.pool.counters()
                        metrics.update_gauge(pool_counters)
                        if self._autoscale is not None:
                            # close the loop: the controller reads this
                            # step's fleet gauges + the PREVIOUS record's
                            # critpath attribution and acts on the pool;
                            # its decision lands in THIS record
                            metrics.update_gauge(self._autoscale.tick(
                                self.global_step, fleet=pool_counters,
                                record=self._last_record))
                self._maybe_validate(metrics,
                                     force=self.global_step >= cfg.total_steps)
                if self._ckpt is not None and ckpt_lib.should_save_checkpoint(
                    self.global_step, cfg.total_steps, cfg.save_freq,
                    esi_expiry_ts=self._esi_expiry,
                    esi_margin_s=cfg.esi_margin_s,
                ):
                    with marked_timer("save_checkpoint", metrics):
                        self._save_checkpoint()
                # distribution roll-up: drain the process-global histogram
                # registry (rollout latency / decode rate, transfer push,
                # manager RTT — observed by components with no tracker
                # handle) into this step's record as p50/p95/p99/max.
                # Drained BEFORE goodput accounting so the ledger can
                # attribute the resume-wait / manager-RTT totals.
                hists = obs.drain_histograms()
                # goodput attribution (obs/goodput.py): the FULL step wall
                # (incl. validation + checkpoint IO, which perf/step_time_s
                # predates) decomposed into non-overlapping goodput/* phases
                gp = self._goodput.account(
                    step_time_s=time.monotonic() - step_t0,
                    timings=metrics.timings(),
                    bubble_s=state["bubble"],
                    overlap_s=metrics.get("perf/pipeline_overlap_s"),
                    histograms=hists,
                    n_tokens=state["n_tokens"],
                    mean_context_len=state["n_tokens"] / n_traj,
                    n_chips=self._n_chips)
                metrics.update(gp)
                metrics.merge_histograms(hists)
                tracer = obs.get_tracer()
                if tracer.enabled:
                    # critical-path attribution over the step's span tree:
                    # which segment actually bounded the wall, and how much
                    # a 10% speedup there would buy (critpath/* gauges;
                    # obs/critical_path.py). Windowed to the goodput wall so
                    # validation/checkpoint time attributes as housekeeping.
                    cp = obs.extract_critical_path(
                        tracer.records(), step=self.global_step,
                        wall_s=gp["goodput/step_wall_s"])
                    if cp is not None:
                        metrics.update_gauge(cp.metrics())
                        self._critpaths.append(cp.to_dict())
                if self._health is not None:
                    # training health plane: close the step's RL-dynamics
                    # window — training/* gauges (group diagnostics,
                    # staleness, actor mirrors) + distribution histograms
                    # land in this record; the recorder watches the
                    # direction-aware keys off the same record
                    hg, hh = self._health.finalize_step(
                        self.global_step, metrics)
                    metrics.update_gauge(hg)
                    metrics.merge_histograms(hh)
                if self.logger is not None:
                    metrics.update_gauge({"obs/log_errors": float(
                        getattr(self.logger, "log_errors", 0))})
                if self._recorder is not None:
                    # one step of lag by design: the gauges describe the
                    # steps already watched when this record was built
                    metrics.update_gauge(self._recorder.counters())
                record = metrics.as_dict()
                history.append(record)
                self._last_record = record
                # time-series rail: the bounded per-key ring behind the
                # /statusz "timeseries" section (windowed aggregates +
                # slopes — the fleet trend surface autoscaling reads)
                self._timeseries.observe(self.global_step, record)
                if self._recorder is not None:
                    # anomaly watch over the live step stream; a spike in
                    # step time (or a throughput collapse) dumps a
                    # post-mortem bundle into the run dir
                    self._recorder.record_step(self.global_step, record)
                if self.logger is not None and self._is_main:
                    self.logger.log(record, step=self.global_step)
        except BaseException as exc:
            if self._recorder is not None:
                # crash post-mortem: the bundle carries the trace ring and
                # every thread's stack at the moment of death
                self._recorder.dump(f"crash-{type(exc).__name__}",
                                    detail=repr(exc), step=self.global_step)
            raise
        finally:
            if pipeline is not None:
                pipeline.close()
        # drain the last async push before teardown can stop the sender
        self._wait_pushed()
        self._profile_gate(-1)  # close any open trace
        tracer = obs.get_tracer()
        if tracer.enabled and self._is_main:
            # per-run Perfetto dump next to the JSONL metrics (spans.jsonl
            # + trace.json); no-op when no out_dir is configured
            tracer.export_run()
        if self._ckpt is not None:
            self._ckpt.wait()
        return history
