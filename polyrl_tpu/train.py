"""Trainer entry point: ``python -m polyrl_tpu.train [--config run.yaml]
[section.field=value ...]``.

Equivalent of the reference's C1 trainer driver (``python -m
rlboost.verl_stream.trainer.main_stream``, main_stream.py:40-94): compose
config, build datasets/tokenizer/reward, spawn the rollout manager when
disaggregated (head-node role, main_stream.py:342-362), assemble the
trainer, run ``fit``. The colocated mode is the ``main_ppo`` synchronous
baseline (SURVEY.md §3.5) behind the same flag surface
(``rollout.mode=colocated``).
"""

from __future__ import annotations

import argparse
import importlib.util
import logging
import os
import sys

from polyrl_tpu.config import RunConfig, load_config, to_dict

log = logging.getLogger("polyrl_tpu.train")


def build_tokenizer(cfg: RunConfig):
    from polyrl_tpu.utils.tokenizer import ByteTokenizer, load_tokenizer

    if cfg.tokenizer.kind == "byte":
        return ByteTokenizer()
    return load_tokenizer(cfg.tokenizer.name_or_path)


def build_dataset(cfg: RunConfig, split: str = "train"):
    from polyrl_tpu.data.dataset import RLDataset, make_arithmetic_dataset

    path = cfg.data.train_path if split == "train" else cfg.data.val_path
    if not path:
        return None
    if path == "arithmetic":
        return make_arithmetic_dataset(cfg.data.arithmetic_size, seed=cfg.data.seed)
    if path.endswith(".jsonl"):
        return RLDataset.from_jsonl(path)
    if path.endswith(".parquet"):
        return RLDataset.from_parquet(path, prompt_key=cfg.data.prompt_key)
    raise ValueError(f"unsupported dataset path {path!r}")


def load_custom_score(path: str):
    """Load ``compute_score`` from a user file (reference custom reward fn,
    reward.py:95-150)."""
    spec = importlib.util.spec_from_file_location("polyrl_custom_reward", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute_score


def _build_model(cfg: RunConfig, mesh=None):
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder

    if cfg.model.hf_path:
        from polyrl_tpu.models.hf_loader import build_from_hf

        mcfg, params = build_from_hf(cfg.model.hf_path,
                                     dtype=getattr(jnp, cfg.model.dtype),
                                     overrides=cfg.model.overrides)
        log.info("loaded pretrained weights from %s", cfg.model.hf_path)
        return mcfg, params
    mcfg = decoder.get_config(cfg.model.preset, dtype=getattr(jnp, cfg.model.dtype),
                              **cfg.model.overrides)

    def init():
        return decoder.init_params(jax.random.PRNGKey(cfg.trainer.seed), mcfg)

    if mesh is None or jax.process_count() > 1:
        # multi-process: every process inits the same seeded tree locally
        # (the reference policy keeps a process-local copy of it) and the
        # actor shards it
        return mcfg, jax.jit(init)()
    # born sharded: each leaf is created in its mesh layout, so the full
    # tree never stages through the default device (which the mesh may
    # not even contain)
    from jax.sharding import NamedSharding, PartitionSpec as P

    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), decoder.param_specs(mcfg),
        is_leaf=lambda x: isinstance(x, P))
    return mcfg, jax.jit(init, out_shardings=shardings)()


def _build_rollout(cfg: RunConfig, mcfg, params, tokenizer, cleanup: list):
    """Colocated: an in-process engine. Disaggregated: ManagerClient (+
    locally spawned manager when no endpoint is configured) + weight fabric;
    rollout instances join the pool on their own via
    ``python -m polyrl_tpu.rollout.serve``."""
    import jax.numpy as jnp

    if cfg.trainer.weight_sync == "lora_delta":
        # all delta-sync config validation BEFORE any manager spawn (the
        # fail-fast convention build_trainer documents for the SP block)
        if cfg.rollout.mode != "disaggregated":
            raise NotImplementedError(
                "weight_sync=lora_delta requires rollout.mode=disaggregated "
                "(a colocated in-process engine holds the plain tree; "
                "adapter pushes target workers serving --lora-rank)")
        if cfg.actor.lora_rank <= 0:
            raise ValueError(
                "trainer.weight_sync=lora_delta requires actor.lora_rank>0")
        if cfg.rollout.colocated_local:
            raise NotImplementedError(
                "weight_sync=lora_delta with colocated_local is not "
                "supported: the in-process engine serves the plain merged "
                "tree and cannot take adapter-only pushes")

    kv_dtype = getattr(jnp, cfg.rollout.kv_cache_dtype or cfg.model.dtype)
    pad = tokenizer.pad_token_id

    if cfg.rollout.mode == "colocated":
        if cfg.rollout.backend == "cb":
            from polyrl_tpu.rollout.cb_engine import CBEngine

            kwargs = {}
            if cfg.rollout.prompt_buckets:
                kwargs["prompt_buckets"] = tuple(cfg.rollout.prompt_buckets)
            return CBEngine(
                mcfg, params, pad_token_id=pad, kv_cache_dtype=kv_dtype,
                max_slots=cfg.rollout.max_slots, page_size=cfg.rollout.page_size,
                max_seq_len=cfg.rollout.max_seq_len,
                prefill_chunk=cfg.rollout.prefill_chunk,
                salvage_partials=cfg.rollout.salvage_partials,
                admit_wave=cfg.rollout.admit_wave,
                admit_reorder_window=cfg.rollout.admit_reorder_window,
                group_share=cfg.rollout.group_share,
                decode_group_share=cfg.rollout.decode_group_share,
                group_preref_ttl_s=cfg.rollout.group_preref_ttl_s,
                kv_ledger=cfg.rollout.kv_ledger,
                kv_cold_after_dispatches=(
                    cfg.rollout.kv_cold_after_dispatches),
                kv_spill=cfg.rollout.kv_spill,
                kv_spill_host_gb=cfg.rollout.kv_spill_host_gb,
                kv_spill_high_watermark=cfg.rollout.kv_spill_high_watermark,
                kv_spill_low_watermark=(
                    cfg.rollout.kv_spill_low_watermark),
                loop_profile=cfg.rollout.loop_profile, **kwargs)
        from polyrl_tpu.rollout.engine import RolloutEngine

        kwargs = {}
        if cfg.rollout.batch_buckets:
            kwargs["batch_buckets"] = tuple(cfg.rollout.batch_buckets)
        if cfg.rollout.prompt_buckets:
            kwargs["prompt_buckets"] = tuple(cfg.rollout.prompt_buckets)
        return RolloutEngine(mcfg, params, pad_token_id=pad,
                             kv_cache_dtype=kv_dtype, **kwargs)

    if cfg.rollout.mode != "disaggregated":
        raise ValueError(f"unknown rollout.mode {cfg.rollout.mode!r}")

    from polyrl_tpu.manager.client import ManagerClient
    from polyrl_tpu.manager.supervisor import ManagerSupervisor
    from polyrl_tpu.rollout.remote import RemoteRollout
    from polyrl_tpu.transfer import TransferInterface

    fault = None
    if cfg.rollout.fault_injection.enabled:
        # chaos mode: one injector shared by the trainer-side stream
        # wrapper and (below) the colocated local server
        from polyrl_tpu.rollout.faults import FaultInjector

        fault = FaultInjector(cfg.rollout.fault_injection)
        log.warning("rollout fault injection ENABLED: %s",
                    cfg.rollout.fault_injection)

    endpoint = cfg.rollout.manager_endpoint
    if not endpoint:
        # locally spawned manager runs SUPERVISED: crash/health failure →
        # backoff respawn + /reconcile state replay, and the client below
        # re-resolves the fresh ephemeral port through the supervisor
        supervisor = ManagerSupervisor(
            extra_args=list(cfg.rollout.manager_args),
            respawn_backoff_s=cfg.rollout.manager_respawn_backoff_s,
            respawn_backoff_max_s=cfg.rollout.manager_respawn_backoff_max_s,
        ).start()
        cleanup.append(supervisor.stop)
        mgr = supervisor.client()
        log.info("spawned supervised rollout manager on %s (log: %s)",
                 supervisor.endpoint, supervisor.log_path)
    else:
        mgr = ManagerClient(endpoint)
    mgr.wait_healthy()
    template = params
    if cfg.trainer.weight_sync == "lora_delta":
        # LoRA delta sync: the wire carries ONLY adapters (~rank/hidden of
        # the model); workers must serve with the matching --lora-rank
        # (combination validated fail-fast at the top of this function)
        from polyrl_tpu.models import lora as lora_mod

        template = lora_mod.adapter_template(mcfg, cfg.actor.lora_rank)
    transfer_fault = None
    if cfg.transfer.fault_injection.enabled:
        # transfer-plane chaos: frame corruption / stream stalls /
        # control-channel kills on the weight-push fabric
        from polyrl_tpu.rollout.faults import TransferFaultInjector

        transfer_fault = TransferFaultInjector(cfg.transfer.fault_injection)
        log.warning("transfer fault injection ENABLED: %s",
                    cfg.transfer.fault_injection)
    iface = TransferInterface(
        template, manager_client=mgr,
        num_streams=cfg.rollout.transfer_streams,
        advertise_host=cfg.rollout.advertise_host,
        sender_groups=cfg.rollout.sender_groups,
        sender_nic_cidr=cfg.rollout.sender_nic_cidr,
        groups_per_sender=cfg.rollout.groups_per_sender,
        cfg=cfg.transfer, fault=transfer_fault)
    cleanup.append(iface.close)

    local_server = None
    if cfg.rollout.colocated_local:
        # hybrid mode: an in-process engine shares this chip with training
        # and registers as a LOCAL instance — the manager time-slices it
        # (abort after the balancer window) and RemoteRollout releases /
        # resumes its KV HBM around the generation phase (reference
        # sglang_http_async_engine.py:43-113 + stream_fsdp_workers.py:468-492)
        from polyrl_tpu.rollout.cb_engine import CBEngine
        from polyrl_tpu.rollout.server import RolloutServer

        eng = CBEngine(
            mcfg, params, pad_token_id=pad, kv_cache_dtype=kv_dtype,
            max_slots=cfg.rollout.max_slots, page_size=cfg.rollout.page_size,
            max_seq_len=cfg.rollout.max_seq_len,
            prefill_chunk=cfg.rollout.prefill_chunk,
            spec_tokens=cfg.rollout.spec_tokens,
            spec_rounds=cfg.rollout.spec_rounds,
            salvage_partials=cfg.rollout.salvage_partials,
            admit_wave=cfg.rollout.admit_wave,
            admit_reorder_window=cfg.rollout.admit_reorder_window,
            group_share=cfg.rollout.group_share,
            decode_group_share=cfg.rollout.decode_group_share,
            group_preref_ttl_s=cfg.rollout.group_preref_ttl_s,
            kv_ledger=cfg.rollout.kv_ledger,
            kv_cold_after_dispatches=cfg.rollout.kv_cold_after_dispatches,
            kv_spill=cfg.rollout.kv_spill,
            kv_spill_host_gb=cfg.rollout.kv_spill_host_gb,
            kv_spill_high_watermark=cfg.rollout.kv_spill_high_watermark,
            kv_spill_low_watermark=cfg.rollout.kv_spill_low_watermark,
            loop_profile=cfg.rollout.loop_profile,
            **({"prompt_buckets": tuple(cfg.rollout.prompt_buckets)}
               if cfg.rollout.prompt_buckets else {}))
        local_server = RolloutServer(eng, host="127.0.0.1", port=0)
        local_server.fault = fault
        local_server.start()
        cleanup.append(local_server.stop)
        # register through the trainer's client (not a fresh one): the
        # supervisor then records the local endpoint for replay after a
        # manager respawn
        mgr.register_local_rollout_instances([local_server.endpoint])
        log.info("colocated local engine registered at %s",
                 local_server.endpoint)
    # fleet control plane: membership sweeps for /statusz + pool/* step
    # gauges, scale-up join gating, and preemption drills (rollout/pool.py)
    from polyrl_tpu.rollout.pool import PoolManager

    pool = PoolManager(mgr, cfg.rollout.pool)
    cleanup.append(pool.close)
    # weight-fabric supervision loop closure (ARCHITECTURE.md
    # "Weight-fabric fault tolerance"): a receiver that exhausts its push
    # retry budget is drained + deregistered by the fleet control plane,
    # and the sender's per-engine sync health rides the /statusz pool
    # section's engine rows
    iface.set_laggard_callback(pool.escalate_laggard)
    pool.transfer_health_fn = iface.sync_health
    return RemoteRollout(mgr, transfer=iface, local_server=local_server,
                         pad_token_id=pad,
                         resume_budget=cfg.rollout.resume_budget,
                         resume_wait_s=cfg.rollout.resume_wait_s,
                         salvage_partials=cfg.rollout.salvage_partials,
                         fault_injector=fault,
                         balance_window=cfg.rollout.pool.balance_window,
                         pool=pool)


def _build_mesh(cfg: RunConfig):
    """Build the global GSPMD mesh when parallelism is configured, a device
    list is given, or the run is multi-process (jax.distributed). Returns
    None single-chip — the actor then skips sharding entirely."""
    import jax

    from polyrl_tpu.parallel import distributed
    from polyrl_tpu.parallel import mesh as meshlib

    p = cfg.parallel
    axes = (p.dp, p.fsdp, p.tp, p.sp, p.ep, p.pp)
    if (jax.process_count() == 1 and all(a == 1 for a in axes)
            and not p.devices):
        return None
    fsdp = p.fsdp
    if all(a == 1 for a in axes) and not p.devices:
        # multi-process with no axes configured: absorb the global device
        # count into fsdp (MeshConfig's own default) so a plain multi-host
        # launch works without hand-set parallel: overrides
        fsdp = -1
    mcfg = meshlib.MeshConfig(dp=p.dp, fsdp=fsdp, tp=p.tp, sp=p.sp,
                              pp=p.pp, ep=p.ep)
    if p.devices:
        all_devs = jax.devices()
        mesh = meshlib.make_mesh(mcfg, [all_devs[i] for i in p.devices])
    else:
        mesh = distributed.make_hybrid_mesh(config=mcfg)
    log.info("mesh: %s over %d of %d devices (%d processes)",
             dict(zip(mesh.axis_names, mesh.devices.shape)),
             mesh.devices.size, jax.device_count(), jax.process_count())
    return mesh


def build_trainer(cfg: RunConfig, cleanup: list | None = None):
    """Assemble the full trainer from a RunConfig. ``cleanup`` collects
    teardown callables (spawned manager, fabric threads)."""
    from polyrl_tpu.data.dataset import PromptDataLoader
    from polyrl_tpu.parallel import multihost
    from polyrl_tpu.rewards.manager import load_reward_manager
    from polyrl_tpu.trainer.actor import ReferencePolicy, StreamActor
    from polyrl_tpu.trainer.critic import StreamCritic, init_critic_params
    from polyrl_tpu.trainer.stream_trainer import StreamRLTrainer
    from polyrl_tpu.utils.metrics import Tracking

    cleanup = [] if cleanup is None else cleanup
    # observability first: spans opened during bring-up (manager spawn,
    # fabric registration) should already land in the ring buffer. The
    # trace dir defaults next to the JSONL metrics so the Perfetto dump
    # sits beside the run's step records.
    from polyrl_tpu import obs

    trace_dir = cfg.obs.trace_dir
    if not trace_dir and cfg.obs.trace and cfg.logging.path:
        trace_dir = os.path.dirname(os.path.abspath(cfg.logging.path))
    obs.configure(trace=cfg.obs.trace, max_spans=cfg.obs.trace_buffer,
                  out_dir=trace_dir or None)
    tokenizer = build_tokenizer(cfg)
    mesh = _build_mesh(cfg)
    mcfg, params = _build_model(cfg, mesh)

    # SP attention setup + config validation FIRST: a bad combination must
    # fail before the manager/fabric/reward workers are spawned and torn
    # back down on every attempt
    attn_fn = None
    packed_attn_fn = None
    sp_in_pipeline = False
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        # long-context: shard the sequence dim with a dedicated SP attention
        # (Ulysses all-to-all / ring ppermute) instead of whatever GSPMD
        # derives for dense attention over a sharded seq axis
        from polyrl_tpu.parallel.sequence import make_sp_attention

        sp = mesh.shape["sp"]
        # SP × TP composes: the SP attention keeps the head dim sharded
        # over tp (parallel/sequence.py specs), so tp-sharded projections
        # feed in with no head all-gather. Ulysses all-to-alls each tp
        # shard's LOCAL heads over sp → needs num_heads % (tp*sp) == 0;
        # ring never moves heads, so it has no extra constraint.
        tp = mesh.shape.get("tp", 1)
        if cfg.parallel.sp_mode == "ulysses" and mcfg.num_heads % (sp * tp):
            raise ValueError(
                f"ulysses SP needs num_heads ({mcfg.num_heads}) divisible "
                f"by sp*tp ({sp}*{tp}); use sp_mode=ring or different axes")
        if mesh.shape.get("pp", 1) > 1:
            # sp × pp: decoder.forward routes the whole stack through the
            # pipeline layers_fn, so the SP attention must live INSIDE the
            # stages — ring does (ring_attention_local in the pipeline's
            # {pp, sp}-manual region); Ulysses' head all-to-all would
            # reshard every stage boundary and is not implemented there.
            if cfg.parallel.sp_mode != "ring":
                raise NotImplementedError(
                    "parallel.sp > 1 with parallel.pp > 1 requires "
                    "sp_mode=ring (stage attention rings over sp inside "
                    f"the pipeline); got {cfg.parallel.sp_mode!r}")
            t_total = (cfg.trainer.max_prompt_length
                       + cfg.trainer.max_response_length)
            if t_total % sp:
                raise ValueError(
                    f"sp×pp needs max_prompt+max_response ({t_total}) "
                    f"divisible by sp ({sp})")
            sp_in_pipeline = True
        else:
            attn_fn = make_sp_attention(mesh, cfg.parallel.sp_mode)
            if cfg.trainer.use_remove_padding:
                # packed (remove-padding) long-context training composes
                # with SP via the segment-aware variant — the reference's
                # default long-context configuration (Ulysses over PACKED
                # varlen inputs, stream_dp_actor.py:37-47,135). The trainer
                # rounds pack_len up to a multiple of sp (_pack_geometry).
                # Only ulysses/ring have the segment-aware path; 'dense'
                # under sp>1 would silently hand GSPMD an unvalidated
                # composition.
                if cfg.parallel.sp_mode not in ("ulysses", "ring"):
                    raise NotImplementedError(
                        "use_remove_padding with parallel.sp > 1 requires "
                        "sp_mode=ulysses or ring (segment-aware SP "
                        f"attention); got sp_mode={cfg.parallel.sp_mode!r}")
                packed_attn_fn = make_sp_attention(
                    mesh, cfg.parallel.sp_mode, packed=True)

    layers_fn = None
    critic_layers_fn = None
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        # pipeline-parallel layer stack (parallel/pipeline.py): validate the
        # combination up front, same rationale as the SP block above.
        # packed × pp composes (stage attention takes per-batch segment
        # ids); sp × pp composes via sp_ring (validated above).
        from polyrl_tpu.parallel.pipeline import make_pipeline_layers_fn

        pp = mesh.shape["pp"]
        n_micro = cfg.parallel.pp_microbatches or 2 * pp
        if cfg.trainer.micro_batch_size % n_micro != 0:
            # not strictly required (the pipeline pads ragged feeds), but a
            # micro size that never fills the microbatches wastes the whole
            # configured pipeline width every step — treat as a config error
            raise ValueError(
                f"micro_batch_size {cfg.trainer.micro_batch_size} not "
                f"divisible by pp_microbatches {n_micro}")
        layers_fn = make_pipeline_layers_fn(mesh, mcfg, n_micro,
                                            remat=cfg.actor.remat,
                                            sp_ring=sp_in_pipeline)
        critic_layers_fn = make_pipeline_layers_fn(mesh, mcfg, n_micro,
                                                   remat=cfg.critic.remat,
                                                   sp_ring=sp_in_pipeline)

    if multihost.is_main():
        rollout = _build_rollout(cfg, mcfg, params, tokenizer, cleanup)
    else:
        # non-main hosts never open manager/fabric connections — batches
        # arrive via the trainer's broadcast plane (parallel/multihost.py)
        rollout = multihost.NullRollout(pad_token_id=tokenizer.pad_token_id)

    compute_score = (load_custom_score(cfg.reward.custom_score_path)
                     if cfg.reward.custom_score_path else None)
    if compute_score is None and cfg.reward.sandbox_url:
        # pod-scale code RL: ship code execution to the sandbox service,
        # bounded by a concurrency semaphore (reference reward.py:95-150)
        from polyrl_tpu.rewards.sandbox import SandboxClient

        compute_score = SandboxClient(
            cfg.reward.sandbox_url,
            max_concurrent=cfg.reward.sandbox_max_concurrent,
            timeout_s=cfg.reward.sandbox_timeout_s,
            memory_limit_mb=cfg.reward.sandbox_memory_limit_mb,
        ).compute_score
    reward_manager = load_reward_manager(
        cfg.reward.manager, tokenizer, compute_score=compute_score,
        num_workers=cfg.reward.num_workers)

    dataset = build_dataset(cfg, "train")
    loader = PromptDataLoader(dataset, cfg.trainer.train_batch_size,
                              shuffle=cfg.data.shuffle, seed=cfg.data.seed)

    actor = StreamActor(mcfg, cfg.actor, params, mesh=mesh, attn_fn=attn_fn,
                        layers_fn=layers_fn, packed_attn_fn=packed_attn_fn)
    critic = None
    if cfg.trainer.adv_estimator == "gae":
        import jax

        critic = StreamCritic(mcfg, cfg.critic, init_critic_params(
            jax.random.PRNGKey(cfg.trainer.seed + 1), mcfg), mesh=mesh,
            attn_fn=attn_fn, layers_fn=critic_layers_fn,
            packed_attn_fn=packed_attn_fn)
    # ReferencePolicy stays mesh-FREE deliberately: its params are a local
    # replicated copy and its feeds arrive as host numpy on every process —
    # a mesh-bound shard_map attn_fn would drag the global mesh into a
    # computation that must stay process-local in multi-host runs
    ref_policy = (ReferencePolicy(mcfg, params)
                  if (cfg.trainer.use_kl_in_reward or cfg.actor.use_kl_loss)
                  else None)
    logger = Tracking(backends=tuple(cfg.logging.backends),
                      path=cfg.logging.path or None)

    recorder = None
    if cfg.obs.recorder and multihost.is_main():
        # anomaly flight recorder (obs/recorder.py): watches the step
        # stream; an anomaly/crash/SIGTERM dumps a post-mortem bundle
        # (trace ring + step records + thread stacks) into the run dir
        from polyrl_tpu.obs.recorder import FlightRecorder

        rec_dir = (cfg.obs.recorder_dir
                   or (os.path.dirname(os.path.abspath(cfg.logging.path))
                       if cfg.logging.path else "polyrl_postmortem"))
        recorder = FlightRecorder(
            rec_dir, keep_steps=cfg.obs.recorder_keep_steps,
            z_threshold=cfg.obs.recorder_z, warmup=cfg.obs.recorder_warmup,
            max_bundles=cfg.obs.recorder_max_bundles)
        log.info("flight recorder armed: bundles -> %s/postmortem", rec_dir)

    if cfg.trainer.pipeline_depth > 0:
        # pipelined rollout (ARCHITECTURE.md "Pipeline overlap" +
        # "Bounded-staleness async training"): announce the mode +
        # staleness handling up front, since the step records will look
        # different (perf/pipeline_* + perf/staleness_* keys, async
        # weight pushes that may overlap generation at staleness_limit>1)
        log.info(
            "pipelined rollout enabled: depth=%d, staleness_limit=%d "
            "(%s), stale-rollout IS correction=%s (cap=%.2f)",
            cfg.trainer.pipeline_depth, cfg.trainer.staleness_limit,
            "hard wait_pushed fence" if cfg.trainer.staleness_limit <= 1
            else "bounded-staleness admission gate",
            "on" if cfg.trainer.rollout_is_correction else "OFF",
            cfg.trainer.rollout_is_cap)

    # training health plane (obs/rlhealth.py): default ON — training/*
    # step metrics, /statusz training section, training.json bundles.
    # obs.rlhealth=false turns it off (health=False disables the ledger).
    if cfg.obs.rlhealth:
        from polyrl_tpu.obs.rlhealth import TrainingHealthLedger

        health = TrainingHealthLedger(
            tail_steps=cfg.obs.rlhealth_tail,
            max_group_rows=cfg.obs.rlhealth_group_rows)
    else:
        health = False

    # closed-loop autoscaling (rollout/autoscale.py): default OFF — when
    # enabled (and a PoolManager exists to act on), the controller ticks
    # once per step from the fit loop; a spot-market trace doubles as its
    # CapacityProvider so scripted offers satisfy its add requests
    autoscale = None
    if (cfg.rollout.autoscale.enabled
            and getattr(rollout, "pool", None) is not None):
        from polyrl_tpu.rollout.autoscale import AutoscaleController

        capacity = None
        if cfg.rollout.spot_market.enabled:
            from polyrl_tpu.rollout.spotmarket import SpotMarket

            market = SpotMarket(
                rollout.pool, cfg.rollout.spot_market,
                injector=getattr(rollout, "fault_injector", None))
            market.start()
            cleanup.append(market.stop)
            capacity = market
        autoscale = AutoscaleController(
            rollout.pool, rollout.balance, cfg.rollout.autoscale,
            capacity=capacity, rollout=rollout)
        cleanup.append(autoscale.close)
        log.info("autoscale controller armed: envelope [%d, %d]%s",
                 cfg.rollout.autoscale.min_engines,
                 cfg.rollout.autoscale.max_engines,
                 " (dry-run)" if cfg.rollout.autoscale.dry_run else "")

    val_dataset = build_dataset(cfg, "val")
    trainer = StreamRLTrainer(
        cfg.trainer, actor, rollout, tokenizer, reward_manager, loader,
        critic=critic, ref_policy=ref_policy, logger=logger,
        val_dataset=val_dataset, recorder=recorder, health=health,
        autoscale=autoscale)
    if cfg.obs.statusz and multihost.is_main():
        # live health plane: GET /statusz answers "what is this trainer
        # doing right now" (shared schema with the rollout server's route)
        srv = trainer.start_statusz(port=cfg.obs.statusz_port,
                                    host=cfg.obs.statusz_host)
        cleanup.append(trainer.stop_statusz)
        log.info("trainer /statusz serving at http://%s/statusz",
                 srv.endpoint)
    return trainer


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m polyrl_tpu.train",
        description="Streaming PPO/GRPO trainer (colocated or disaggregated)")
    parser.add_argument("--config", default=None, help="YAML run config")
    parser.add_argument("--print-config", action="store_true",
                        help="resolve config, print as YAML, exit")
    parser.add_argument("overrides", nargs="*",
                        help="dotted overrides: trainer.total_steps=100 ...")
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    # multi-host bring-up first (no-op single-process): jax.distributed from
    # the standard env vars, before any backend use (parallel/distributed.py)
    from polyrl_tpu.parallel import distributed
    from polyrl_tpu.utils.xla_cache import configure_compile_cache

    distributed.initialize()
    configure_compile_cache()
    cfg = load_config(args.config, args.overrides)
    if args.print_config:
        import yaml

        print(yaml.safe_dump(to_dict(cfg), sort_keys=False))
        return 0

    cleanup: list = []
    try:
        trainer = build_trainer(cfg, cleanup)
        if trainer._recorder is not None:
            # SIGTERM (driver timeout, preemption) dumps a post-mortem
            # bundle before the process dies — main-thread entry only
            trainer._recorder.install_signal_handlers()
        history = trainer.fit()
        if history:
            last = history[-1]
            log.info("finished %d steps; final metrics: %s",
                     trainer.global_step,
                     {k: round(v, 5) for k, v in sorted(last.items())})
        return 0
    finally:
        for fn in reversed(cleanup):
            try:
                fn()
            except Exception:  # noqa: BLE001
                log.exception("cleanup failed")


if __name__ == "__main__":
    sys.exit(main())
