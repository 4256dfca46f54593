"""Rollout server launcher: ``python -m polyrl_tpu.rollout.serve``.

TPU-native equivalent of the reference's rollout-node launch path
(rlboost/sglang/launch_server.py:21-43 + patched_launch_server,
patches.py:513-543): build the engine, register with the rollout manager
(receiving the assigned weight-sender endpoint), spawn the weight-receiver
agent, then serve until shutdown.

The receiver's buffer layout is derived from THIS server's own model params
— the same scheme as the reference, where the TpWorker builds meta tensors
from its own model on bootstrap (patches.py:169-183); the sender validates
compatibility via the buffer-length handshake.
"""

from __future__ import annotations

import argparse
import logging
import os
import time

log = logging.getLogger(__name__)


def create_server(model: str, manager_endpoint: str | None = None,
                  host: str = "0.0.0.0", port: int = 0,
                  advertise_host: str = "127.0.0.1",
                  dtype: str = "bfloat16", seed: int = 0,
                  transfer_streams: int = 4,
                  batch_buckets: tuple[int, ...] | None = None,
                  prompt_buckets: tuple[int, ...] | None = None,
                  is_local: bool = False,
                  model_overrides: dict | None = None,
                  backend: str = "cb",
                  max_slots: int = 64,
                  page_size: int = 64,
                  max_seq_len: int = 16384,
                  num_pages: int | None = None,
                  steps_per_dispatch: int = 8,
                  pipeline_depth: int | None = None,
                  weight_quant: str = "",
                  warmup: bool = False,
                  tp: int = 1,
                  prefill_chunk: int = 0,
                  spec_tokens: int = 0,
                  spec_rounds: int = 2,
                  lora_rank: int = 0,
                  lora_alpha: float = 16.0,
                  salvage_partials: bool = True,
                  admit_wave: int | None = None,
                  admit_reorder_window: int = 8,
                  group_share: bool = True,
                  decode_group_share: bool = True,
                  group_preref_ttl_s: float | None = None,
                  kv_ledger: bool = True,
                  kv_cold_after_dispatches: int = 256,
                  kv_spill: bool = True,
                  kv_spill_host_gb: float = 4.0,
                  kv_spill_high_watermark: float = 0.92,
                  kv_spill_low_watermark: float = 0.80,
                  loop_profile: bool = True,
                  fault_injector=None,
                  devices: tuple[int, ...] | None = None):
    """Build engine + server, register with the manager, attach receiver.

    ``backend="cb"`` (default) serves with the paged continuous-batching
    engine; ``backend="step"`` keeps the bucketed v0 StepDecoder path.
    ``weight_quant="int8"`` serves with int8 weight-only quantized matmuls
    (models/quant.py) — halves weight HBM and fits 8B-class models on a
    16 GiB chip; weight pushes from the trainer stay bf16 on the wire and
    are re-quantized on arrival (server.weight_preprocess).

    ``devices``: indices into ``jax.devices()`` this engine may use (its
    ``tp`` chips are the first ``tp`` of them). None = the process's first
    ``tp`` devices. One process can hold several engines and a trainer,
    each on its own chips; naming the chips is how they are kept apart —
    parameters and KV pools are then created on exactly those chips."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.engine import RolloutEngine
    from polyrl_tpu.rollout.server import RolloutServer

    if weight_quant not in ("", "int8"):
        raise ValueError(f"unknown weight_quant {weight_quant!r}")
    mesh = None
    if tp > 1 or devices is not None:
        # tensor-parallel serving (the reference's --tp-size role,
        # launch_sglang.sh:13): params/KV shard over tp chips of this host.
        # Built BEFORE param materialization so weights never stage
        # unsharded through one chip's HBM (the models tp exists for don't
        # fit one chip). A named device list builds the mesh even at tp=1:
        # the mesh is what pins params and pools to those chips.
        if backend != "cb":
            raise NotImplementedError(
                "tp > 1 or a device list requires backend='cb'")
        from polyrl_tpu.parallel import mesh as meshlib

        all_devs = jax.devices()
        devs = (all_devs if devices is None
                else [all_devs[i] for i in devices])
        if len(devs) < tp or (devices is None and len(devs) % tp):
            raise ValueError(f"tp={tp} does not fit {len(devs)} devices")
        mesh = meshlib.make_mesh(meshlib.MeshConfig(fsdp=1, tp=tp),
                                 devs[:tp])
    if os.path.isdir(model):
        # a local HF checkpoint dir: pretrained weights + config.json arch.
        # With int8, the loader quantizes host-side — the full-precision
        # tree never exists on device (8B on a 16 GiB chip). Under tp the
        # leaves stay host-side and the engine device_puts each one
        # straight into its sharded layout.
        from polyrl_tpu.models.hf_loader import build_from_hf

        cfg, params = build_from_hf(model, dtype=getattr(jnp, dtype),
                                    overrides=model_overrides,
                                    quantize=weight_quant,
                                    to_device=mesh is None)
    else:
        cfg = decoder.get_config(model, dtype=getattr(jnp, dtype),
                                 **(model_overrides or {}))
        if weight_quant == "int8":
            from polyrl_tpu.models.quant import init_quantized_params

            # leaf-by-leaf device init in quantized form (same draws as
            # init_params; the bf16 tree never materializes)
            params = init_quantized_params(jax.random.PRNGKey(seed), cfg)
        elif mesh is not None:
            # born sharded: out_shardings places each leaf across tp at
            # init, no single-chip staging of the full tree
            from jax.sharding import NamedSharding, PartitionSpec as P

            specs = decoder.param_specs(cfg)
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
            params = jax.jit(
                lambda: decoder.init_params(jax.random.PRNGKey(seed), cfg),
                out_shardings=shardings)()
        else:
            params = jax.jit(
                lambda: decoder.init_params(jax.random.PRNGKey(seed), cfg))()
    weight_template = None
    weight_preprocess = None
    weight_apply = None
    if weight_quant == "int8" and lora_rank == 0:
        from polyrl_tpu.models.quant import quantize_params

        # the transfer fabric's layout/unflatten contract stays the
        # full-precision tree the TRAINER packs; quantize on arrival
        weight_template = jax.eval_shape(
            lambda: decoder.init_params(jax.random.PRNGKey(seed), cfg))
        weight_preprocess = quantize_params
    if lora_rank > 0:
        # LoRA DELTA sync (trainer.weight_sync=lora_delta): serve the
        # wrapped tree — the base (possibly int8 ⇒ QLoRA serving) never
        # changes, and each push carries only the a/b adapters (~rank/
        # hidden of the full tree), replacing them in place. The trainer
        # must run the same lora_rank/alpha.
        from polyrl_tpu.models import lora as lora_mod

        params = lora_mod.wrap_lora(params,
                                    jax.random.PRNGKey(7919 + lora_rank),
                                    lora_rank, lora_alpha)
        weight_template = lora_mod.adapter_template(cfg, lora_rank)
        weight_preprocess = None
        weight_apply = lora_mod.apply_adapters
    if backend == "cb":
        engine = CBEngine(
            cfg, params, pad_token_id=0, kv_cache_dtype=getattr(jnp, dtype),
            max_slots=max_slots, page_size=page_size, max_seq_len=max_seq_len,
            num_pages=num_pages, steps_per_dispatch=steps_per_dispatch,
            prompt_buckets=tuple(prompt_buckets) if prompt_buckets
            else (128, 256, 512, 1024, 2048, 4096), seed=seed, mesh=mesh,
            prefill_chunk=prefill_chunk, spec_tokens=spec_tokens,
            spec_rounds=spec_rounds, pipeline_depth=pipeline_depth,
            salvage_partials=salvage_partials, admit_wave=admit_wave,
            admit_reorder_window=admit_reorder_window,
            group_share=group_share, decode_group_share=decode_group_share,
            group_preref_ttl_s=group_preref_ttl_s,
            kv_ledger=kv_ledger,
            kv_cold_after_dispatches=kv_cold_after_dispatches,
            kv_spill=kv_spill,
            kv_spill_host_gb=kv_spill_host_gb,
            kv_spill_high_watermark=kv_spill_high_watermark,
            kv_spill_low_watermark=kv_spill_low_watermark,
            loop_profile=loop_profile)
    else:
        kwargs = {}
        if batch_buckets:
            kwargs["batch_buckets"] = tuple(batch_buckets)
        if prompt_buckets:
            kwargs["prompt_buckets"] = tuple(prompt_buckets)
        engine = RolloutEngine(cfg, params, pad_token_id=0,
                               kv_cache_dtype=getattr(jnp, dtype), **kwargs)
    if warmup and backend == "cb":
        # precompile every admission/decode bucket before the manager's
        # health check promotes this instance (the reference leans on
        # SGLang's own server warmup; here it's a first-class engine step)
        engine.warmup()
    server = RolloutServer(engine, host=host, port=port,
                           advertise_host=advertise_host)
    server.weight_template = weight_template
    server.weight_preprocess = weight_preprocess
    server.weight_apply = weight_apply
    server.fault = fault_injector
    server.start()

    if manager_endpoint:
        register_with_manager(server, manager_endpoint, is_local=is_local,
                              transfer_streams=transfer_streams)
    return server


def register_with_manager(server, manager_endpoint: str = "",
                          is_local: bool = False,
                          transfer_streams: int = 4,
                          client=None) -> None:
    """POST /register_rollout_instance; spawn the receiver agent pointed at
    the assigned weight sender (reference §3.2 startup flow). Passing an
    existing ``client`` (PoolManager.add_engine does) registers through it
    so a bound supervisor records the membership for /reconcile replay."""
    from polyrl_tpu.manager.client import ManagerClient
    from polyrl_tpu.transfer.agents import ReceiverAgent
    from polyrl_tpu.transfer.layout import build_layout, build_shard_spec

    if client is None:
        if not manager_endpoint:
            raise ValueError("register_with_manager needs an endpoint or "
                             "a client")
        client = ManagerClient(manager_endpoint)
    # remember who we joined: the /preempt → leave() lifecycle deregisters
    # through this endpoint on graceful departure
    server.manager_endpoint = client.endpoint.replace("http://", "")
    if is_local:
        client.register_local_rollout_instances([server.endpoint])
        return
    out = client.register_rollout_instance(server.endpoint)
    sender_ep = out.get("weight_sender_endpoint") or ""
    if sender_ep:
        # quantized engines keep the TRAINER's bf16 tree as the wire layout
        layout = build_layout(server.weight_template
                              if server.weight_template is not None
                              else server.engine.params)
        # advertise THIS engine's tp sharding so the sender builds the
        # (trainer shard → engine shard) resharding map per receiver.
        # Quantized/LoRA wire templates are host trees — they come back
        # replicated, which correctly disables the sharded plan for them.
        shard_spec = build_shard_spec(server.weight_template
                                      if server.weight_template is not None
                                      else server.engine.params, axis="tp")
        advertise = server.endpoint.rsplit(":", 1)[0]
        server.receiver = ReceiverAgent(
            layout, server.endpoint, sender_ep,
            num_streams=transfer_streams, advertise_host=advertise,
            shard_spec=shard_spec)
        server.receiver.start()
        log.info("receiver agent attached to sender %s", sender_ep)


def main() -> None:
    p = argparse.ArgumentParser(description="polyrl-tpu rollout server")
    p.add_argument("--model", default="qwen3-1.7b")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=30000)
    p.add_argument("--advertise-host", default="127.0.0.1")
    p.add_argument("--manager-endpoint", default=None,
                   help="host:port of the rollout manager")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--is-local", action="store_true",
                   help="register as a colocated (time-sliced) instance")
    p.add_argument("--transfer-streams", type=int, default=4)
    p.add_argument("--backend", default="cb", choices=("cb", "step"),
                   help="cb = paged continuous batching, step = bucketed v0")
    p.add_argument("--max-slots", type=int, default=64)
    p.add_argument("--page-size", type=int, default=64)
    p.add_argument("--max-seq-len", type=int, default=16384)
    p.add_argument("--steps-per-dispatch", type=int, default=8,
                   help="fused decode steps per device dispatch")
    p.add_argument("--pipeline-depth", type=int, default=None,
                   help="run-ahead dispatch window for the fetcher-thread "
                        "pipeline (default 16 / POLYRL_CB_PIPELINE); lower "
                        "it for tighter abort latency on colocated "
                        "time-sliced workers")
    p.add_argument("--weight-quant", default="", choices=("", "int8"),
                   help="int8 = weight-only quantized serving")
    p.add_argument("--warmup", action="store_true",
                   help="precompile all admission/decode buckets at launch")
    p.add_argument("--prompt-buckets", type=int, nargs="+", default=None,
                   help="prompt-length padding buckets (default "
                        "128 256 512 1024 2048 4096)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel serving over this many chips")
    p.add_argument("--devices", type=int, nargs="+", default=None,
                   help="indices into jax.devices() this engine may use "
                        "(default: the first --tp devices)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked prefill: prompts longer than this prefill "
                        "one page-aligned chunk per engine iteration, "
                        "interleaved with decode (0 = off)")
    p.add_argument("--spec-tokens", type=int, default=0,
                   help="prompt-lookup speculative decoding: verify this "
                        "many ngram-proposed draft tokens per decode "
                        "dispatch — up to N+1 tokens per weight read, "
                        "distribution-exact (0 = off)")
    p.add_argument("--spec-rounds", type=int, default=2,
                   help="fused device-side speculation rounds per dispatch "
                        "(proposals and acceptance never leave the chip)")
    p.add_argument("--admit-wave", type=int, default=None,
                   help="max admissions fused into one batched prefill "
                        "dispatch (default 8)")
    p.add_argument("--admit-reorder-window", type=int, default=8,
                   help="blocked queue heads admission may skip past while "
                        "forming a wave (0 = strict FIFO head-of-line)")
    p.add_argument("--no-group-share", action="store_true",
                   help="disable group-shared prefill (siblings admit as "
                        "singleton suffix dispatches — the A/B baseline)")
    p.add_argument("--no-decode-group-share", action="store_true",
                   help="disable shared-prefix decode attention (every "
                        "sibling re-streams the group's prompt KV per "
                        "decode step — the --decode-attn A/B baseline)")
    p.add_argument("--group-preref-ttl-s", type=float, default=None,
                   help="sibling-wait pre-ref expiry for groups whose "
                        "members never arrive (default 30)")
    p.add_argument("--no-kv-ledger", action="store_true",
                   help="disable the per-page KV memory ledger (the "
                        "memory statusz section / kv_*_page_frac gauges "
                        "go empty; engine output is identical either way)")
    p.add_argument("--kv-cold-after-dispatches", type=int, default=256,
                   help="idle age (decode dispatches) past which a "
                        "resident KV page counts as cold")
    p.add_argument("--no-kv-spill", action="store_true",
                   help="disable the host-RAM KV spill tier (cold "
                        "published pages stay in HBM and capacity "
                        "eviction destroys them; --no-kv-ledger also "
                        "disables spilling)")
    p.add_argument("--kv-spill-host-gb", type=float, default=4.0,
                   help="host-side capacity of the KV spill tier, GB")
    p.add_argument("--no-loop-profile", action="store_true",
                   help="disable the engine-loop profiler (the engine.loop "
                        "statusz block reads enabled=false and the "
                        "device_frac/accounting_frac gauges go absent; "
                        "sampled output is identical either way)")
    p.add_argument("--lora-rank", type=int, default=0,
                   help="LoRA delta sync: serve base + adapters; pushes "
                        "carry only adapters (match the trainer's rank)")
    p.add_argument("--lora-alpha", type=float, default=16.0)
    p.add_argument("--seed", type=int, default=0,
                   help="random-init seed for preset models (delta sync "
                        "presumes trainer and workers share the base — "
                        "normally via the same checkpoint dir)")
    args = p.parse_args()

    logging.basicConfig(level=logging.INFO)
    from polyrl_tpu.utils.xla_cache import configure_compile_cache

    configure_compile_cache()
    server = create_server(args.model, args.manager_endpoint, host=args.host,
                           port=args.port, advertise_host=args.advertise_host,
                           dtype=args.dtype, is_local=args.is_local,
                           seed=args.seed,
                           transfer_streams=args.transfer_streams,
                           backend=args.backend, max_slots=args.max_slots,
                           page_size=args.page_size,
                           max_seq_len=args.max_seq_len,
                           steps_per_dispatch=args.steps_per_dispatch,
                           pipeline_depth=args.pipeline_depth,
                           weight_quant=args.weight_quant,
                           warmup=args.warmup,
                           prompt_buckets=args.prompt_buckets,
                           tp=args.tp,
                           devices=args.devices,
                           prefill_chunk=args.prefill_chunk,
                           spec_tokens=args.spec_tokens,
                           spec_rounds=args.spec_rounds,
                           admit_wave=args.admit_wave,
                           admit_reorder_window=args.admit_reorder_window,
                           group_share=not args.no_group_share,
                           decode_group_share=not args.no_decode_group_share,
                           group_preref_ttl_s=args.group_preref_ttl_s,
                           kv_ledger=not args.no_kv_ledger,
                           kv_cold_after_dispatches=(
                               args.kv_cold_after_dispatches),
                           kv_spill=not args.no_kv_spill,
                           kv_spill_host_gb=args.kv_spill_host_gb,
                           loop_profile=not args.no_loop_profile,
                           lora_rank=args.lora_rank,
                           lora_alpha=args.lora_alpha)
    log.info("rollout server on %s", server.endpoint)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()


if __name__ == "__main__":
    main()
