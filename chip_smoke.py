#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user runs — the C++
manager (built from source during the run), ``rollout.serve.create_server``
with ``CBEngine``, ``RemoteRollout``, the TCP weight fabric and
``polyrl_tpu.train.main`` — at the full width of qwen3-1.7b with random
weights from a seed, in ONE process (the manager is the only child and
never touches JAX):

- leg A: rollout plane at full depth on one chip — 8 GRPO groups of 8
  through HTTP → manager → server → engine, then a second parameter tree
  pushed through the fabric and installed;
- leg B: three whole GRPO steps on one chip with the depth cut (and said
  so): generate, score, update, push, generate again;
- leg C (``--chips 4``): trainer on an fsdp=2 mesh over chips 0-1, two
  one-chip engines on chips 2 and 3, with placement asserted.

Exits non-zero unless ``jax.devices()[0].platform == "tpu"`` (a CPU
rehearsal at the ``tiny`` preset must be asked for with ``--rehearse-cpu``
and never prints a pass), on any failed assertion, and when a switch that
reroutes a kernel is set in the environment. Every number it prints is a
fact about this run, not a benchmark metric. A pass ends with one JSON
line: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import gc
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
# switches that would let a run on the chip quietly take another path
KERNEL_SWITCHES = ("POLYRL_PAGED_ATTN", "POLYRL_KV_WRITE",
                   "POLYRL_GROUPED_ATTN", "POLYRL_PEAK_TFLOPS")
# what each dispatcher must have taken on the chip / takes on the CPU
KERNELS_ON_TPU = {"paged_attention": ("lib",), "kv_write": ("pallas",),
                  "grouped": ("pallas",), "train_attention": ("flash",)}
KERNELS_ON_CPU = {"paged_attention": ("ref",), "kv_write": ("scatter",),
                  "grouped": ("ref",), "train_attention": ("dense",)}
# The manager hands an instance at most --max-assigned-batches requests per
# stats tick (default 4 a second). A whole group batch at once keeps the
# engine's dispatch shapes few (full admission waves, full decode groups):
# every other shape is another compile of the full-depth step.
MANAGER_ARGS = ["--health-check-interval-s", "0.2",
                "--stats-poll-interval-s", "0.2",
                "--max-assigned-batches", "64"]


@dataclasses.dataclass(frozen=True)
class Sizes:
    model: str
    dtype: str
    train_layers: int      # leg B's depth (leg A and C run the preset's)
    page_size: int
    prompt_len: int        # leg A prompt tokens == trainer max_prompt_length
    new_tokens: int        # leg A tokens per request
    response_len: int      # trainer max_response_length
    max_seq_len: int       # leg A engine
    num_pages: int         # leg A engine KV pool
    train_max_seq_len: int  # leg B/C engines
    groups: int
    group_size: int
    micro_batch: int


# Width is never cut. Leg B cuts DEPTH: parameters, Adam's two moments and
# the accumulation buffer are 4 x 3.44 GB at 28 layers, more than one 16 GB
# chip holds beside gradients and the engine's own copy; at 8 layers
# (0.71 B parameters) the state is 5.7 GB.
CHIP = Sizes(model="qwen3-1.7b", dtype="bfloat16", train_layers=8,
             page_size=64, prompt_len=128, new_tokens=64, response_len=128,
             max_seq_len=2048, num_pages=513, train_max_seq_len=512,
             groups=8, group_size=8, micro_batch=8)
REHEARSAL = Sizes(model="tiny", dtype="float32", train_layers=1,
                  page_size=8, prompt_len=16, new_tokens=8, response_len=16,
                  max_seq_len=64, num_pages=65, train_max_seq_len=64,
                  groups=2, group_size=4, micro_batch=4)

_children: list = []   # manager processes, for the watchdog


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def wait_until(pred, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while not pred():
        if time.monotonic() > deadline:
            raise TimeoutError(f"{what}: not within {timeout_s:.0f}s")
        time.sleep(0.1)


def start_watchdog(limit_s: float) -> None:
    """A hung phase must end the process (and the manager), not the
    caller's patience: dump every thread's stack and exit 4."""
    def watch() -> None:
        time.sleep(limit_s)
        print(f"[chip_smoke] still running after {limit_s:.0f}s; stacks:",
              file=sys.stderr, flush=True)
        faulthandler.dump_traceback(file=sys.stderr)
        for proc in _children:
            proc.kill()
        os._exit(4)

    threading.Thread(target=watch, name="smoke-watchdog", daemon=True).start()


def start_manager(tag: str):
    """Start the manager (built in main()); returns (process, 'host:port')."""
    from polyrl_tpu.manager.client import ManagerClient, spawn_rollout_manager

    proc, port = spawn_rollout_manager(
        "127.0.0.1:0", extra_args=MANAGER_ARGS,
        log_path=os.path.join(OUT_DIR, f"manager_{tag}.log"))
    _children.append(proc)
    endpoint = f"127.0.0.1:{port}"
    ManagerClient(endpoint).wait_healthy()
    return proc, endpoint


def stop_manager(proc) -> None:
    proc.terminate()
    try:
        proc.wait(timeout=5.0)
    except Exception:  # noqa: BLE001 — it ignored SIGTERM
        proc.kill()
        proc.wait(timeout=5.0)
    _children.remove(proc)


def make_server(sz: Sizes, *, layers: int | None, max_seq_len: int,
                num_pages: int | None, seed: int, manager_endpoint=None,
                devices=None):
    from polyrl_tpu.rollout.serve import create_server

    return create_server(
        sz.model, manager_endpoint=manager_endpoint, host="127.0.0.1",
        dtype=sz.dtype, seed=seed, backend="cb", page_size=sz.page_size,
        max_seq_len=max_seq_len, num_pages=num_pages,
        prompt_buckets=(sz.prompt_len,), devices=devices,
        model_overrides={"num_layers": layers} if layers else None)


def group_prompts(sz: Sizes) -> list[list[int]]:
    """``groups`` distinct prompts of exactly ``prompt_len`` byte tokens
    from the seeded arithmetic set, each repeated ``group_size`` times."""
    from polyrl_tpu.data.dataset import make_arithmetic_dataset
    from polyrl_tpu.utils.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    text = " ".join(r["prompt"] for r in make_arithmetic_dataset(64, seed=0))
    prompts = []
    for g in range(sz.groups):
        ids = tok.encode(f"{g}: {text}")
        check(len(ids) >= sz.prompt_len, "prompt text too short")
        prompts += [ids[:sz.prompt_len]] * sz.group_size
    return prompts


def tree_bitwise_equal(device_tree, other_tree) -> bool:
    """Leaf by leaf, comparing raw bytes (bf16 has no numpy equality)."""
    import jax
    import numpy as np

    a_leaves, a_def = jax.tree_util.tree_flatten(device_tree)
    b_leaves, b_def = jax.tree_util.tree_flatten(other_tree)
    if a_def != b_def:
        return False
    for a, b in zip(a_leaves, b_leaves):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            return False
        if a.tobytes() != b.tobytes():
            return False
    return True


def peak_bytes() -> list:
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append(stats.get("peak_bytes_in_use") if stats else None)
    return out


def check_kernels(platform: str, must_cover: tuple[str, ...]) -> dict:
    from polyrl_tpu.ops import dispatch

    taken = dispatch.taken()
    expect = KERNELS_ON_TPU if platform == "tpu" else KERNELS_ON_CPU
    for kernel in must_cover:
        check(kernel in taken, f"kernel {kernel} was never dispatched")
    for kernel, impls in taken.items():
        check(impls == expect[kernel],
              f"kernel {kernel} took {impls}, expected {expect[kernel]}")
    return taken


# -- leg A -------------------------------------------------------------------


def leg_a(sz: Sizes) -> dict:
    """Rollout plane at full depth: HTTP → manager → server → engine, then
    a second parameter tree through the fabric."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyrl_tpu.manager.client import ManagerClient
    from polyrl_tpu.models import decoder
    from polyrl_tpu.rollout.remote import RemoteRollout
    from polyrl_tpu.rollout.sampling import SamplingParams
    from polyrl_tpu.transfer import TransferInterface

    t0 = time.monotonic()
    proc, endpoint = start_manager("A")
    mgr = ManagerClient(endpoint)
    cfg = decoder.get_config(sz.model, dtype=getattr(jnp, sz.dtype))

    def init(seed: int):
        return decoder.init_params(jax.random.PRNGKey(seed), cfg)

    # the sender registers with the manager BEFORE the server does, as in
    # a deployment (trainer first): registration hands the server its
    # sender, and only then does it attach a receiver
    iface = TransferInterface(jax.eval_shape(lambda: init(0)),
                              manager_client=mgr,
                              advertise_host="127.0.0.1")
    srv = make_server(sz, layers=None, max_seq_len=sz.max_seq_len,
                      num_pages=sz.num_pages, seed=0,
                      manager_endpoint=endpoint)
    eng = srv.engine
    try:
        check(srv.receiver is not None, "server attached no weight receiver")
        # as a trainer's fit() does first: behind a fabric the manager
        # routes to an instance only once a push has reached it
        boot = iface.update_weights_with_agent(eng.params)
        wait_until(lambda: any(i["active"] for i in
                               mgr.get_instances_status()["instances"]),
                   600, "instance active in the manager")
        check(eng.weight_version == boot == 1,
              f"bootstrap push: engine at version {eng.weight_version}")
        remote = RemoteRollout(mgr, transfer=iface, pad_token_id=0)
        prompts = group_prompts(sz)
        n_req = len(prompts)
        sp = SamplingParams(temperature=1.0, max_new_tokens=sz.new_tokens,
                            stop_token_ids=())

        def one_round(cold: bool) -> None:
            pc = eng.prefix_cache
            forked0, hits0, misses0 = (eng.group_forked_requests,
                                       pc.req_hits, pc.req_misses)
            results = [r for chunk in remote.generate_stream(
                prompts, sp, group_size=sz.group_size,
                min_emit=sz.group_size) for _i, r in chunk]
            ok = [r for r in results if r.success]
            check(len(ok) == n_req, f"{len(ok)} of {n_req} requests succeeded")
            check(all(len(r.output_token_ids) == sz.new_tokens for r in ok),
                  "a request returned the wrong number of tokens")
            check(all(np.isfinite(r.output_token_logprobs).all() for r in ok),
                  "non-finite log-prob in a rollout")
            check(remote.dropped_groups == 0,
                  f"dropped_groups={remote.dropped_groups}")
            # every sibling attached to its leader's published prompt KV.
            # (group_forked_requests counts only the siblings that attached
            # in waves of two or more; how the manager's admission quota
            # slices the arrivals decides that, so it is not an exact count)
            siblings = sz.groups * (sz.group_size - 1)
            hits, misses = pc.req_hits - hits0, pc.req_misses - misses0
            check(hits + misses == n_req and hits >= siblings,
                  f"prefix-cache request hits {hits}, misses {misses}")
            if cold:
                check(misses == sz.groups,
                      f"{misses} prompts prefilled for {sz.groups} groups")
            forked = eng.group_forked_requests - forked0
            check(0 < forked <= hits, f"group_forked_requests {forked}")
            wait_until(lambda: eng.num_running == 0
                       and eng.deck.attributed_frac() == 1.0, 30,
                       "flight deck reconciled at quiescence")

        def greedy_probe() -> list[int]:
            probe = [prompts[0]]
            chunk = next(iter(remote.generate_stream(
                probe, SamplingParams(temperature=0.0, max_new_tokens=16,
                                      stop_token_ids=()),
                group_size=1, min_emit=1)))
            check(chunk[0][1].success, "greedy probe failed")
            return list(chunk[0][1].output_token_ids)

        one_round(cold=True)              # compiles every dispatch variant
        before = greedy_probe()
        setup_s = time.monotonic() - t0
        t1 = time.monotonic()
        one_round(cold=False)             # the same shapes, compiled
        run_s = time.monotonic() - t1
        check(eng.deck.shared_prefix_read_frac() > 0,
              "shared_prefix_read_frac == 0: the grouped kernel carried "
              "no decode")
        check(eng.grouped_decode_dispatches > 0, "no grouped decode dispatch")

        # a second seeded tree, kept on the host, through the fabric
        pushed = jax.tree_util.tree_map(np.asarray, jax.jit(lambda: init(1))())
        t2 = time.monotonic()
        version = iface.update_weights_with_agent(pushed)
        wait_until(lambda: eng.weight_version == version, 600,
                   f"engine installed weight version {version}")
        push_s = time.monotonic() - t2
        check(version == boot + 1, f"pushed version {version}")
        check(tree_bitwise_equal(eng.params, pushed),
              "engine params are not bitwise the pushed tree")
        after = greedy_probe()
        check(after != before, "greedy probe decodes the same after the push")
        check(eng.recoveries == 0, f"engine reset {eng.recoveries} times")
        facts = {"setup_s": round(setup_s, 1), "run_s": round(run_s, 1),
                 "push_install_s": round(push_s, 1), "requests": 2 * n_req,
                 "layers": cfg.num_layers,
                 "compiled_dispatch_variants":
                     len(eng._step_fns) + len(eng._prefill_fns),
                 "shared_prefix_read_frac":
                     round(eng.deck.shared_prefix_read_frac(), 4)}
    finally:
        srv.stop()   # engine threads, receiver agent, HTTP listener
        iface.close()
        stop_manager(proc)
    return facts


# -- legs B and C ------------------------------------------------------------


def train_leg(sz: Sizes, tag: str, *, layers: int | None, steps: int,
              trainer_devices: tuple[int, ...] = (),
              engine_devices: tuple[tuple[int, ...] | None, ...] = (None,),
              ) -> tuple[dict, object, list]:
    """Whole GRPO steps through ``polyrl_tpu.train.main`` against engines
    built in this process. Returns (facts, trainer, engine servers) with
    everything already torn down except the arrays the caller inspects."""
    import math

    import polyrl_tpu.train as train_mod
    from polyrl_tpu.manager.client import ManagerClient
    from polyrl_tpu.rollout.serve import register_with_manager

    t0 = time.monotonic()
    proc, endpoint = start_manager(tag)
    mgr = ManagerClient(endpoint)
    # engines take another seed than the trainer: their parameters can
    # only become the actor's through the fabric
    servers = [make_server(sz, layers=layers,
                           max_seq_len=sz.train_max_seq_len, num_pages=None,
                           seed=1 + i, devices=devs)
               for i, devs in enumerate(engine_devices)]
    steps_path = os.path.join(OUT_DIR, f"steps_{tag}.jsonl")
    captured: list = []
    errors: list = []

    def join_pool() -> None:
        # a deployment starts the trainer first; an engine that registers
        # before the trainer's sender exists is handed no sender and never
        # receives weights. The first version bump says the sender is up.
        try:
            wait_until(lambda: mgr.get_instances_status()
                       ["weight_version"] >= 1, 900,
                       "trainer's bootstrap push")
            for srv in servers:
                register_with_manager(srv, endpoint)
                check(srv.receiver is not None,
                      "server attached no weight receiver")
        except BaseException as exc:  # noqa: BLE001 — re-raised below
            errors.append(exc)

    build_trainer = train_mod.build_trainer

    def capturing_build(cfg, cleanup=None):
        trainer = build_trainer(cfg, cleanup)
        captured.append(trainer)
        # main() tears the fabric down right after fit(); the last push
        # may still be installing. Cleanups run in reverse: wait first.
        cleanup.append(lambda: wait_until(
            lambda: all(s.engine.weight_version
                        >= trainer.rollout.weight_version for s in servers),
            600, "engines installed the last push"))
        return trainer

    batch = sz.groups * sz.group_size
    argv = [
        f"model.preset={sz.model}", f"model.dtype={sz.dtype}",
        "tokenizer.kind=byte", "data.train_path=arithmetic",
        "rollout.mode=disaggregated", f"rollout.manager_endpoint={endpoint}",
        f"trainer.train_batch_size={sz.groups}",
        f"trainer.rollout_n={sz.group_size}",
        f"trainer.ppo_mini_batch_size={batch}",
        f"trainer.micro_batch_size={sz.micro_batch}",
        f"trainer.min_stream_batch_size={2 * sz.group_size}",
        f"trainer.max_prompt_length={sz.prompt_len}",
        f"trainer.max_response_length={sz.response_len}",
        f"trainer.total_steps={steps}", "trainer.seed=0",
        # the arithmetic reward of random weights is all zero, hence zero
        # GRPO advantages: the entropy term gives the update a gradient and
        # this rate lets it move bf16 weights, so the bitwise check at the
        # end compares trees that changed
        "actor.entropy_coeff=0.001", "actor.lr=0.001",
        "reward.num_workers=1",
        "logging.backends=console,jsonl", f"logging.path={steps_path}",
    ]
    if layers:
        argv.append('model.overrides={"num_layers": %d}' % layers)
    if trainer_devices:
        argv += [f"parallel.fsdp={len(trainer_devices)}",
                 "parallel.devices=" + ",".join(map(str, trainer_devices))]
    joiner = threading.Thread(target=join_pool, name="smoke-join-pool")
    joiner.start()
    train_mod.build_trainer = capturing_build
    try:
        rc = train_mod.main(argv)
    finally:
        train_mod.build_trainer = build_trainer
        joiner.join(timeout=30.0)
        for srv in servers:
            srv.stop()
        stop_manager(proc)
    wall_s = time.monotonic() - t0
    if errors:
        raise errors[0]
    check(rc == 0, f"train.main returned {rc}")
    trainer = captured[0]

    with open(steps_path) as f:
        records = [json.loads(line) for line in f]
    check(len(records) == steps, f"{len(records)} step records != {steps}")
    for i, rec in enumerate(records):
        for key in ("actor/pg_loss", "actor/grad_norm", "actor/entropy"):
            check(key in rec and math.isfinite(rec[key]),
                  f"step {i + 1}: {key} = {rec.get(key)}")
        check(rec["actor/nonfinite_skips"] == 0,
              f"step {i + 1}: nonfinite_skips {rec['actor/nonfinite_skips']}")
        check(rec["fault/dropped_groups"] == 0,
              f"step {i + 1}: dropped_groups {rec['fault/dropped_groups']}")
        check(rec["transfer/push_failures"] == 0,
              f"step {i + 1}: push_failures {rec['transfer/push_failures']}")
    check(records[-1]["actor/grad_norm"] > 0, "the update had no gradient")
    # every push round (the bootstrap, then one a step) verified on every
    # engine. A step's record can be taken before its own round's verify
    # lands, so the per-record count only has to keep up to one round
    # behind; the total is read after the last install.
    rounds = len(servers)
    verified = [r["transfer/rounds_verified"] for r in records]
    check(all(v >= (i + 1) * rounds for i, v in enumerate(verified)),
          f"transfer/rounds_verified fell behind the steps: {verified}")
    final = trainer.rollout.transfer.counters()
    check(final["transfer/rounds_verified"] == (1 + steps) * rounds
          and final["transfer/push_failures"] == 0,
          f"fabric after the run: {final}")
    exported = trainer.actor.export_params()
    for i, srv in enumerate(servers):
        eng = srv.engine
        # the bootstrap push, then one per step
        check(eng.weight_version == 1 + steps,
              f"engine {i} weight_version {eng.weight_version} != {1 + steps}")
        check(tree_bitwise_equal(eng.params, exported),
              f"engine {i} params are not bitwise the actor's")
        check(eng.recoveries == 0, f"engine {i} reset {eng.recoveries} times")
    # (which engine served is the manager's choice: a step's burst goes to
    # whichever instance has already installed the push)
    served = [srv.engine.total_tokens_served for srv in servers]
    check(sum(served) > 0, "no engine served a token")
    step_s = [r["perf/step_time_s"] for r in records]
    # the first step compiles the engine's dispatches and the trainer's
    # passes; the later steps run them
    facts = {"setup_s": round(wall_s - sum(step_s[1:]), 1),
             "run_s": round(sum(step_s[1:]), 1),
             "step_s": [round(t, 1) for t in step_s],
             "layers": trainer.actor.model_cfg.num_layers,
             "tokens_served_per_engine": served}
    return facts, trainer, servers


def leg_b(sz: Sizes) -> dict:
    from polyrl_tpu.models import decoder

    facts, _trainer, _servers = train_leg(sz, "B", layers=sz.train_layers,
                                          steps=3)
    full = decoder.PRESETS[sz.model].num_layers
    say(f"leg B depth CUT to {facts['layers']} of {full} layers "
        "(width unchanged)")
    return facts


def leg_c(sz: Sizes) -> dict:
    """Trainer on chips 0-1, one engine each on chips 2 and 3, full depth;
    placement asserted, not just completion."""
    import jax

    devs = jax.devices()
    facts, trainer, servers = train_leg(
        sz, "C", layers=None, steps=2, trainer_devices=(0, 1),
        engine_devices=((2,), (3,)))
    leaves = jax.tree_util.tree_leaves
    for x in leaves(trainer.actor.params):
        check(x.sharding.device_set == {devs[0], devs[1]},
              f"actor parameter on {x.sharding.device_set}")
    for i, srv in enumerate(servers):
        own = {devs[2 + i]}
        for x in leaves(srv.engine.params) + leaves(srv.engine._pools):
            check(x.devices() == own,
                  f"engine {i} array on {x.devices()}, expected {own}")
    peaks = peak_bytes()
    check(all(p for p in peaks[:4]) or devs[0].platform != "tpu",
          f"a chip was never used: peak bytes {peaks}")
    return facts


# -- entry -------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: legs A and B on one chip; 4: leg C")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the same legs at the tiny preset on the CPU; "
                         "never prints a pass")
    args = ap.parse_args(argv)

    switched = [v for v in KERNEL_SWITCHES if v in os.environ]
    if switched:
        print(f"chip_smoke: refusing to start with {switched} set: a smoke "
              "run takes the path the platform and shapes choose",
              file=sys.stderr)
        return 2
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}")

    import jax

    from polyrl_tpu.utils.xla_cache import (
        ENV_VAR, cache_entries, configure_compile_cache)

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke: no TPU (platform={platform}); a CPU rehearsal "
              "must be asked for with --rehearse-cpu", file=sys.stderr)
        return 3
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 3
    shutil.rmtree(OUT_DIR, ignore_errors=True)   # logs and records append
    os.makedirs(OUT_DIR)
    start_watchdog(1150.0 if args.chips == 1 else 2400.0)
    sz = REHEARSAL if args.rehearse_cpu else CHIP

    cache_dir = configure_compile_cache()
    entries_before = cache_entries(cache_dir)
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    import importlib.metadata as md

    def version(pkg: str) -> str:
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "not installed"

    say(f"platform={platform} device_kind={kind} devices={len(devs)} "
        f"using={args.chips}"
        + (" REHEARSAL (cpu, tiny preset): not a pass"
           if args.rehearse_cpu else ""))
    say(f"jax={jax.__version__} jaxlib={version('jaxlib')} "
        f"libtpu={version('libtpu')}")
    placed = ENV_VAR if os.environ.get(ENV_VAR) else "default"
    say(f"compile cache: {cache_dir} ({placed}), "
        f"{entries_before} entries before")

    from polyrl_tpu.manager.client import build_manager
    from polyrl_tpu.ops import dispatch

    # rebuild even a binary that looks fresh: it is git-ignored, and one
    # that rode along in a copy of the tree says nothing about main.cc and
    # the headers git tracks
    build_manager(force=True)
    dispatch.reset()
    if args.chips == 1:
        # everything lands on the default device, devs[0]
        say(f"leg A passed: {json.dumps(leg_a(sz))}")
        gc.collect()
        say(f"leg B passed: {json.dumps(leg_b(sz))}")
    else:
        say(f"leg C passed: {json.dumps(leg_c(sz))}")
    # leg C's prompts (the arithmetic set's) are shorter than a page, so
    # its groups share no full page and the grouped kernel has no work
    kernels = check_kernels(platform, tuple(
        k for k in KERNELS_ON_TPU if args.chips == 1 or k != "grouped"))
    say("kernels: " + ", ".join(f"{k}={'+'.join(kernels[k])}"
                                for k in KERNELS_ON_TPU if k in kernels))
    say(f"compile cache: {cache_entries(cache_dir)} entries after, "
        f"{cache_events['hits']} read, {cache_events['misses']} compiled "
        "and written")
    say(f"peak bytes per device: {peak_bytes()}")
    device = {"platform": platform, "kind": kind, "count": len(devs)}
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal": "passed", "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
