"""Multi-host stream trainer: N jax.distributed CPU processes (2 and 4)
run one fit step — process-0 control plane (manager/reward/weight push),
raw-bytes ibatch broadcast data plane, and cross-process dp (+fsdp at
nprocs=4) mesh sharding of the jitted updates (SURVEY.md L4; reference
worker groups stream_fsdp_workers.py:262-546)."""

import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
@pytest.mark.parametrize("nprocs", [2, 4])
def test_multi_process_fit_step(tmp_path, nprocs):
    """N jax.distributed processes run one fit step: process-0 control
    plane, raw-bytes ibatch broadcast, cross-process dp (and fsdp at
    nprocs=4) sharding; params must end bit-identical on every host."""
    port = _free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        JAX_ENABLE_X64="0",
    )
    # drop any inherited distributed env from the conftest/session
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES", "JAX_PROCESS_ID"):
        env.pop(k, None)
    worker = os.path.join(os.path.dirname(__file__), "multihost_fit_worker.py")
    procs = [
        subprocess.Popen([sys.executable, worker, str(port), str(pid), "",
                          str(nprocs)],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True,
                         cwd="/root/repo")
        for pid in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} rc={p.returncode}:\n{out[-4000:]}"
        assert "MULTIHOST_OK" in out, f"worker {pid}:\n{out[-4000:]}"
    # identical param sums printed by all (cross-checked in-process too)
    sums = [[ln for ln in o.splitlines() if "MULTIHOST_OK" in ln][0]
            .split("param_sum=")[1] for o in outs]
    assert len(set(sums)) == 1, sums


def _fit_one_step_on_mesh(extra_overrides, check):
    """Shared driver for the sp/pp/ep config-plane tests: build a trainer
    over the 8-virtual-device mesh with the given parallel overrides, run
    the per-test assertions, fit ONE step, and require finite results."""
    import jax
    import numpy as np

    from polyrl_tpu import train as train_mod
    from polyrl_tpu.config import load_config

    if jax.device_count() < 8:
        pytest.skip("needs the 8-virtual-device CPU mesh")
    cfg = load_config(None, [
        "model.dtype=float32",
        "rollout.backend=step", "rollout.batch_buckets=8",
        "rollout.prompt_buckets=16",
        "trainer.train_batch_size=4", "trainer.rollout_n=2",
        "trainer.ppo_mini_batch_size=8", "trainer.micro_batch_size=8",
        "trainer.min_stream_batch_size=8", "trainer.max_prompt_length=16",
        "trainer.max_response_length=16", "trainer.total_steps=1",
        "data.arithmetic_size=8"] + extra_overrides)
    cleanup: list = []
    trainer = train_mod.build_trainer(cfg, cleanup)
    check(trainer)
    hist = trainer.fit()
    for fn in reversed(cleanup):
        fn()
    assert len(hist) == 1
    assert np.isfinite(hist[0]["actor/pg_loss"])
    leaves = jax.tree_util.tree_leaves(trainer.actor.params)
    assert all(np.all(np.isfinite(np.asarray(x))) for x in leaves)


def _axes(trainer):
    return dict(zip(trainer.actor.mesh.axis_names,
                    trainer.actor.mesh.devices.shape))


def test_sp_trainer_single_process_mesh():
    """parallel.sp=2 wires Ulysses sequence-parallel attention into the
    actor and runs a real fit step over the 8-virtual-device mesh (dp=2,
    fsdp=2, sp=2) — the long-context training config end to end."""

    def check(trainer):
        assert _axes(trainer)["sp"] == 2
        assert "ulysses" in trainer.actor.attn_fn.__qualname__

    _fit_one_step_on_mesh(
        ['model.overrides={"vocab_size": 512}',
         "parallel.dp=2", "parallel.fsdp=2", "parallel.sp=2"], check)


def test_pp_trainer_single_process_mesh():
    """parallel.pp=2 wires the GPipe pipeline layer stack into the actor
    and runs a real fit step over the 8-virtual-device mesh (dp=2, fsdp=2,
    pp=2) — pipeline-parallel training end to end through the config
    plane."""

    def check(trainer):
        assert trainer.actor.layers_fn is not None
        assert _axes(trainer)["pp"] == 2

    _fit_one_step_on_mesh(
        ['model.overrides={"vocab_size": 512}',
         "parallel.dp=2", "parallel.fsdp=2", "parallel.pp=2",
         "parallel.pp_microbatches=2"], check)


def test_ep_moe_trainer_single_process_mesh():
    """parallel.ep=2 with the MoE preset: expert weights shard over the
    expert axis through the config plane and a real fit step runs over the
    8-virtual-device mesh — completing the sp/pp/ep config-plane trio."""

    def check(trainer):
        assert _axes(trainer)["ep"] == 2
        we = trainer.actor.params["layers"]["we_gate"]
        assert we.sharding.spec[1] == "ep", we.sharding.spec

    _fit_one_step_on_mesh(
        ["model.preset=moe-tiny", 'model.overrides={"use_qk_norm": false}',
         "parallel.dp=2", "parallel.fsdp=2", "parallel.ep=2"], check)
