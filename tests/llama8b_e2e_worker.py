"""Subprocess worker for the north-star 8B recipe end-to-end test
(spawned by tests/test_examples.py).

Runs in its OWN process with the persistent XLA compilation cache
DISABLED: this VM can migrate across physical hosts, and loading an
XLA:CPU AOT executable compiled with different machine features aborts the
process (cpu_aot_loader SIGILL warning) — an in-process abort would kill
the whole pytest session. The 4096-wide compiles are redone each run; the
crash-isolation is worth it.
"""

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
    # ONE core drives all 8 virtual devices: under load (compile threads,
    # the rest of the suite) a collective's 8 participant threads can miss
    # XLA:CPU's default 40 s rendezvous termination window, which ABORTS
    # the process. Slow is fine; aborted is not.
    + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=120"
    + " --xla_cpu_collective_call_terminate_timeout_seconds=1200")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import numpy as np

    from polyrl_tpu import train as train_mod
    from polyrl_tpu.config import load_config

    assert jax.device_count() == 8, jax.device_count()
    cfg = load_config("examples/configs/stream_grpo_llama3_8b.yaml", [
        # CPU-test scaling (the ONLY deviations from the recipe):
        "model.dtype=float32",
        'model.overrides={"num_layers": 1, "vocab_size": 2048}',
        "rollout.colocated_local=true",   # serve in-process (single jax proc)
        "rollout.max_slots=8", "rollout.max_seq_len=256",
        "rollout.spec_tokens=2",  # speculation on the flagship path: spec ×
                                  # time-slice abort × weight push × manager
                                  # continuation all interact here
        "trainer.train_batch_size=4", "trainer.rollout_n=2",
        "trainer.ppo_mini_batch_size=8", "trainer.micro_batch_size=8",
        "trainer.min_stream_batch_size=8", "trainer.max_prompt_length=16",
        "trainer.max_response_length=16", "trainer.total_steps=1",
        "trainer.micro_token_budget=512", "trainer.save_freq=0",
        "trainer.test_freq=0", "reward.num_workers=2",
        "logging.backends=[console]", "data.arithmetic_size=8",
    ])
    assert cfg.model.preset == "llama3-8b"
    assert cfg.rollout.mode == "disaggregated"
    assert cfg.trainer.use_remove_padding and cfg.actor.offload_optimizer
    cleanup: list = []
    try:
        trainer = train_mod.build_trainer(cfg, cleanup)
        # the recipe's 8B dims actually reached the model
        mcfg = trainer.actor.model_cfg
        assert (mcfg.hidden_size, mcfg.num_heads, mcfg.num_kv_heads,
                mcfg.intermediate_size) == (4096, 32, 8, 14336)
        axes = dict(zip(trainer.actor.mesh.axis_names,
                        trainer.actor.mesh.devices.shape))
        assert axes["fsdp"] == 8, axes  # fsdp=-1 absorbed the mesh
        hist = trainer.fit()
        assert len(hist) == 1 and np.isfinite(hist[0]["actor/pg_loss"])
        # completed weight push: bootstrap + post-step land on the engine
        srv = trainer.rollout.local_server
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and srv.engine.weight_version < 2:
            time.sleep(0.2)
        assert srv.engine.weight_version >= 2, srv.engine.weight_version
    finally:
        for fn in reversed(cleanup):
            fn()
    print("LLAMA8B_E2E_OK", flush=True)


if __name__ == "__main__":
    main()
