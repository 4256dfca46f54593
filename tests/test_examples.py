"""Example recipes: preprocess scripts produce parquet the dataset layer and
reward dispatch consume (C19 parity)."""

import json
import subprocess
import sys
import time

import pytest

from polyrl_tpu.data.dataset import RLDataset
from polyrl_tpu.rewards.scorers import default_compute_score


def test_gsm8k_preprocess_roundtrip(tmp_path):
    src = tmp_path / "raw.jsonl"
    rows = [
        {"question": "Tom has 3 apples and buys 4 more. How many?",
         "answer": "He has 3+4=7 apples.\n#### 7"},
        {"question": "2 plus 2?", "answer": "#### 4"},
    ]
    src.write_text("\n".join(json.dumps(r) for r in rows))
    out_dir = tmp_path / "out"
    subprocess.run(
        [sys.executable, "examples/data_preprocess/gsm8k.py",
         "--local-json", str(src), "--out-dir", str(out_dir),
         "--split", "train"],
        check=True, capture_output=True, cwd="/root/repo")
    ds = RLDataset.from_parquet(str(out_dir / "train.parquet"))
    assert len(ds) == 2
    rec = ds[0]
    assert rec["ground_truth"] == "7"
    assert rec["data_source"] == "openai/gsm8k"
    assert rec["extra_info"]["split"] == "train"  # JSON round-trip
    assert "####" in rec["prompt"]
    # dispatch: a correct generation scores 1.0
    assert default_compute_score(rec["data_source"], "so #### 7",
                                 rec["ground_truth"]) == 1.0


def test_openr1_preprocess_roundtrip(tmp_path):
    src = tmp_path / "raw.jsonl"
    rows = [{"problem": "Compute 1+1.", "answer": "2"}]
    src.write_text("\n".join(json.dumps(r) for r in rows))
    out_dir = tmp_path / "out"
    subprocess.run(
        [sys.executable, "examples/data_preprocess/openr1.py",
         "--local-json", str(src), "--out-dir", str(out_dir)],
        check=True, capture_output=True, cwd="/root/repo")
    ds = RLDataset.from_parquet(str(out_dir / "train.parquet"))
    rec = ds[0]
    assert rec["data_source"] == "openr1_math"
    assert "\\boxed{}" in rec["prompt"]
    assert default_compute_score(rec["data_source"], "\\boxed{2}",
                                 rec["ground_truth"]) == 1.0


def test_recipe_yaml_loads():
    from polyrl_tpu import config as cfg_lib

    cfg = cfg_lib.load_config("examples/configs/stream_grpo_qwen3_1p7b.yaml")
    assert cfg.model.preset == "qwen3-1.7b"
    assert cfg.rollout.mode == "disaggregated"
    assert cfg.trainer.min_stream_batch_size == 16
    assert cfg.trainer.rollout_n == 8
    assert cfg.trainer.max_response_length == 14336
    # the round-2 features must actually be ON in the flagship recipe
    # (reference trains varlen-packed with a dynamic token budget,
    # run_async_grpo_pipeline.sh:29)
    assert cfg.trainer.use_remove_padding is True
    assert cfg.trainer.micro_token_budget == 16384


def test_hybrid_recipe_yaml_loads():
    from polyrl_tpu import config as cfg_lib

    cfg = cfg_lib.load_config(
        "examples/configs/stream_grpo_qwen3_1p7b_hybrid.yaml")
    assert cfg.rollout.colocated_local is True
    assert cfg.rollout.mode == "disaggregated"
    assert cfg.trainer.use_remove_padding is True
    assert cfg.actor.offload_optimizer is True
    assert "--initial-local-gen-s" in cfg.rollout.manager_args


def test_llama8b_recipe_yaml_loads():
    """The north-star 8B recipe parses into a valid RunConfig with the
    deployment-critical knobs set."""
    from polyrl_tpu import config as cfg_lib

    cfg = cfg_lib.load_config("examples/configs/stream_grpo_llama3_8b.yaml")
    assert cfg.model.preset == "llama3-8b"
    assert cfg.rollout.mode == "disaggregated"
    assert cfg.trainer.use_remove_padding
    assert cfg.trainer.micro_token_budget == 16384
    assert cfg.trainer.max_response_length == 14336
    assert cfg.rollout.prefill_chunk == 512
    assert cfg.parallel.fsdp == -1


@pytest.mark.slow
def test_llama8b_recipe_runs_end_to_end():
    """The north-star 8B recipe EXECUTES, not just parses: the actual YAML
    drives polyrl_tpu.train's assembly at true 8B dims (hidden 4096, 32/8
    heads, head_dim 128; depth 1 + small vocab + tiny batch/seq are the
    only CPU-physics deviations) — disaggregated mode with the real C++
    manager, fsdp=-1 over the 8-device mesh, varlen packing, optimizer
    offload, remat, CB engine with prefill chunking, and the real TCP
    weight fabric. Runs in a SUBPROCESS (tests/llama8b_e2e_worker.py) with
    the persistent XLA cache disabled: loading an XLA:CPU AOT executable
    compiled on a different physical host aborts the process, and that
    must never take the pytest session down with it."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # worker sets its own device count
    worker = os.path.join(os.path.dirname(__file__), "llama8b_e2e_worker.py")
    proc = subprocess.run([sys.executable, worker], env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True, timeout=1500, cwd="/root/repo")
    assert proc.returncode == 0, proc.stdout[-5000:]
    assert "LLAMA8B_E2E_OK" in proc.stdout, proc.stdout[-3000:]
