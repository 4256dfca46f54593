"""MoE model family + real expert parallelism.

The reference stubs expert-parallel config without executing it
(reference workers/config/rollout.py:193-196); here MoE is implemented:
Qwen3-MoE architecture (softmax-over-all top-k routing), a dropless block
(choices sorted by expert, grouped matmuls; static shapes), and a real
``ep`` mesh axis the expert weights shard over. Correctness anchors: logits
parity against transformers' Qwen3MoeForCausalLM here, and against the
benchmark's float32 reference in ``test_moe_reference.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import blocks, decoder
from polyrl_tpu.models.decoder import _moe_mlp


def _mk(cfg_overrides=None, seed=0):
    cfg = decoder.get_config("moe-tiny", dtype=jnp.float32,
                             **(cfg_overrides or {}))
    params = decoder.init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def test_moe_router_selects_forced_expert():
    """With a router that sends every token to expert 0 with certainty, the
    MoE output equals expert 0's SwiGLU alone (gate weight 1 after top-k
    renorm)."""
    cfg, params = _mk()
    lp = dict(jax.tree_util.tree_map(lambda a: a[0], params["layers"]))
    d, e = cfg.hidden_size, cfg.num_experts
    # bias-free router: make expert 0 dominate for a constant input
    router = np.full((d, e), -1.0, np.float32)
    router[:, 0] = 1.0
    lp["router"] = jnp.asarray(router)
    x = jnp.ones((3, d), jnp.float32) * 0.1

    w_g, w_u, w_d = lp["we_gate"][0], lp["we_up"][0], lp["we_down"][0]
    gate = jax.nn.silu(x @ w_g)
    want_e0 = (gate * (x @ w_u)) @ w_d
    # k=1 isolates expert 0 (all rows on one expert: nothing is dropped)
    cfg1 = dataclasses.replace(cfg, num_experts_per_tok=1)
    out1, load = _moe_mlp(cfg1, x, lp)
    assert load.tolist() == [3, 1, 3]
    np.testing.assert_allclose(np.asarray(out1), np.asarray(want_e0),
                               rtol=1e-5, atol=1e-6)


def test_moe_forward_full_and_decode_paths():
    """Training (scan) and decode (unrolled KV-cache) paths trace and agree
    on the prefill prefix."""
    cfg, params = _mk()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                             cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(10), (2, 10))
    mask = jnp.ones((2, 10))
    full, _ = decoder.forward(params, cfg, ids, pos, mask)
    assert full.shape == (2, 10, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(full)))

    cache = decoder.make_cache(cfg, 2, 16)
    cmask = (jnp.arange(16) < 10).astype(jnp.float32)[None].repeat(2, 0)
    dec, _ = decoder.forward(params, cfg, ids, pos, cmask, cache=cache,
                             write_idx=0)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_moe_grads_flow_including_router():
    """Backprop through the remat'd scan path reaches router and expert
    weights (the training path for RL fine-tuning of MoE)."""
    cfg, params = _mk()
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    mask = jnp.ones((2, 8))

    def loss(p):
        logits, _ = decoder.forward(p, cfg, ids, pos, mask, remat=True)
        return jnp.mean(jax.nn.log_softmax(logits)[..., 0])

    grads = jax.grad(loss)(params)
    for key in ("router", "we_gate", "we_up", "we_down"):
        g = np.asarray(grads["layers"][key])
        assert np.all(np.isfinite(g))
        assert np.abs(g).max() > 0.0, f"zero grad for {key}"


@pytest.mark.parametrize("quant", [False, True])
def test_moe_hf_logits_parity(tmp_path, quant):
    """Logits parity against transformers Qwen3MoeForCausalLM (the MoE
    correctness anchor), on the default path: the block is dropless as
    HF's loop is."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from polyrl_tpu.models.hf_loader import config_from_hf, load_hf_params

    hf_cfg = transformers.Qwen3MoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=48, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
        max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=10000.0,
        tie_word_embeddings=False, decoder_sparse_step=1, mlp_only_layers=[],
    )
    torch.manual_seed(0)
    model = transformers.AutoModelForCausalLM.from_config(hf_cfg).eval()
    out_dir = tmp_path / "qwen3moe"
    model.save_pretrained(out_dir, safe_serialization=True)

    cfg = config_from_hf(str(out_dir), dtype=jnp.float32)
    assert cfg.num_experts == 4 and cfg.num_experts_per_tok == 2
    assert cfg.moe_intermediate_size == 48 and cfg.use_qk_norm
    params = load_hf_params(str(out_dir), cfg,
                            quantize="int8" if quant else "")

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)
    with torch.no_grad():
        want = model(torch.from_numpy(ids).long()).logits.numpy()
    pos = np.broadcast_to(np.arange(12, dtype=np.int32), (2, 12))
    mask = np.ones((2, 12), np.float32)
    got, _ = decoder.forward(params, cfg, jnp.asarray(ids), jnp.asarray(pos),
                             jnp.asarray(mask))
    got = np.asarray(got)
    if quant:
        # int8 attention/head/experts: statistical closeness, not
        # elementwise parity
        nrmse = np.sqrt(np.mean((got - want) ** 2)) / (np.std(want) + 1e-9)
        assert nrmse < 0.05, nrmse
    else:
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_moe_expert_parallel_mesh(devices8):
    """The ep axis is REAL: expert weights placed over a dp1·fsdp2·tp2·ep2
    mesh; with the mesh set (``parallel.mesh.under``) each ep rank computes
    its own experts' rows and the results are summed over ep
    (``_expert_mix_sharded``); output matches the single-device forward."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polyrl_tpu.parallel import mesh as meshlib

    cfg, params = _mk()
    mesh = meshlib.make_mesh(meshlib.MeshConfig(dp=1, fsdp=2, tp=2, ep=2),
                             devices8)
    specs = decoder.param_specs(cfg)
    assert specs["layers"]["we_gate"] == P(None, meshlib.EP, meshlib.FSDP,
                                           meshlib.TP)
    sharded = meshlib.shard_params(mesh, params, specs)
    we = sharded["layers"]["we_gate"]
    assert we.sharding.spec == specs["layers"]["we_gate"]

    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    mask = jnp.ones((2, 8))
    ref, _ = decoder.forward(params, cfg, ids, pos, mask)

    @jax.jit
    def fwd(p, i, po, m):
        logits, _ = decoder.forward(p, cfg, i, po, m)
        return logits

    fwd_ep = meshlib.under(mesh, fwd)   # as the trainer and the engine call
    assert "psum" in str(meshlib.under(mesh, jax.make_jaxpr(fwd))(
        sharded, ids, pos, mask))
    got = fwd_ep(sharded,
                 jax.device_put(ids, NamedSharding(mesh, P())),
                 jax.device_put(pos, NamedSharding(mesh, P())),
                 jax.device_put(mask, NamedSharding(mesh, P())))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    # and the gradient through the manual region and its sums
    def loss(p):
        logits, _ = decoder.forward(p, cfg, ids, pos, mask)
        return jnp.sum(jnp.tanh(logits))

    want_g = jax.grad(loss)(params)["layers"]
    got_g = meshlib.under(mesh, jax.jit(jax.grad(loss)))(sharded)["layers"]
    for key in ("router", "we_gate", "we_up", "we_down", "wo"):
        want_k = np.asarray(want_g[key])
        np.testing.assert_allclose(
            np.asarray(got_g[key]), want_k, rtol=2e-4,
            atol=2e-5 * np.abs(want_k).max(), err_msg=key)


def test_moe_cb_engine_decode():
    """The production CB paged engine serves an MoE model (decode path
    routes per-token through the experts)."""
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg, params = _mk()
    engine = CBEngine(cfg, params, pad_token_id=0, max_slots=4, page_size=8,
                      max_seq_len=64, prompt_buckets=(8,), num_pages=64)
    try:
        sp = SamplingParams(temperature=0.0, max_new_tokens=6,
                            stop_token_ids=())
        outs = engine.generate([[1, 2, 3, 4], [9, 8, 7]], sp, timeout=120.0)
        assert all(len(o["token_ids"]) == 6 for o in outs)
    finally:
        engine.stop()


def test_moe_quantize_params_covers_experts_not_router():
    """Experts (the bulk of MoE params) quantize; the tiny routing matrix
    stays full precision (routing decisions are precision-sensitive)."""
    from polyrl_tpu.models.quant import QuantWeight, quantize_params

    cfg, params = _mk()
    qp = quantize_params(params)
    assert isinstance(qp["layers"]["wq"], QuantWeight)
    assert isinstance(qp["layers"]["we_gate"], QuantWeight)
    assert qp["layers"]["we_gate"].q.dtype == jnp.int8
    assert qp["layers"]["we_gate"].scale.shape == (
        cfg.num_layers, cfg.num_experts, cfg.moe_intermediate_size)
    assert not isinstance(qp["layers"]["router"], QuantWeight)
    # quantized MoE forward tracks full precision
    ids = jax.random.randint(jax.random.PRNGKey(9), (2, 10), 1,
                             cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(10), (2, 10))
    mask = jnp.ones((2, 10))
    ref, _ = decoder.forward(params, cfg, ids, pos, mask)
    got, _ = decoder.forward(qp, cfg, ids, pos, mask)
    ref, got = np.asarray(ref, np.float32), np.asarray(got, np.float32)
    nrmse = np.sqrt(np.mean((ref - got) ** 2)) / (np.std(ref) + 1e-9)
    assert nrmse < 0.05, nrmse


def test_moe_grpo_e2e_fit_step():
    """Full streaming GRPO fit on the MoE family: rollout through the
    bucketed engine, packed grads through router + experts, weight push —
    RL fine-tuning of a MoE model end to end."""
    from polyrl_tpu.data.dataset import PromptDataLoader, make_arithmetic_dataset
    from polyrl_tpu.rewards.manager import load_reward_manager
    from polyrl_tpu.rollout.engine import RolloutEngine
    from polyrl_tpu.trainer.actor import ActorConfig, StreamActor
    from polyrl_tpu.trainer.stream_trainer import StreamRLTrainer, TrainerConfig
    from polyrl_tpu.utils.tokenizer import ByteTokenizer

    cfg = decoder.get_config("moe-tiny", dtype=jnp.float32,
                             max_position_embeddings=128)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    params0 = jax.tree_util.tree_map(lambda x: np.asarray(x).copy(), params)
    tok = ByteTokenizer()
    engine = RolloutEngine(cfg, params, pad_token_id=tok.pad_token_id,
                           batch_buckets=(16,), prompt_buckets=(16,),
                           kv_cache_dtype=jnp.float32)
    tcfg = TrainerConfig(
        train_batch_size=4, rollout_n=2, ppo_mini_batch_size=8,
        micro_batch_size=4, min_stream_batch_size=4,
        max_prompt_length=16, max_response_length=8,
        adv_estimator="grpo", total_steps=1, temperature=1.0,
    )
    actor = StreamActor(cfg, ActorConfig(lr=1e-3, remat=True), params)
    trainer = StreamRLTrainer(
        tcfg, actor, engine, tok,
        load_reward_manager("naive", tok, num_workers=1),
        PromptDataLoader(make_arithmetic_dataset(32), tcfg.train_batch_size),
    )
    history = trainer.fit()
    assert len(history) == 1 and np.isfinite(history[0]["actor/pg_loss"])
    # router and expert weights both moved
    for key in ("router", "we_gate"):
        a0 = params0["layers"][key]
        a1 = np.asarray(actor.params["layers"][key])
        assert np.abs(a1 - a0).sum() > 0.0, f"{key} unchanged"


def test_mixtral_hf_logits_parity(tmp_path):
    """Mixtral family parity: block_sparse_moe tensor naming and the
    softmax-after-top-k routing (== softmax-all → top-k → renorm)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from polyrl_tpu.models.hf_loader import config_from_hf, load_hf_params

    hf_cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = transformers.AutoModelForCausalLM.from_config(hf_cfg).eval()
    out_dir = tmp_path / "mixtral"
    model.save_pretrained(out_dir, safe_serialization=True)

    cfg = config_from_hf(str(out_dir), dtype=jnp.float32)
    assert cfg.num_experts == 4 and cfg.num_experts_per_tok == 2
    assert cfg.moe_intermediate_size == 48 and not cfg.use_qk_norm
    params = load_hf_params(str(out_dir), cfg)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)
    with torch.no_grad():
        want = model(torch.from_numpy(ids).long()).logits.numpy()
    pos = np.broadcast_to(np.arange(12, dtype=np.int32), (2, 12))
    mask = np.ones((2, 12), np.float32)
    got, _ = decoder.forward(params, cfg, jnp.asarray(ids), jnp.asarray(pos),
                             jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def _count_ep_traces(monkeypatch) -> list:
    """Count the traces that enter ``blocks._expert_mix_sharded``."""
    calls = []
    real = blocks._expert_mix_sharded

    def counted(*args):
        calls.append(1)
        return real(*args)

    monkeypatch.setattr(blocks, "_expert_mix_sharded", counted)
    return calls


def test_moe_packed_logprobs_under_ep_match_single(devices8, monkeypatch):
    """Packed (remove-padding) training on the MoE family under a real
    expert-parallel mesh, entered as the trainer enters it
    (``StreamActor(mesh=...)``): the packed logprob pass with experts
    sharded over ep goes manual over ep (``_expert_mix_sharded``) and matches
    the single-device segment-id pass (packed × ep cell — ep needs no
    special attention; pack-pad columns are segment 0 and loss-masked, and
    route nowhere via token_valid)."""
    from polyrl_tpu.parallel import mesh as meshlib
    from polyrl_tpu.trainer.actor import (ActorConfig, StreamActor,
                                          _packed_logprobs_entropy)

    cfg, params = _mk()
    b, t = 2, 16
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, t)), jnp.int32)
    seg = np.zeros((b, t), np.int32)
    pos = np.zeros((b, t), np.int32)
    lm = np.zeros((b, t), np.float32)
    for s, e, sid in [(0, 6, 1), (6, 13, 2)]:  # trailing pack-pad cols 13..15
        seg[:, s:e] = sid
        pos[:, s:e] = np.arange(e - s)
        lm[:, s + 2:e] = 1.0
    am = (seg > 0).astype(np.float32)
    seg, pos, lm, am = map(jnp.asarray, (seg, pos, lm, am))

    want_lp, _ = _packed_logprobs_entropy(
        params, cfg, ids, pos, am, seg, False, False, loss_mask=lm)

    mesh = meshlib.make_mesh(meshlib.MeshConfig(dp=1, fsdp=2, tp=2, ep=2),
                             devices8)
    calls = _count_ep_traces(monkeypatch)
    actor = StreamActor(cfg, ActorConfig(lr=1e-4, remat=False), params,
                        mesh=mesh)
    assert actor.params["layers"]["we_gate"].sharding.spec[1] == meshlib.EP
    got_lp, _ = actor.compute_log_prob_packed(
        {"input_ids": ids, "positions": pos, "attention_mask": am,
         "segment_ids": seg, "loss_mask": lm}, compute_entropy=False)
    assert calls, "the trainer's program did not take the ep path"
    np.testing.assert_allclose(np.asarray(got_lp), np.asarray(want_lp),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("tp,ep", [(1, 2), (2, 2)])
def test_moe_cb_engine_under_ep_matches_single(devices8, monkeypatch, tp, ep):
    """The engine under an ep mesh (``CBEngine(mesh=...)``), alone and
    with tp: prefill and decode programs go manual over the mesh with the
    expert stacks left whole (and the attention kernels' wrappers are
    taken without tp too, as a TPU needs on any mesh of several chips),
    and greedy decoding gives the single-device engine's tokens."""
    from polyrl_tpu.parallel import mesh as meshlib
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg, params = _mk()
    kw = dict(pad_token_id=0, kv_cache_dtype=jnp.float32, max_slots=4,
              page_size=8, max_seq_len=64, prompt_buckets=(8,), num_pages=64)
    sp = SamplingParams(temperature=0.0, max_new_tokens=8, stop_token_ids=())
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7]]
    single = CBEngine(cfg, params, **kw)
    try:
        want = [o["token_ids"] for o in
                single.generate(prompts, sp, timeout=120.0)]
    finally:
        single.stop()
    mesh = meshlib.make_mesh(meshlib.MeshConfig(fsdp=1, tp=tp, ep=ep),
                             devices8[:tp * ep])
    calls = _count_ep_traces(monkeypatch)
    engine = CBEngine(cfg, params, mesh=mesh, **kw)
    try:
        assert engine._tp_kv_write() is not None
        assert engine.params["layers"]["we_down"].sharding.spec[1] == \
            meshlib.EP
        got = [o["token_ids"] for o in
               engine.generate(prompts, sp, timeout=120.0)]
    finally:
        engine.stop()
    # every layer of a prefill and of a decode program, at the least
    assert len(calls) >= 2 * cfg.num_layers, len(calls)
    assert got == want, (got, want)


@pytest.mark.parametrize("rows,quant", [(9, False), (33, False), (9, True)],
                         ids=["9 rows", "33 rows", "int8 experts"])
def test_moe_block_by_table_is_the_tiled_path(monkeypatch, rows, quant):
    """A decode step's form on a TPU (the kernels take a tile's rows from
    the tokens by table and sum them back: ``blocks._expert_rows``),
    interpreted here, against the tiled path, with a row without a
    request."""
    from polyrl_tpu.models.quant import quantize_tensor
    from tests.moe_forms import assert_both_forms_agree

    cfg, params = _mk()
    lp = dict(jax.tree_util.tree_map(lambda a: a[0], params["layers"]))
    if quant:
        for key in blocks.EXPERT_KEYS:
            lp[key] = quantize_tensor(lp[key], contract_axis=-2)
    x = jax.random.normal(jax.random.PRNGKey(3), (rows, cfg.hidden_size))
    valid = jnp.arange(rows) != 2
    out = assert_both_forms_agree(monkeypatch, cfg, x, lp, valid)
    assert np.all(np.asarray(out)[2] == 0.0)


@pytest.mark.parametrize("fused_steps", [4, 1])
def test_moe_gather_kernel_steps_move_with_an_engine_that_took_the_kernels(
        monkeypatch, fused_steps):
    """The engine asks once, at construction (``hybrid.step_counters``);
    a dispatch's steps reach ``moe_gather_kernel_steps`` when it lands,
    beside ``decode_steps_done``, and stay out of it on an engine whose
    program took the tiled form; the tokens are the same either way."""
    from polyrl_tpu.ops import grouped_matmul
    from polyrl_tpu.rollout.cb_engine import CBEngine
    from polyrl_tpu.rollout.sampling import SamplingParams

    cfg, params = _mk()
    tokens = {}
    for kernel in (False, True):
        if kernel:
            monkeypatch.setattr(grouped_matmul, "in_kernel",
                                grouped_matmul.rows_by_table)
        eng = CBEngine(cfg, params, max_slots=2, page_size=8, max_seq_len=32,
                       prompt_buckets=(16,), num_pages=16,
                       steps_per_dispatch=fused_steps,
                       kv_cache_dtype=jnp.float32)
        assert ("moe_gather_kernel_steps"
                in eng._step_counters[False]) is kernel
        eng.start()
        try:
            (out,) = eng.generate([[3, 1, 4, 1, 5]], SamplingParams(
                temperature=0.0, max_new_tokens=9))
            info = eng.loop_profile_info()
        finally:
            eng.stop()
        tokens[kernel] = out["token_ids"]
        assert info["decode_steps_done"] >= 8
        assert info["moe_gather_kernel_steps"] == (
            info["decode_steps_done"] if kernel else 0)
    assert len(tokens[True]) == 9 and tokens[True] == tokens[False]
