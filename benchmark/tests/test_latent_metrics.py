"""``costs_latent.py`` against the numbers of ISSUE 35, by hand and against
the parameter tree the program builds; the three readers this cell brings
(``mla_core_roofline.latent``, ``decode_step_roofline.latent``,
``mla_proj_ms``) on a hand-made decoded trace with fabricated counters,
and None where a scope, a counter or a latent key is absent (the parent's
program, a dense model under a ``--rehearse-cpu`` walk); the plane walked
end to end on a tiny model of the family; and one walk in which
``correct`` has to come out false.

Run by hand: ``python -m pytest benchmark/tests -q``."""

import os
import time

import numpy as np
import pytest

from benchmark.lib import costs, costs_latent, costs_moe, harness, xspans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = os.path.join(HERE, "tests", "data", "tiny_scoped_tpu.xplane.pb")
READERS = ("mla_core_roofline.latent", "decode_step_roofline.latent",
           "mla_proj_ms")
V5E = {"flops": 197e12, "bytes": 819e9}


def _dots():
    return harness.load_config(os.path.join(HERE, "configs", "dots.vlm1.json"))


def test_costs_of_the_published_sizes_are_the_issues_numbers():
    c = _dots()["config"]
    assert costs_latent.is_latent(c)
    assert costs_latent.plan(c) == ["dense", "moe", "moe", "moe", "moe"]
    # Wqa 7168 x 1536, Wqb 1536 x 24576, Wkva 7168 x 576, Wkvb 512 x 32768,
    # Wo 16384 x 7168, and the two latents' norms
    assert costs_latent.mla_params(c) == (
        7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768
        + 16384 * 7168 + 1536 + 512)
    assert costs_latent.mla_params(c) == pytest.approx(187.1e6, rel=1e-3)
    assert costs_latent.expert_params(c) == 3 * 7168 * 2048 == 44040192
    assert costs_moe.expert_bytes(c) == 88080384                  # 88.1 MB
    assert costs_latent.shared_params(c) == 44040192
    assert costs_latent.router_params(c) == 7168 * 256
    gb = lambda n: 2 * n / 1e9                                  # noqa: E731
    assert gb(costs_latent.layer_params(c, "moe")) == pytest.approx(
        1.875, abs=1e-3)
    assert gb(costs_latent.layer_params(c, "dense")) == pytest.approx(
        1.167, abs=1e-3)
    assert gb(costs_latent.vocab_params(c)) == pytest.approx(0.463, abs=1e-3)
    assert gb(costs_latent.weight_params(c)) == pytest.approx(9.131, abs=2e-3)
    assert gb(costs_latent.dense_params(c)) == pytest.approx(3.26, abs=1e-2)
    # a token keeps 576 values a layer: 1,152 B, 5,760 B over the 5 layers
    assert costs_latent.paged_bytes_per_token(c) == 5 * 1152
    assert _dots()["serve"]["kv_pool_bytes"] == 10240 * 64 * 5760
    # the attention: 2 x 128 heads x (576 + 512) FLOPs a row a layer, 242 a
    # byte, where v5e's ridge is 240: the FLOP roof binds, by 0.5%
    assert costs_latent.mla_core_flops(c, 1) == 5 * 278528
    assert costs_latent.mla_core_bytes(c, 1) == 5 * 1152
    least, roof = costs_latent.mla_core_least(c, 500_000, V5E)
    assert roof == "flops"
    assert least == pytest.approx(5 * 500_000 * 278528 / 197e12)
    assert least == pytest.approx(3.53e-3, rel=1e-2)
    assert costs_latent.mla_core_least(
        c, 500_000, {"flops": 400e12, "bytes": 819e9})[1] == "bytes"
    # ISSUE 35's step: 14 of 16 held experts hit in each of 4 sparse
    # layers, 0.5M rows: 3.26 GB + 4.93 GB at 819 GB/s, and the attention
    step = costs_latent.decode_step_least(c, 14 * 4, 500_000, V5E)
    assert step == pytest.approx((3.262e9 + 56 * 88.08e6) / 819e9 + least,
                                 rel=1e-3)
    assert step == pytest.approx(13.5e-3, rel=2e-2)


def test_the_programs_tree_has_the_counted_parameters():
    """``deployment`` in the configuration's file: recounted from the tree
    the program builds, and the FLOPs from the shapes of the program's own
    absorbed query and latent row."""
    import jax

    from polyrl_tpu.models import cache_spec, decoder

    cfg = decoder.get_config(_dots()["preset"])
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree_util.tree_leaves(tree))
    c = _dots()["config"]
    assert n == costs_latent.weight_params(c)
    mla = tree["layers"]["mla"]
    assert sum(a.size for a in jax.tree_util.tree_leaves(mla)) == \
        5 * costs_latent.mla_params(c)
    # what the program lays out a token: rows of 640 in 5 pools
    assert cache_spec.paged_bytes_per_token(cfg) == 5 * 640 * 2
    assert cache_spec.latent_width(cfg) * 2 * 5 == \
        costs_latent.paged_bytes_per_token(c)
    # the kernel's two products over a row: [H, w] x [w] and [H] x [rank]
    assert 2 * cfg.num_heads * (cache_spec.latent_width(cfg)
                                + cfg.kv_lora_rank) * cfg.num_layers == \
        costs_latent.mla_core_flops(c, 1)


TINY = {"hidden_size": 8, "num_attention_heads": 2, "kv_lora_rank": 6,
        "q_lora_rank": 5, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
        "v_head_dim": 4, "intermediate_size": 16, "moe_intermediate_size": 4,
        "n_shared_experts": 1, "n_routed_experts": 3, "num_experts": 3,
        "published": {"n_routed_experts": 12}, "num_experts_per_tok": 2,
        "vocab_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1}


def test_costs_of_a_hand_counted_tiny_case():
    c = TINY
    assert costs_latent.plan(c) == ["dense", "moe", "moe"]
    assert costs_latent.paged_bytes_per_token(c) == 3 * (6 + 2) * 2
    mla = 8 * 5 + 5 + 5 * 2 * 6 + 8 * 8 + 6 + 6 * 2 * 8 + 2 * 4 * 8
    assert costs_latent.mla_params(c) == mla
    dense = 32 * 8 + 3 * mla + 3 * 8 * 16 + 2 * (8 * 12 + 3 * 8 * 4)
    assert costs_latent.dense_params(c) == dense
    assert costs_latent.mla_core_flops(c, 10) == 10 * 3 * 2 * 2 * (8 + 6)
    peaks = {"bytes": 1e9, "flops": 1e9}
    # 48 B against 168 FLOPs a token: the FLOP roof
    assert costs_latent.mla_core_least(c, 100, peaks) == (16800 / 1e9,
                                                          "flops")
    assert costs_latent.decode_step_least(c, 5, 100, peaks) == \
        pytest.approx((2 * dense + 5 * 3 * 8 * 4 * 2) / 1e9 + 16800 / 1e9)
    # without a latent key: GQA's page arithmetic (a rehearsal's dense model)
    gqa = {"num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 16,
           "hidden_size": 64, "num_attention_heads": 4}
    assert not costs_latent.is_latent(gqa)
    assert costs_latent.paged_bytes_per_token(gqa) == \
        costs.kv_bytes_per_token(gqa) == 2 * 2 * 2 * 16 * 2
    # the hybrid's configuration is not this family
    assert not costs_latent.is_latent({"kv_lora_rank": 512,
                                       "layer_group_size": 6})


def _obs(samples, config=TINY, **over):
    obs = {"config": {"config": dict(config)},
           "peaks": {"bytes": 1e9, "flops": 4e9},
           "mix": {"engine": {"steps_per_dispatch": 2, "max_slots": 4}},
           "window": (0.0, 10.0), "trace": {"window_s": 4.0},
           "kv_tokens_at_end": 1000.0, "tokens_in_window": 100.0,
           "server_info": samples, "checks": {}}
    obs.update(over)
    return obs


def _trace():
    """Two whole ``jit_step`` programs of 2 fused steps; 120 ns under
    ``mla_core``, 90 under ``mla_proj``; a prefill's operations count
    nowhere."""
    step = "jit(step)/while/body/closed_call/"
    ops = [("fusion.1", step + "mla_proj/dot_general", 1000.0, 20.0),
           ("latent_paged_attention.5", step + "mla_core/jit(latent_paged_"
            "attention_pallas)/latent_paged_attention/pallas_call", 1300.0,
            80.0),
           ("scatter.6", step + "mla_core/scatter", 1400.0, 40.0),
           ("fusion.4", step + "mla_proj/mul", 3200.0, 70.0),
           ("fusion.8", "jit(prefill_extend)/mla_proj/dot", 9000.0, 70.0)]
    modules = [("jit_step(1)", 900.0, 1000.0), ("jit_step(1)", 3000.0, 1000.0),
               ("jit_prefill_extend(2)", 8900.0, 500.0)]
    return {"window": (0.0, 10000.0),
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


SAMPLES = [
    {"occupancy": 1.0},                                   # an older engine
    {"decode_steps_done": 80, "moe_routed": 1000, "moe_choices": 16000,
     "moe_experts_hit": 400, "moe_load_max": 300, "mla_rows_read": 100_000},
    {"decode_steps_done": 880, "moe_routed": 3400, "moe_choices": 54400,
     "moe_experts_hit": 4400, "moe_load_max": 2700,
     "mla_rows_read": 100_000 + 800 * 3 * 950},
]


def test_readers_on_a_decoded_trace_with_fabricated_counters(monkeypatch):
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    read = harness.load_reader
    obs = _obs(SAMPLES)
    c = obs["config"]["config"]
    assert read("mla_proj_ms")(obs) == pytest.approx(1e3 * 90e-9 / 4)
    assert read("mla_core_ms")(obs) == pytest.approx(1e3 * 120e-9 / 4)
    kv_mid = 1000.0 - 100.0 * (1.0 - 0.4 / 2.0)
    assert costs_latent.kv_tokens_mid(obs) == kv_mid
    # 48 B a token at 1 GB/s against 168 FLOPs at 4 GFLOP/s: bytes bind
    got = read("mla_core_roofline.latent")(obs)
    assert got == pytest.approx(100.0 * (kv_mid * 48 / 1e9) / 30e-9)
    assert obs["checks"]["mla_core_bound_by"] == "bytes"
    # the program counted 950 rows a step a layer; the client 950 at the
    # window's middle
    assert obs["checks"]["mla_rows"] == {
        "program_rows_a_step": 950.0, "client_tokens_mid_window": 950.0,
        "agree": True}
    hit = 4000 / 800
    step_s = 1000e-9 / 2
    assert read("decode_step_roofline.latent")(obs) == pytest.approx(
        100.0 * costs_latent.decode_step_least(c, hit, kv_mid, obs["peaks"])
        / step_s)
    assert read("experts_held_share")(obs) == pytest.approx(6.25)
    # a program whose count parts from the client's by more than 2%
    off = [dict(SAMPLES[1]), dict(SAMPLES[2],
                                  mla_rows_read=100_000 + 800 * 3 * 900)]
    obs = _obs(off)
    read("mla_core_roofline.latent")(obs)
    assert obs["checks"]["mla_rows"]["agree"] is False
    # where the FLOP roof is the slower, it is the one taken
    obs = _obs(SAMPLES, peaks={"bytes": 1e9, "flops": 1e9})
    assert read("mla_core_roofline.latent")(obs) == pytest.approx(
        100.0 * (kv_mid * 168 / 1e9) / 30e-9)
    assert obs["checks"]["mla_core_bound_by"] == "flops"


def test_readers_return_none_without_scopes_counters_or_latent_keys(
        monkeypatch):
    read = harness.load_reader
    # the parent's program under this PR's benchmark files, or the
    # rehearsal's dense model: a trace without the scopes, an engine
    # without the counters, a configuration without the latent keys
    monkeypatch.setattr(xspans, "load",
                        lambda path=None, _load=xspans.load: _load(SCOPED))
    dense = {"hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
             "num_key_value_heads": 2, "num_hidden_layers": 2,
             "vocab_size": 512, "intermediate_size": 128}
    plain = [{"decode_steps_done": 80}, {"decode_steps_done": 880}]
    ling = harness.load_config(
        os.path.join(HERE, "configs", "ling-3.0-flash.json"))["config"]
    for name in READERS:
        assert read(name)(_obs(plain, config=dense)) is None, name
        assert read(name)(_obs(plain)) is None, name
        assert read(name)(_obs(SAMPLES, config=ling)) is None, name
    # counters without the scopes (a trace of another program): the step's
    # share needs no scope of this family, the other two do
    assert read("decode_step_roofline.latent")(_obs(SAMPLES)) is not None
    assert read("mla_core_roofline.latent")(_obs(SAMPLES)) is None
    assert read("mla_proj_ms")(_obs(SAMPLES)) is None
    # Ling's cell reads mla_proj_ms from its own scope; the two latent
    # rooflines are not its (layer_group_size)
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    assert read("mla_proj_ms")(_obs(plain, config=ling)) is not None
    for name in READERS[:2]:
        assert read(name)(_obs(SAMPLES, config=ling)) is None, name
    # a rehearsal: no peaks, no reduced trace, no xplane at all
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    for name in READERS:
        assert read(name)(_obs(SAMPLES, peaks=None, trace=None)) is None, name


def _tiny_config(correct=None):
    from benchmark.lib import traffic
    from polyrl_tpu.models import cache_spec, decoder

    cfg = decoder.get_config("mla-moe-tiny")
    plan = cache_spec.layer_plan(cfg)
    first, held = cache_spec.experts_held(cfg)
    s = cfg.rope_scaling
    sizes = {
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
        "kept_layers": [p.published for p in plan],
        "first_k_dense_replace": sum(p.mlp == "dense" for p in plan),
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "rms_norm_eps": cfg.rms_norm_eps,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "type": "yarn", "factor": s.factor, "beta_fast": s.beta_fast,
            "beta_slow": s.beta_slow, "mscale": s.mscale,
            "mscale_all_dim": s.mscale_all_dim,
            "original_max_position_embeddings":
                s.original_max_position_embeddings},
        "experts_held": [first, held], "n_routed_experts": held,
        "num_experts": held, "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob,
        "tie_word_embeddings": cfg.tie_word_embeddings}
    mix = harness.rehearsal(_dots(), traffic.load_mix("rollout-long-latent"))[1]
    per_page = costs_latent.paged_bytes_per_token(sizes) \
        * mix["engine"]["page_size"]
    config = {"preset": "mla-moe-tiny", "reference": "mla_moe",
              "dtype": "float32", "config": sizes,
              "serve": {"kv_pool_bytes": 1700 * per_page},
              "correct": correct or {"logprob_mean_abs_diff_max": 1e-5,
                                     "logprob_max_abs_diff_max": 5e-5,
                                     "experts_rel_diff_max": 1e-4}}
    return cfg, config, mix


def test_the_latent_plane_walks_a_tiny_model_of_the_family_end_to_end():
    """``harness.rehearsal`` walks every cell with a dense model, so this
    is the walk of ``planes/rollout_latent.py`` on a model of its own
    family, here on the CPU in float32: the ``mla-moe-tiny`` preset
    through the manager with the cell's mix at its rehearsal sizes
    (chunks held first, the router's bias evened by the reference, the
    prefix cache on), the log-probabilities and the routed experts
    compared."""
    import jax

    _cfg, config, mix = _tiny_config()
    assert mix["plane"] == "rollout_latent" and mix["engine"]["prefill_first"]
    cell = {"name": "mla-moe-tiny.rehearsal", "chips": 1}
    plane = harness.load_named("planes", mix["plane"])
    assert jax.default_backend() == "cpu"
    out = plane.run(cell, config, mix, harness.Device(1, True), 3141592653,
                    3.0, False, harness.CompileCounter(), time.monotonic())
    ref = out["checks"]["reference"]
    assert ref["ok"], ref
    assert out["failed"] == 0 and out["checks"]["admitted"] == 4
    assert "router_evened" in out["checks"]["setup_phases_s"]
    assert "level" in out["checks"]["setup_phases_s"]
    assert ref["sequences"] == 2 and ref["experts_positions"] > 0
    assert out["checks"]["engine_recoveries"] == 0
    assert out["checks"]["kernels"] == {"latent_attention": ["ref"]}
    info = out["observed"]["server_info"][-1]
    assert info["mla_rows_read"] > 0 and info["kda_state_rows"] == 0
    assert harness.verdict(out, True)
    # the same walk held to a limit it cannot meet is not correct
    out["checks"]["reference"]["ok"] = False
    assert not harness.verdict(out, True)


def test_correct_comes_out_false_for_experts_on_int8s_grid():
    """The comparison's own control, on the CPU at the tiny size: the
    program's routed experts computed with weights on int8's grid against
    the reference with the unrounded ones. The log-probabilities handed in
    are the reference's own, so ``experts_rel_diff`` alone decides."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder

    cfg, config, _mix = _tiny_config()
    cfg = decoder.get_config("mla-moe-tiny", dtype=jnp.float32)
    plane = harness.load_named("planes", "rollout_latent")
    reference = harness.load_named("references", "mla_moe")
    params = decoder.init_params(jax.random.PRNGKey(1), cfg)
    c, limits = config["config"], config["correct"]
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 512, 40).tolist()
    toks = rng.integers(1, 512, 16).tolist()
    lps = reference.trace(params, c, prompt + toks, 40, 16)["logprobs"]
    samples = [(prompt, toks, lps.tolist())]
    walked = plane.walk(reference, cfg, params, c, samples)
    sound = plane.compare(reference, params, c, limits, samples, walked)
    assert sound["ok"] and sound["experts_rel_diff"] < 1e-5
    assert sound["logprob_max_abs_diff"] == 0.0

    def rounded(w):
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.round(w / jnp.maximum(scale, 1e-30)) * scale

    moe = dict(params["layers"]["moe"])
    for key in decoder.EXPERT_KEYS:
        moe[key] = rounded(moe[key])
    served = {**params, "layers": {**params["layers"], "moe": moe}}
    walked = plane.walk(reference, cfg, served, c, samples)
    got = plane.compare(reference, params, c, limits, samples, walked,
                        again=True)
    assert not got["ok"]
    assert got["experts_rel_diff"] > 10 * limits["experts_rel_diff_max"]
    assert got["logprob_mean_abs_diff"] <= limits["logprob_mean_abs_diff_max"]
    # and the reference's own int8 control reads the same size of error
    low = reference.routed_block(params, c, 0, walked[0]["moe_in"][0],
                                 control="int8_experts")
    ref = reference.routed_block(params, c, 0, walked[0]["moe_in"][0])
    some = np.linalg.norm(ref, axis=-1) > 0
    assert np.median(plane.hybrid.rel(low[some], ref[some], axis=-1)) > \
        10 * limits["experts_rel_diff_max"]
