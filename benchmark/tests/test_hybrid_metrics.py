"""``costs_hybrid.py`` against the numbers of ISSUE 33 and by hand, and the
seven readers of the hybrid cell: on a hand-made decoded trace with
fabricated counters, and None where a scope, a counter or a latent key is
absent (the parent's program, a dense model under a ``--rehearse-cpu``
walk, the scoped trace recorded on a TPU, which has none of the scopes).

Run by hand: ``python -m pytest benchmark/tests -q``."""

import os

import pytest

from benchmark.lib import costs, costs_hybrid, costs_moe, harness, xspans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = os.path.join(HERE, "tests", "data", "tiny_scoped_tpu.xplane.pb")
READERS = ("kda_core_ms", "kda_core_roofline", "kda_proj_ms", "mla_core_ms",
           "mla_core_roofline", "decode_step_roofline.hybrid",
           "experts_held_share")


def _ling():
    return harness.load_config(
        os.path.join(HERE, "configs", "ling-3.0-flash.json"))


def test_costs_of_the_published_sizes_are_the_issues_numbers():
    c = _ling()["config"]
    assert costs_hybrid.plan(c) == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("kda", "moe"),
        ("mla", "moe"), ("kda", "moe"), ("kda", "moe")]
    assert costs_hybrid.expert_params(c) == 3 * 2560 * 768 == 5898240
    assert costs_moe.expert_bytes(c) == 11796480                  # 11.8 MB
    assert costs_hybrid.router_params(c) == 2560 * 512
    # q, k, v, o, decay and gate at 2560 x 4096; beta; three kernel-4
    # convolutions; a_log, the decay's bias, the head norm
    assert costs_hybrid.kda_params(c) == (6 * 2560 * 4096 + 2560 * 32
                                          + 3 * 4 * 4096 + 32 + 4096 + 128)
    assert costs_hybrid.kda_params(c) == pytest.approx(63.1e6, rel=2e-3)
    # wq 2560 x 6144, wkv_a 2560 x 576, wkv_b 512 x 8192, wo 4096 x 2560,
    # the head gate 2560 x 32, the latent's norm
    assert costs_hybrid.mla_params(c) == (2560 * 6144 + 2560 * 576
                                          + 512 * 8192 + 4096 * 2560
                                          + 2560 * 32 + 512)
    assert costs_hybrid.mla_params(c) == pytest.approx(32.0e6, rel=2e-3)
    gb = lambda n: 2 * n / 1e9                                  # noqa: E731
    assert gb(costs_hybrid.layer_params(c, "kda", "moe")) == \
        pytest.approx(1.650, abs=1e-3)
    assert gb(costs_hybrid.layer_params(c, "mla", "moe")) == \
        pytest.approx(1.588, abs=1e-3)
    assert gb(costs_hybrid.layer_params(c, "kda", "dense")) == \
        pytest.approx(0.220, abs=1e-3)
    assert gb(costs_hybrid.vocab_params(c)) == pytest.approx(0.402, abs=1e-3)
    assert gb(costs_hybrid.weight_params(c)) == pytest.approx(10.46, abs=5e-3)
    assert costs_hybrid.paged_bytes_per_token(c) == 1152
    assert costs_hybrid.state_bytes(c) == 32 * 128 * 128 * 4 == 2097152
    assert costs_hybrid.kda_core_bytes(c, 1) == pytest.approx(4.2e6, rel=2e-3)
    assert costs_hybrid.slot_bytes(c) == pytest.approx(13.0e6, rel=3e-3)
    assert 128 * costs_hybrid.slot_bytes(c) == pytest.approx(1.67e9, rel=2e-3)
    # the cell's pool: 15,616 pages of 64 tokens, 1.15 GB, holds the 128
    # requests' 15,597 pages
    assert _ling()["serve"]["kv_pool_bytes"] == 15616 * 64 * 1152
    # ISSUE 33's step at 128 rows: 111 experts hit a sparse layer, 6 KDA
    # layers, 5k tokens of context a row: about 13 GB
    step = costs_hybrid.decode_step_bytes(c, 111 * 6, 128 * 6, 128 * 5000)
    assert step == pytest.approx(13.0e9, rel=5e-3)
    assert costs_moe.experts_bytes(c, 111 * 6) == pytest.approx(7.9e9, rel=1e-2)
    assert costs_hybrid.kda_core_bytes(c, 128 * 6) == pytest.approx(3.2e9,
                                                                    rel=1e-2)
    assert 2 * costs_hybrid.dense_params(c) == pytest.approx(1.2e9, rel=1e-2)


def test_the_programs_tree_has_the_counted_parameters():
    """``deployment`` in the configuration's file: recounted from the tree
    the program builds."""
    import jax

    from polyrl_tpu.models import decoder

    cfg = decoder.get_config(_ling()["preset"])
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree_util.tree_leaves(tree))
    assert n == costs_hybrid.weight_params(_ling()["config"])


TINY = {"hidden_size": 8, "num_attention_heads": 2, "head_dim": 4,
        "short_conv_kernel_size": 4, "kv_lora_rank": 6, "qk_nope_head_dim": 4,
        "qk_rope_head_dim": 2, "v_head_dim": 4, "intermediate_size": 16,
        "moe_intermediate_size": 4, "moe_shared_expert_intermediate_size": 4,
        "num_experts": 3, "published": {"num_experts": 12},
        "num_experts_per_tok": 2, "vocab_size": 32, "num_hidden_layers": 3,
        "layer_group_size": 3, "first_k_dense_replace": 1}


def test_costs_of_a_hand_counted_tiny_case():
    c = TINY
    assert costs_hybrid.plan(c) == [("kda", "dense"), ("kda", "moe"),
                                    ("mla", "moe")]
    assert costs_hybrid.paged_bytes_per_token(c) == 1 * (6 + 2) * 2
    assert costs_hybrid.state_bytes(c) == 2 * 4 * 4 * 4
    assert costs_hybrid.slot_bytes(c) == 2 * (128 + 3 * 3 * 8 * 2)
    kda = 6 * 8 * 8 + 8 * 2 + 3 * 4 * 8 + 2 + 8 + 4
    mla = 8 * 2 * 6 + 8 * 8 + 6 * 2 * 8 + 8 * 8 + 8 * 2 + 6
    assert (costs_hybrid.kda_params(c), costs_hybrid.mla_params(c)) == (kda, mla)
    dense = 32 * 8 + 2 * kda + mla + 3 * 8 * 16 + 2 * (8 * 12 + 3 * 8 * 4)
    assert costs_hybrid.dense_params(c) == dense
    assert costs_hybrid.decode_step_bytes(c, 5, 7, 100) == \
        2 * dense + 5 * 3 * 8 * 4 * 2 + 7 * 2 * 128 + 100 * 16
    # without a latent key: GQA's page arithmetic (a rehearsal's dense model)
    gqa = {"num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 16,
           "hidden_size": 64, "num_attention_heads": 4}
    assert costs_hybrid.paged_bytes_per_token(gqa) == \
        costs.kv_bytes_per_token(gqa) == 2 * 2 * 2 * 16 * 2


def _obs(samples, config=TINY, **over):
    obs = {"config": {"config": dict(config)}, "peaks": {"bytes": 1e9},
           "mix": {"engine": {"steps_per_dispatch": 2, "max_slots": 4}},
           "window": (0.0, 10.0), "trace": {"window_s": 4.0},
           "kv_tokens_at_end": 1000.0, "tokens_in_window": 100.0,
           "server_info": samples}
    obs.update(over)
    return obs


def _trace():
    """Two whole ``jit_step`` programs of 2 fused steps; 300 ns under
    ``kda_core``, 50 under ``kda_proj``, 120 under ``mla_core``; a
    prefill's operations and a container event count nowhere."""
    step = "jit(step)/while/body/closed_call/"
    ops = [("fusion.1", step + "kda_proj/dot_general", 1000.0, 20.0),
           ("fusion.2", step + "kda_core/reduce", 1030.0, 100.0),
           ("fusion.3", step + "kda_core/add", 1140.0, 50.0),
           ("fusion.4", step + "kda_proj/mul", 1200.0, 30.0),
           ("latent_paged_attention.5", step + "mla_core/jit(latent_paged_"
            "attention_pallas)/latent_paged_attention/pallas_call", 1300.0,
            80.0),
           ("scatter.6", step + "mla_core/scatter", 1400.0, 40.0),
           ("fusion.2", step + "kda_core/reduce", 3100.0, 150.0),
           ("while.7", step + "kda_core/while", 1000.0, 900.0),
           ("fusion.8", "jit(prefill_extend)/kda_core/scan", 9000.0, 70.0)]
    modules = [("jit_step(1)", 900.0, 1000.0), ("jit_step(1)", 3000.0, 1000.0),
               ("jit_prefill_extend(2)", 8900.0, 500.0)]
    return {"window": (0.0, 10000.0),
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


SAMPLES = [
    {"occupancy": 1.0},                                   # an older engine
    {"decode_steps_done": 80, "moe_routed": 1000, "moe_choices": 4000,
     "moe_experts_hit": 400, "moe_load_max": 300, "kda_state_rows": 600},
    {"decode_steps_done": 880, "moe_routed": 3400, "moe_choices": 13600,
     "moe_experts_hit": 4400, "moe_load_max": 2700, "kda_state_rows": 6200},
]


def test_readers_on_a_decoded_trace_with_fabricated_counters(monkeypatch):
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    read = harness.load_reader
    obs = _obs(SAMPLES)
    c = obs["config"]["config"]
    assert read("kda_core_ms")(obs) == pytest.approx(1e3 * 300e-9 / 4)
    assert read("kda_proj_ms")(obs) == pytest.approx(1e3 * 50e-9 / 4)
    assert read("mla_core_ms")(obs) == pytest.approx(1e3 * 120e-9 / 4)
    rows = 5600 / 800                      # live rows x KDA layers, a step
    assert costs_hybrid.state_rows_per_step(obs) == rows
    assert read("kda_core_roofline")(obs) == pytest.approx(
        100.0 * (rows * 2 * 128 / 1e9) / 75e-9)
    kv_mid = 1000.0 - 100.0 * (1.0 - 0.4 / 2.0)
    assert costs_hybrid.kv_tokens_mid(obs) == kv_mid
    assert read("mla_core_roofline")(obs) == pytest.approx(
        100.0 * (kv_mid * 16 / 1e9) / 30e-9)
    hit = 4000 / 800
    step_s = 1000e-9 / 2
    assert read("decode_step_roofline.hybrid")(obs) == pytest.approx(
        100.0 * costs_hybrid.decode_step_bytes(c, hit, rows, kv_mid) / 1e9
        / step_s)
    assert read("experts_held_share")(obs) == pytest.approx(25.0)


def test_a_count_of_more_rows_than_the_engine_has_is_a_fault():
    over = [dict(SAMPLES[1]),
            dict(SAMPLES[2], kda_state_rows=600 + 11 * 800)]
    with pytest.raises(ValueError, match="engine has"):
        costs_hybrid.state_rows_per_step(_obs(over))
    full = [dict(SAMPLES[1]),
            dict(SAMPLES[2], kda_state_rows=600 + 10 * 800)]
    assert costs_hybrid.state_rows_per_step(_obs(full)) == 10   # 5 rows x 2


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
    for name in READERS:
        assert read(name)(_obs(plain, config=dense)) is None, name
        assert read(name)(_obs(plain)) is None, name
    # counters without the scopes (a trace of another program)
    assert read("experts_held_share")(_obs(SAMPLES)) == pytest.approx(25.0)
    assert read("kda_core_roofline")(_obs(SAMPLES)) is None
    assert read("mla_core_roofline")(_obs(SAMPLES)) is None
    # a rehearsal: no peaks, no reduced trace, no xplane at all
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    for name in READERS[:6]:
        assert read(name)(_obs(SAMPLES, peaks=None, trace=None)) is None, name


def test_the_hybrid_plane_walks_a_tiny_hybrid_end_to_end():
    """``harness.rehearsal`` walks every cell with a dense model, so this
    is the walk of ``planes/rollout_hybrid.py`` on a model of its own
    family, here on the CPU in float32: the ``hybrid-tiny`` preset through
    the manager with the cell's mix at its rehearsal sizes (chunks held
    first, the router's bias evened by the reference), the held state of
    each scored request and the routed experts compared part by part."""
    import time

    import jax

    from benchmark.lib import traffic
    from polyrl_tpu.models import cache_spec, decoder

    cfg = decoder.get_config("hybrid-tiny")
    plan = cache_spec.layer_plan(cfg)
    first, held = cache_spec.experts_held(cfg)
    sizes = {
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
        "kept_layers": [p.published for p in plan],
        "layer_group_size": cfg.layer_group_size,
        "first_k_dense_replace": sum(p.mlp == "dense" for p in plan),
        "num_attention_heads": cfg.num_heads, "head_dim": cfg.head_dim_,
        "kda_lower_bound": cfg.kda_lower_bound,
        "rms_norm_eps": cfg.rms_norm_eps,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "rope_theta": cfg.rope_theta, "experts_held": [first, held],
        "num_experts": held, "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob,
        "tie_word_embeddings": cfg.tie_word_embeddings}
    mix = harness.rehearsal(_ling(), traffic.load_mix("rollout-long-wide"))[1]
    per_page = costs_hybrid.paged_bytes_per_token(sizes) \
        * mix["engine"]["page_size"]
    config = {"preset": "hybrid-tiny", "reference": "hybrid_kda_mla_moe",
              "dtype": "float32", "config": sizes,
              "serve": {"kv_pool_bytes": 1700 * per_page},
              "correct": {"logprob_mean_abs_diff_max": 1e-5,
                          "logprob_max_abs_diff_max": 5e-5,
                          "state_rel_diff_max": 1e-4,
                          "experts_rel_diff_max": 1e-4}}
    cell = {"name": "hybrid-tiny.rehearsal", "chips": 1}
    plane = harness.load_named("planes", mix["plane"])
    assert jax.default_backend() == "cpu"
    out = plane.run(cell, config, mix, harness.Device(1, True), 3141592653,
                    3.0, False, harness.CompileCounter(), time.monotonic())
    ref = out["checks"]["reference"]
    assert ref["ok"], ref
    assert out["failed"] == 0 and out["checks"]["admitted"] == 4
    assert "router_evened" in out["checks"]["setup_phases_s"]
    assert ref["sequences"] == 2 and ref["experts_positions"] > 0
    assert all(len(s) == 2 for s in ref["state_rel_diffs"])
    assert out["checks"]["engine_recoveries"] == 0
