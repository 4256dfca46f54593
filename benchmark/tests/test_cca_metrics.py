"""``costs_cca.py`` against the numbers of ISSUE 41, by hand and against
the parameter tree the program builds; the preset against the
configuration's file, key for key; the three readers this cell brings
(``cca_mix_ms``, ``cca_proj_ms``, ``decode_step_roofline.cca``) on a
hand-made decoded trace with fabricated counters, and None where a scope,
a counter or a CCA key is absent (the parent's program, a dense model
under a ``--rehearse-cpu`` walk); the plane walked end to end on a tiny
model of the family; and one walk in which ``correct`` has to come out
false.

Run by hand: ``python -m pytest benchmark/tests -q``."""

import os
import time

import numpy as np
import pytest

from benchmark.lib import costs, costs_cca, costs_moe, harness, xspans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = os.path.join(HERE, "tests", "data", "tiny_scoped_tpu.xplane.pb")
READERS = ("cca_mix_ms", "cca_proj_ms", "decode_step_roofline.cca")


def _zaya():
    return harness.load_config(os.path.join(HERE, "configs", "zaya1-8b.json"))


def test_costs_of_the_published_sizes_are_the_issues_numbers():
    c = _zaya()["config"]
    assert costs_cca.is_cca(c)
    # a CCA mixer: 5.24M of projections, 0.33M of convolutions
    assert 2048 * 1536 + 1024 * 2048 == 5_242_880
    assert costs_cca.cca_params(c) == 5_242_880 + 330_240 + 2
    assert round(costs_cca.router_params(c) / 1e6, 2) == 0.66
    assert costs_cca.expert_params(c) == 12_582_912
    assert round(costs_cca.layer_params(c) / 1e6, 1) == 207.6
    assert round(costs_cca.vocab_params(c) / 1e6, 1) == 537.1
    # 12 layers and the tied head: 6.06 GB in bf16
    assert round(costs_cca.weight_params(c) * 2 / 1e9, 2) == 6.06
    # 12 KiB of K/V a token, as the accepted readers count it
    assert costs_cca.paged_bytes_per_token(c) == 12 * 1024 == \
        costs.kv_bytes_per_token(c)
    assert costs_moe.expert_bytes(c) == 25_165_824
    assert costs_cca.slot_bytes(c) == 12 * 5376
    # the pool: 8,704 pages of 64 tokens
    serve = _zaya()["serve"]
    assert serve["kv_pool_bytes"] == 8704 * 64 * 12 * 1024
    # a decode step at the window's middle: 10.8 GB, 13.1 ms at 819 GB/s
    least = costs_cca.decode_step_bytes(c, 16 * 12, 128 * 12, 383_000)
    assert round(least / 1e9, 1) == 10.8
    assert round(1e3 * least / 819e9, 1) == 13.2


def test_the_programs_tree_has_the_counted_parameters():
    """``deployment`` in the configuration's file: recounted from the tree
    the program builds, and what a token and a slot keep from the
    program's own cache specification."""
    import jax

    from polyrl_tpu.models import cache_spec, decoder

    cfg = decoder.get_config(_zaya()["preset"])
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    c = _zaya()["config"]

    def count(t):
        return sum(a.size for a in jax.tree_util.tree_leaves(t))

    assert count(tree) == costs_cca.weight_params(c)
    assert count(tree["layers"]["cca"]) == 12 * costs_cca.cca_params(c)
    moe = tree["layers"]["moe"]
    assert count({k: v for k, v in moe.items() if k.startswith("router")}) \
        == 12 * costs_cca.router_params(c)
    assert count(tree["embed"]) == costs_cca.vocab_params(c)
    assert "lm_head" not in tree
    assert cache_spec.paged_bytes_per_token(cfg) == \
        costs_cca.paged_bytes_per_token(c)
    assert cache_spec.slot_bytes(cfg) == costs_cca.slot_bytes(c)


def test_the_preset_equals_the_configurations_file():
    """``harness.MODEL_FIELDS`` carries only the dense GQA keys, so the
    family's keys reach the program through the preset: held equal here."""
    from polyrl_tpu.models import cache_spec, decoder

    raw = _zaya()
    c = raw["config"]
    cfg = decoder.get_config(raw["preset"], **harness.model_overrides(raw))
    assert cfg == decoder.get_config(raw["preset"])     # nothing overridden
    rope = c["rope_parameters"]["hybrid"]
    assert (cfg.cca_time0, cfg.cca_time1) == (c["cca_time0"], c["cca_time1"])
    assert cfg.partial_rotary_factor == c["partial_rotary_factor"] == \
        rope["partial_rotary_factor"]
    assert cfg.rope_theta == rope["rope_theta"]
    assert cfg.router_hidden_size == c["router_hidden_size"]
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size) == (
        c["num_experts"], c["num_experts_per_tok"],
        c["moe_intermediate_size"])
    assert cfg.tie_word_embeddings and not c["lm_head_bias"]
    assert c["sliding_window"] is None and set(c["layer_types"]) == {"hybrid"}
    assert [p.published for p in cache_spec.layer_plan(cfg)] == \
        c["kept_layers"] == list(range(12))
    assert c["published"] == {"num_hidden_layers": 40} == {
        "num_hidden_layers": decoder.get_config("zaya1-8b").num_layers}
    assert raw["reduced"] == ["num_hidden_layers"]


TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 2,
        "head_dim": 4, "cca_time0": 2, "cca_time1": 3,
        "router_hidden_size": 3, "num_experts": 4, "num_experts_per_tok": 1,
        "moe_intermediate_size": 5, "vocab_size": 32, "num_hidden_layers": 2,
        "tie_word_embeddings": True}


def test_costs_of_a_hand_counted_tiny_case():
    c = TINY
    # W_in 8 x (16 + 8), conv0 2 x 16, conv1 3 x 4 x 4 x 4, tau 2, Wo 8 x 8
    assert costs_cca.cca_params(c) == 192 + 32 + 192 + 2 + 64
    # Wd 24, norm 3, gamma 1, W1 W2 9 each, W3 12, bias 4
    assert costs_cca.router_params(c) == 24 + 3 + 1 + 18 + 12 + 4
    assert costs_cca.layer_params(c) == 482 + 62 + 4 * 120 + 80
    assert costs_cca.weight_params(c) == 2 * 1104 + 256 + 8
    assert costs_cca.paged_bytes_per_token(c) == 2 * 2 * 2 * 4 * 2
    # (1 + 2) rows of 16 channels and a value half of 4, two layers
    assert costs_cca.slot_bytes(c) == 2 * (48 + 4) * 2
    assert costs_cca.dense_params(c) == 2 * (482 + 62) + 256
    got = costs_cca.decode_step_bytes(c, experts_hit=6, rows_x_layers=10,
                                      kv_tokens_read=100)
    assert got == (2 * 1344 + 6 * 240 + 100 * 64 + 2 * 10 * 104)


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
    """Two whole ``jit_step`` programs of 2 fused steps; 110 ns under
    ``cca_mix``, 90 under ``cca_proj``, 80 under ``attn_core``; a
    prefill's operations count nowhere."""
    step = "jit(step)/while/body/closed_call/"
    ops = [("fusion.1", step + "cca_proj/dot_general", 1000.0, 20.0),
           ("fusion.2", step + "cca_mix/mul", 1100.0, 60.0),
           ("paged_attention.5", step + "attn_core/jit(paged_attention_"
            "pallas)/paged_attention/pallas_call", 1300.0, 80.0),
           ("fusion.3", step + "cca_mix/select_n", 3100.0, 50.0),
           ("fusion.4", step + "cca_proj/dot_general", 3200.0, 70.0),
           ("fusion.8", "jit(prefill_extend)/cca_mix/mul", 9000.0, 70.0)]
    modules = [("jit_step(1)", 900.0, 1000.0), ("jit_step(1)", 3000.0, 1000.0),
               ("jit_prefill_extend(2)", 8900.0, 500.0)]
    return {"window": (0.0, 10000.0),
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


SAMPLES = [
    {"occupancy": 1.0},                                   # an older engine
    {"decode_steps_done": 80, "moe_routed": 320, "moe_choices": 320,
     "moe_experts_hit": 400, "moe_load_max": 300, "cca_tail_rows": 640},
    {"decode_steps_done": 880, "moe_routed": 3520, "moe_choices": 3520,
     "moe_experts_hit": 4400, "moe_load_max": 2700,
     "cca_tail_rows": 640 + 800 * 8},
]


def test_readers_on_a_decoded_trace_with_fabricated_counters(monkeypatch):
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    read = harness.load_reader
    obs = _obs(SAMPLES)
    c = obs["config"]["config"]
    assert read("cca_mix_ms")(obs) == pytest.approx(1e3 * 110e-9 / 4)
    assert read("cca_proj_ms")(obs) == pytest.approx(1e3 * 90e-9 / 4)
    assert read("attn_core_ms")(obs) == pytest.approx(1e3 * 80e-9 / 4)
    assert costs_cca.tail_rows_per_step(obs) == 8.0
    kv_mid = 1000.0 - 100.0 * (1.0 - 0.4 / 2.0)
    hit = 4000 / 800
    step_s = 1000e-9 / 2
    assert read("decode_step_roofline.cca")(obs) == pytest.approx(
        100.0 * costs_cca.decode_step_bytes(c, hit, 8.0, kv_mid) / 1e9
        / step_s)
    # a program that counts more rows than the engine has slots
    off = [dict(SAMPLES[1]), dict(SAMPLES[2], cca_tail_rows=640 + 800 * 11)]
    with pytest.raises(ValueError, match="cca_tail_rows"):
        read("decode_step_roofline.cca")(_obs(off))


def test_readers_return_none_without_scopes_counters_or_cca_keys(
        monkeypatch):
    read = harness.load_reader
    # the parent's program under this PR's benchmark files, or the
    # rehearsal's dense model: a trace without the scopes, an engine
    # without the counters, a configuration without the CCA keys
    monkeypatch.setattr(xspans, "load",
                        lambda path=None, _load=xspans.load: _load(SCOPED))
    dense = {"hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
             "num_key_value_heads": 2, "num_hidden_layers": 2,
             "vocab_size": 512, "intermediate_size": 128}
    plain = [{"decode_steps_done": 80}, {"decode_steps_done": 880}]
    for name in READERS:
        assert read(name)(_obs(plain, config=dense)) is None, name
        assert read(name)(_obs(plain)) is None, name
        assert read(name)(_obs(SAMPLES, config=dense)) is None, name
    # counters without the scopes (a trace of another program): the step's
    # share needs no scope of this family, the other two do
    assert read("decode_step_roofline.cca")(_obs(SAMPLES)) is not None
    assert read("cca_mix_ms")(_obs(SAMPLES)) is None
    assert read("cca_proj_ms")(_obs(SAMPLES)) is None
    # a rehearsal: no peaks, no reduced trace, no xplane at all
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    for name in READERS:
        assert read(name)(_obs(SAMPLES, peaks=None, trace=None)) is None, name


def _tiny_config(correct=None):
    from benchmark.lib import traffic
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("cca-tiny")
    sizes = {
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
        "kept_layers": list(range(cfg.num_layers)),
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "rms_norm_eps": cfg.rms_norm_eps,
        "cca_time0": cfg.cca_time0, "cca_time1": cfg.cca_time1,
        "partial_rotary_factor": cfg.partial_rotary_factor,
        "rope_parameters": {"hybrid": {
            "partial_rotary_factor": cfg.partial_rotary_factor,
            "rope_theta": cfg.rope_theta, "rope_type": "default"}},
        "router_hidden_size": cfg.router_hidden_size,
        "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "tie_word_embeddings": cfg.tie_word_embeddings}
    mix = harness.rehearsal(_zaya(), traffic.load_mix("rollout-wide-cca"))[1]
    per_page = costs_cca.paged_bytes_per_token(sizes) \
        * mix["engine"]["page_size"]
    config = {"preset": "cca-tiny", "reference": "cca_moe",
              "dtype": "float32", "config": sizes,
              "serve": {"kv_pool_bytes": 1700 * per_page},
              "correct": correct or {"logprob_mean_abs_diff_max": 1e-5,
                                     "logprob_max_abs_diff_max": 5e-5,
                                     "experts_rel_diff_max": 1e-4,
                                     "tails_rel_diff_max": 1e-5}}
    return cfg, config, mix


def test_the_cca_plane_walks_a_tiny_model_of_the_family_end_to_end():
    """``harness.rehearsal`` walks every cell with a dense model, so this
    is the walk of ``planes/rollout_cca.py`` on a model of its own family,
    here on the CPU in float32: the ``cca-tiny`` preset through the manager
    with the cell's mix at its rehearsal sizes (chunks held first, the
    router's bias evened by the reference, no prefix cache), the
    log-probabilities, the routed experts with their choice and the slot's
    tails compared."""
    import jax

    _cfg, config, mix = _tiny_config()
    assert mix["plane"] == "rollout_cca" and mix["engine"]["prefill_first"]
    cell = {"name": "cca-tiny.rehearsal", "chips": 1}
    plane = harness.load_named("planes", mix["plane"])
    assert jax.default_backend() == "cpu"
    out = plane.run(cell, config, mix, harness.Device(1, True), 3141592653,
                    3.0, False, harness.CompileCounter(), time.monotonic())
    ref = out["checks"]["reference"]
    assert ref["ok"], ref
    assert out["failed"] == 0 and out["checks"]["admitted"] == 4
    assert "router_evened" in out["checks"]["setup_phases_s"]
    assert "level" in out["checks"]["setup_phases_s"]
    assert ref["sequences"] == 2 and ref["experts_positions"] == 2 * 16 * 3
    assert ref["choice_differs_share"] == 0.0
    assert all(n > 40 + 16 for n in ref["tails_tokens"])
    assert len(ref["tails_rel_diffs"][0]) == 3
    assert out["checks"]["engine_recoveries"] == 0
    assert out["checks"]["kernels"] == {"kv_write": ["scatter"],
                                        "paged_attention": ["ref"]}
    info = out["observed"]["server_info"][-1]
    assert info["cca_tail_rows"] > 0 and info["kda_state_rows"] == 0
    assert info["moe_routed"] == info["moe_choices"] > 0
    assert harness.verdict(out, True)
    # the same walk held to a limit it cannot meet is not correct
    out["checks"]["reference"]["ok"] = False
    assert not harness.verdict(out, True)


@pytest.mark.parametrize("fault", ["int8_experts", "stale_tails",
                                   "stale_tails_past_the_first_layer"])
def test_correct_comes_out_false(fault):
    """The comparison's own controls, on the CPU at the tiny size. The
    log-probabilities handed in are the reference's own, so the number
    that watches the fault alone decides: ``int8_experts``: the program's
    routed experts computed with weights on int8's grid against the
    reference with the unrounded ones; ``stale_tails``: the slot's tails
    of the token BEFORE the last (what a chunk boundary off by one, or a
    re-entry that kept the old row, leaves), in every layer or in every
    layer but the first (what a wrong slot index in ``pool_index`` past
    layer 0 leaves)."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder

    _cfg, config, _mix = _tiny_config()
    cfg = decoder.get_config("cca-tiny", dtype=jnp.float32)
    plane = harness.load_named("planes", "rollout_cca")
    reference = harness.load_named("references", "cca_moe")
    params = decoder.init_params(jax.random.PRNGKey(1), cfg)
    c, limits = config["config"], config["correct"]
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 512, 40).tolist()
    toks = rng.integers(1, 512, 24).tolist()
    got = reference.trace(params, c, prompt + toks, 40, 16)
    samples = [(prompt, toks[:16], got["logprobs"].tolist())]
    held = [{"answer": toks, "states": got["states"]}]
    walked = plane.walk(reference, cfg, params, c, samples, held)
    sound = plane.compare(reference, params, c, limits, samples, held,
                          walked)
    assert sound["ok"] and sound["experts_rel_diff"] < 1e-5
    assert sound["logprob_max_abs_diff"] == 0.0
    assert sound["tails_rel_diff"] == 0.0
    assert sound["choice_differs_share"] == 0.0
    assert sound["failed_by"] == []
    if fault.startswith("stale_tails"):
        before = reference.trace(params, c, prompt + toks[:-1], 40, 16)
        keep = 0 if fault == "stale_tails" else 1
        stale = [{"answer": toks, "states": got["states"][:keep]
                  + before["states"][keep:]}]
        bad = plane.compare(reference, params, c, limits, samples, stale,
                            walked)
        assert not bad["ok"] and bad["tails_rel_diff"] > 0.1
        assert bad["failed_by"] == ["tails_rel_diff"]
        assert bad["experts_rel_diff"] == sound["experts_rel_diff"]
        return

    def rounded(w):
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return jnp.round(w / jnp.maximum(scale, 1e-30)) * scale

    moe = dict(params["layers"]["moe"])
    for key in decoder.EXPERT_KEYS:
        moe[key] = rounded(moe[key])
    served = {**params, "layers": {**params["layers"], "moe": moe}}
    walked = plane.walk(reference, cfg, served, c, samples, held)
    bad = plane.compare(reference, params, c, limits, samples, held, walked,
                        again=True)
    assert not bad["ok"] and bad["experts_rel_diff"] > 1e-3
    assert bad["failed_by"] == ["experts_rel_diff"]
    assert bad["tails_rel_diff"] == 0.0
