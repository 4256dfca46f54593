"""``costs_nemotron_h.py`` against the numbers of ISSUE 58, by hand and
against the parameter tree and the pools the program builds; the readers
this cell brings on a hand-made decoded trace with fabricated counters, and
None where a scope, a counter or a family key is absent (the parent's
program, a dense model under a ``--rehearse-cpu`` walk); the plane walked
end to end on a tiny model of the family, sound and with each control of
``correct`` in the program's place (each has to come out false by the limit
that watches it); every number held to a limit in the result line's
``compared``; and the cell's own ``--rehearse-cpu`` walk.

Run by hand: ``python -m pytest benchmark/tests -q``."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.lib import costs_nemotron_h as costs
from benchmark.lib import harness, xspans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = os.path.join(HERE, "tests", "data", "tiny_scoped_tpu.xplane.pb")
READERS = ("ssd_core_ms", "ssd_proj_ms", "ssd_core_roofline",
           "ssd_kernel_share", "moe_experts_roofline.ssd",
           "attn_core_roofline.ssd", "decode_step_roofline.ssd")
CELL = "nemotron-3-nano-30b-a3b.rollout-wide-ssd"
sys.path.insert(0, os.path.join(HERE, "tests"))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tests"))


def _file():
    return harness.load_config(os.path.join(
        HERE, "configs", "nemotron-3-nano-30b-a3b.json"))


def test_costs_of_the_published_sizes_are_the_issues_numbers():
    c = _file()["config"]
    assert (costs.count(c, "mamba2"), costs.count(c, "gqa"),
            costs.count(c, "moe")) == (23, 6, 23)
    # a sublayer with its one norm, as ISSUE 58 counts them
    assert costs.mamba_params(c) + 2688 == 38_744_896
    assert costs.attn_params(c) + 2688 == 23_399_040
    assert costs.expert_params(c) == 9_977_856
    whole = {**c, **c["published"]}
    assert costs.moe_params(whole) + 2688 == 1_297_468_160
    assert costs.published_params(c) == 31_577_940_288
    assert costs.weight_params(c) == 5_258_420_544       # 10.52 GB
    assert costs.paged_bytes_per_token(c) == 6 * 1024
    assert costs.state_bytes_a_layer(c) == 2 * 2**20
    assert costs.tail_bytes_a_layer(c) == 36_864
    assert costs.slot_bytes(c) == 49_082_368
    d = costs.deployment(c, 64, _file()["serve"]["kv_pool_bytes"])
    assert d["pages"] == 3601 and d["paged_bytes_per_token"] == 6144
    assert d["state_bytes"] == 65 * 49_082_368            # 3.19 GB
    # a step's least bytes at 64 rows, 95% of the held experts hit and
    # contexts of 2,400: ISSUE 58's shares
    hit, keys, rows = 0.95 * 16 * 23, 6 * 64 * 2400.0, 23 * 64.0
    step = costs.decode_step_bytes(c, hit, keys, rows)
    assert 16.9e9 < step < 17.5e9
    assert 0.35 < costs.ssd_core_bytes(c, rows) / step < 0.37
    assert 0.39 < costs.experts_bytes(c, hit) / step < 0.42
    assert 0.05 < costs.attn_core_bytes(c, keys) / step < 0.07


def test_the_programs_tree_and_pools_have_the_counted_sizes():
    import jax

    from polyrl_tpu.models import cache_spec, decoder

    config = _file()
    c = config["config"]
    cfg = decoder.get_config(config["preset"],
                             **harness.model_overrides(config))
    assert cfg == decoder.get_config(config["preset"])
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) \
        == costs.weight_params(c)
    stack = tree["layers"]["mamba2"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(stack)) \
        == 23 * costs.mamba_params(c)
    assert stack["w_in"].size + stack["w_out"].size \
        == 23 * costs.mamba_matmul_params(c)
    moe = tree["layers"]["moe"]
    assert moe["we_up"].size + moe["we_down"].size \
        == 23 * 16 * costs.expert_params(c)
    assert moe["router"].shape == (23, 2688, 128)
    assert cache_spec.paged_bytes_per_token(cfg) \
        == costs.paged_bytes_per_token(c)
    assert cache_spec.slot_bytes(cfg) == costs.slot_bytes(c)
    whole = decoder.get_config("nemotron-3-nano-30b-a3b")
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), whole))
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) \
        == costs.published_params(c)


TINY = {"hybrid_override_pattern": "ME*EM", "num_hidden_layers": 5,
        "hidden_size": 8, "mamba_num_heads": 2, "mamba_head_dim": 4,
        "n_groups": 1, "ssm_state_size": 4, "conv_kernel": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
        "moe_intermediate_size": 6, "moe_shared_expert_intermediate_size": 12,
        "n_routed_experts": 2, "published": {"n_routed_experts": 4},
        "vocab_size": 32}


def test_costs_of_a_hand_counted_tiny_case():
    c = TINY
    # in: 8 x (8 + 16 + 2), out 8 x 8; taps and bias 5 x 16; 3 x 2; norm 8
    assert costs.mamba_matmul_params(c) == 8 * 26 + 64
    assert costs.mamba_params(c) == 8 * 26 + 64 + 80 + 6 + 8
    assert costs.attn_params(c) == 8 * 8 * 4 + 16 * 8
    assert costs.moe_params(c) == 2 * 96 + 192 + 9 * 4
    assert costs.paged_bytes_per_token(c) == 2 * 2 * 4 * 2
    assert costs.slot_bytes(c) == 2 * (8 * 4 * 4 + 3 * 16 * 2)
    assert costs.ssd_core_bytes(c, 3.0) == 3 * 2 * 128
    assert costs.experts_bytes(c, 5.0) == 5 * 96 * 2
    assert costs.attn_core_bytes(c, 7.0) == 7 * 32
    assert costs.decode_step_bytes(c, 5.0, 7.0, 3.0) == (
        2 * (2 * (8 * 26 + 64) + 384 + 2 * (192 + 32) + 32 * 8)
        + 960 + 224 + 768 + 3 * 2 * 96)


def _obs(samples, config=TINY, **over):
    obs = {"config": {"config": dict(config)},
           "peaks": {"bytes": 1e9, "flops": 4e12},
           "mix": {"engine": {"steps_per_dispatch": 2, "max_slots": 4}},
           "window": (0.0, 10.0), "trace": {"window_s": 4.0},
           "kv_tokens_at_end": 1000.0, "tokens_in_window": 100.0,
           "server_info": samples, "checks": {}}
    obs.update(over)
    return obs


def _trace():
    """Two whole ``jit_step`` programs of 2 fused steps; nanoseconds under
    each scope; a prefill's operations count nowhere."""
    step = "jit(step)/while/body/closed_call/"
    kernel = "/jit(paged_attention_pallas)/paged_attention/pallas_call"
    ops = [("fusion.1", step + "ssd_proj/dot_general", 1010.0, 20.0),
           ("ssd_state.2", step + "ssd_core/pallas_call", 1040.0, 40.0),
           ("paged_attention.6", step + "attn_core" + kernel, 1300.0, 80.0),
           ("fusion.3", step + "ssd_proj/reduce", 1400.0, 30.0),
           ("grouped_matmul.4", step + "mlp/moe_experts/pallas_call", 1450.0,
            60.0),
           ("fusion.5", step + "head/dot_general", 1800.0, 50.0),
           ("ssd_state.2", step + "ssd_core/pallas_call", 3200.0, 24.0),
           ("fusion.8", "jit(prefill_batch)/ssd_core/dot", 9000.0, 70.0)]
    modules = [("jit_step(1)", 900.0, 1000.0), ("jit_step(1)", 3000.0, 1000.0),
               ("jit_prefill_batch(2)", 8900.0, 500.0)]
    return {"window": (0.0, 10000.0),
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


SAMPLES = [
    {"occupancy": 1.0},                                   # an older engine
    {"decode_steps_done": 80, "ssd_state_rows": 640, "ssd_kernel_steps": 80,
     "moe_experts_hit": 100, "paged_rows_read": 1000},
    {"decode_steps_done": 880, "ssd_state_rows": 640 + 800 * 8,
     "ssd_kernel_steps": 880, "moe_experts_hit": 100 + 800 * 3,
     "paged_rows_read": 1000 + 800 * 950},
]


def test_readers_on_a_decoded_trace_with_fabricated_counters(monkeypatch):
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    read = harness.load_reader
    obs = _obs(SAMPLES)
    c = obs["config"]["config"]
    per = 1e3 * 1e-9 / 4
    assert read("ssd_core_ms")(obs) == pytest.approx((40 + 24) * per)
    assert read("ssd_proj_ms")(obs) == pytest.approx((20 + 30) * per)
    assert read("moe_experts_ms")(obs) == pytest.approx(60 * per)
    assert read("ssd_kernel_share")(obs) == 100.0
    # one attention layer; the keys are the client's at the TRACED part's
    # middle (0.4 of the window), not the window's mean of the counter
    rows, hit = 8.0, 3.0
    keys = 1000.0 - 100.0 * (1.0 - 0.4 / 2.0)
    assert read("ssd_core_roofline")(obs) == pytest.approx(
        100.0 * costs.ssd_core_bytes(c, rows) / 1e9 / (64e-9 / 4))
    assert read("moe_experts_roofline.ssd")(obs) == pytest.approx(
        100.0 * costs.experts_bytes(c, hit) / 1e9 / (60e-9 / 4))
    assert read("attn_core_roofline.ssd")(obs) == pytest.approx(
        100.0 * costs.attn_core_bytes(c, keys) / 1e9 / (80e-9 / 4))
    # the engine's count of keys against the client's at the window's
    # middle: 950 a step and layer, 950 tokens
    assert obs["checks"]["paged_rows"] == {
        "program_rows_a_step": 950.0, "client_tokens_mid_window": 950.0,
        "agree": True}
    assert read("decode_step_roofline.ssd")(obs) == pytest.approx(
        100.0 * costs.decode_step_bytes(c, hit, keys, rows) / 1e9
        / (1000e-9 / 2))
    off = _obs([dict(SAMPLES[1]),
                dict(SAMPLES[2], paged_rows_read=1000 + 800 * 1100)])
    read("attn_core_roofline.ssd")(off)
    assert off["checks"]["paged_rows"]["agree"] is False
    # more experts hit than the chip holds: the counter is wrong
    many = [dict(SAMPLES[1]), dict(SAMPLES[2], moe_experts_hit=100 + 800 * 5)]
    with pytest.raises(ValueError, match="holds fewer"):
        read("moe_experts_roofline.ssd")(_obs(many))


def test_readers_return_none_without_scopes_counters_or_family_keys(
        monkeypatch):
    read = harness.load_reader
    # the parent's program under this PR's benchmark files, or the
    # rehearsal's dense model: a trace without the scopes, an engine
    # without the counters, a configuration without the family's keys
    monkeypatch.setattr(xspans, "load",
                        lambda path=None, _load=xspans.load: _load(SCOPED))
    dense = {"hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
             "num_key_value_heads": 2, "num_hidden_layers": 2,
             "vocab_size": 512, "intermediate_size": 128}
    plain = [{"decode_steps_done": 80, "row_steps_done": 320},
             {"decode_steps_done": 880, "row_steps_done": 3520}]
    for name in READERS:
        assert read(name)(_obs(plain, config=dense)) is None, name
        if name == "attn_core_roofline.ssd":
            # the client's contexts and the trace suffice: no counter
            assert read(name)(_obs(plain)) > 0.0
        else:
            assert read(name)(_obs(plain)) is None, name
        if name in ("ssd_core_ms", "ssd_proj_ms", "ssd_core_roofline"):
            # the recorded trace has none of this family's own scopes
            assert read(name)(_obs(SAMPLES)) is None, name
        if name != "ssd_kernel_share":
            assert read(name)(_obs(SAMPLES, config=dense)) is None, name
    # a rehearsal: no peaks, no reduced trace, no xplane at all
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    for name in READERS:
        if name != "ssd_kernel_share":
            assert read(name)(_obs(SAMPLES, peaks=None, trace=None)) is None


def _tiny_config(correct=None):
    from benchmark.lib import traffic
    from polyrl_tpu.models import decoder

    from test_nemotron_h import file_keys

    cfg = decoder.get_config("nemotron-h-tiny")
    sizes = {**file_keys(cfg), "num_hidden_layers": cfg.num_layers,
             "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
             "intermediate_size": cfg.intermediate_size,
             "moe_intermediate_size": cfg.moe_intermediate_size,
             "moe_shared_expert_intermediate_size":
                 cfg.moe_shared_expert_intermediate_size,
             "n_routed_experts": 4, "published": {"n_routed_experts": 8}}
    mix = harness.rehearsal(_file(), traffic.load_mix("rollout-wide-ssd"))[1]
    per_page = costs.paged_bytes_per_token(sizes, 4) \
        * mix["engine"]["page_size"]
    config = {"preset": "nemotron-h-tiny", "reference": "nemotron_h",
              "dtype": "float32", "config": sizes,
              "serve": {"kv_pool_bytes": 1700 * per_page},
              "correct": correct or {"logprob_mean_abs_diff_max": 1e-5,
                                     "logprob_max_abs_diff_max": 5e-5,
                                     "state_rel_diff_max": 1e-5,
                                     "experts_rel_diff_max": 1e-5}}
    return cfg, config, mix


@pytest.mark.parametrize("control", ["", "state_bf16", "no_decay",
                                     "int8_experts", "fp8_weights"])
def test_the_plane_walks_a_tiny_model_of_the_family_end_to_end(
        control, monkeypatch, capsys):
    """``harness.rehearsal`` walks every cell with a dense model, so this
    is the walk of ``planes/rollout_nemotron_h.py`` on a model of its own
    family, here on the CPU in float32: the ``nemotron-h-tiny`` preset
    through the manager with the cell's mix at its rehearsal sizes, the
    router's bias evened by the reference: log-probabilities, the first
    Mamba-2 layer's state after every token consumed and the program's
    routed experts compared, every number held to a limit printed as a
    ``compared`` row (the harness's two in the result line, the plane's two
    on stderr, as ``rollout_sala.py`` prints its own) and every program's
    build seconds in ``checks.setup_builds``. With a ``control`` of ``correct`` in the program's place
    (``control_nemotron_h_on_chip``) the same walk has to come out
    ``correct: false`` by the limit that watches it."""
    import jax

    import control_nemotron_h_on_chip as control_mod

    _cfg, config, mix = _tiny_config()
    assert mix["plane"] == "rollout_nemotron_h"
    assert mix["engine"]["prefill_first"]
    cell = {"name": "nemotron-h-tiny.rehearsal", "chips": 1}
    plane = harness.load_named("planes", mix["plane"])
    assert jax.default_backend() == "cpu"
    monkeypatch.setattr(plane, "compare", plane.compare)
    if control:
        control_mod.in_the_programs_place(plane, [control])
    out = plane.run(cell, config, mix, harness.Device(1, True),
                    3141592653, 3.0, False, harness.CompileCounter(),
                    time.monotonic())
    compared = harness.compared(out, config, True)
    ref = out["checks"]["reference"]
    said = capsys.readouterr().err
    for k in plane.HELD:
        limit = config["correct"][k + "_max"]
        if k in compared:
            assert compared[k] == {"value": ref[k], "limit": limit}
        else:
            assert f"compared {k}: {ref[k]:g} (limit {limit:g})" in said
    builds = out["checks"]["setup_builds"]
    assert {kind for kind, _key, _s in builds} >= {"step"}, builds
    assert "programs built until prefilled: " in said
    if control:
        watched = control_mod.CONTROLS[control]
        assert ref["sound"]["ok"] and not ref["ok"], ref
        assert watched in ref["failed_by"]
        assert set(ref["failed_by"]) <= set(ref["controls"][control])
        assert ref["controls"][control][watched] > 1e-3
        assert not harness.verdict(out, True)
        return
    assert ref["ok"] and ref["failed_by"] == [], ref
    assert out["failed"] == 0 and out["checks"]["admitted"] == 4
    assert ref["sequences"] == 2 and ref["positions"] == 2 * 16
    assert all(n > 40 + 16 for n in ref["state_tokens"])
    assert len(ref["state_rel_diffs"][0]) == 2
    assert ref["experts_positions"] > 16
    assert out["checks"]["engine_recoveries"] == 0
    assert out["checks"]["kernels"] == {"kv_write": ["scatter"],
                                        "paged_attention": ["ref"]}
    obs = out["observed"]
    obs.update(config=config, mix=mix)
    info = obs["server_info"][-1]
    assert info["ssd_state_rows"] > 0 and info["moe_experts_hit"] > 0
    said = out["checks"]["window_counters"]
    assert said["ssd_state_rows"] == 2 * 4 and said["slot_yields"] == 0
    assert 0 < said["moe_experts_hit"] <= 2 * 4
    assert harness.load_reader("ssd_kernel_share")(obs) == 0.0
    assert harness.verdict(out, True)
    out["checks"]["reference"]["ok"] = False
    assert not harness.verdict(out, True)


def test_the_cells_own_rehearsal_passes():
    root = os.path.dirname(HERE)
    got = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seconds", "3"], cwd=root, capture_output=True,
        text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "passed"
