"""``costs_looped.py`` against the numbers of ISSUE 51, by hand and against
the parameter tree the program builds (to the unit: 2,667,974,657); the
preset against the configuration's file, key for key; the four readers
this cell brings on a hand-made decoded trace with fabricated counters,
and None where a scope, a counter or a family key is absent (the parent's
program, a dense model under a ``--rehearse-cpu`` walk); the plane walked
end to end on a tiny model of the family with the engine's count of the
passes' keys against the client's; walks in which ``correct`` has to come
out false, each by the limit that watches its fault; and the cell's own
``--rehearse-cpu`` walk.

Run by hand: ``python -m pytest benchmark/tests -q``."""

import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark.lib import costs_looped, harness, xspans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = os.path.join(HERE, "tests", "data", "tiny_scoped_tpu.xplane.pb")
READERS = ("ut_pass_ms", "passes_per_token", "decode_step_roofline.looped",
           "attn_core_roofline.looped")
CELL = "ouro-2.6b.rollout-short-looped"


def _ouro():
    return harness.load_config(os.path.join(HERE, "configs",
                                            "ouro-2.6b.json"))


def test_costs_of_the_published_sizes_are_the_issues_numbers():
    c = _ouro()["config"]
    assert costs_looped.is_looped(c) and costs_looped.passes(c) == 4
    assert costs_looped.layer_params(c) == 51_380_224
    assert costs_looped.stack_params(c) == 2_466_250_752
    assert 2 * costs_looped.head_params(c) == 201_326_592
    assert costs_looped.norm_params(c) == 393_216 + 2_048
    assert costs_looped.gate_params(c) == 2_049
    assert costs_looped.weight_params(c) == 2_667_974_657
    assert costs_looped.paged_bytes_per_token(c) == 1_572_864
    assert costs_looped.paged_bytes_per_token(c) * 64 == 100_663_296
    # a step at 3k tokens kept: the stack four times, the head, the pages
    step = costs_looped.decode_step_bytes(c, 4 * 3000.0)
    assert step == 4 * 4_932_501_504 + 201_326_592 + 3000 * 1_572_864
    assert 24.6e9 < step < 24.7e9


def test_the_programs_tree_has_the_counted_parameters():
    import jax

    from polyrl_tpu.models import cache_spec, decoder

    c = _ouro()["config"]
    cfg = decoder.get_config("ouro-2.6b")
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    sizes = {jax.tree_util.keystr(p): math.prod(a.shape)
             for p, a in jax.tree_util.tree_leaves_with_path(tree)}
    assert sum(sizes.values()) == costs_looped.weight_params(c)
    assert sum(n for k, n in sizes.items() if "norm" in k) \
        == costs_looped.norm_params(c)
    stack = sum(n for k, n in sizes.items()
                if "'gqa'" in k or "'dense'" in k)
    assert stack == costs_looped.stack_params(c)
    assert cache_spec.paged_bytes_per_token(cfg) \
        == costs_looped.paged_bytes_per_token(c)
    pools = jax.eval_shape(lambda: decoder.make_paged_pools(cfg, 97, 64,
                                                            slots=7))
    assert sum(math.prod(a.shape) * 2
               for a in jax.tree_util.tree_leaves(pools)) \
        == 97 * 64 * costs_looped.paged_bytes_per_token(c)


def test_the_preset_equals_the_configurations_file():
    """Key for key: every size the harness hands on as an override is the
    preset's own, the loader of the file's keys gives the preset, and the
    file holds the catalog's keys."""
    from polyrl_tpu.models import decoder, hf_loader

    config = _ouro()
    with open(os.path.join(HERE, "configs", "ouro-2.6b.json")) as f:
        raw = json.load(f)
    preset = decoder.get_config(config["preset"])
    assert config["preset"] == "ouro-2.6b" and raw["reduced"] == []
    for key, field in harness.MODEL_FIELDS.items():
        if key in config["config"]:
            got = getattr(preset, "head_dim_" if field == "head_dim"
                          else field)
            assert got == config["config"][key], key
    assert decoder.get_config(
        config["preset"], **harness.model_overrides(config)) == preset
    assert hf_loader.ouro_config(raw) == preset
    assert (raw["total_ut_steps"], raw["early_exit_threshold"],
            raw["model_type"]) == (4, 1, "ouro")
    assert raw["layer_types"] == ["full_attention"] * 48
    assert raw["sliding_window"] is None and raw["rope_scaling"] is None
    per_page = costs_looped.paged_bytes_per_token(config["config"]) * 64
    assert config["serve"]["kv_pool_bytes"] // per_page == 96
    bench = harness.load_benchmark()
    listed = [m["name"] for m in harness.cell_metrics(bench, CELL,
                                                      "per_layer")]
    assert set(READERS) <= set(listed)
    assert {"attn_core_ms", "attn_proj_ms", "decode_step_ms",
            "yield_share"} <= set(listed)
    assert not {"moe_route_ms", "swa_core_ms"} & set(listed)


TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 2,
        "head_dim": 4, "num_hidden_layers": 3, "intermediate_size": 5,
        "vocab_size": 32, "total_ut_steps": 2, "tie_word_embeddings": False}


def test_costs_of_a_hand_counted_tiny_case():
    c = TINY
    # q, k, v, o 8 x 8 each; the MLP's three 8 x 5
    assert costs_looped.layer_params(c) == 4 * 64 + 3 * 40
    assert costs_looped.weight_params(c) == (
        3 * 376 + 2 * 32 * 8 + 13 * 8 + 9)
    assert costs_looped.kv_bytes_a_layer(c) == 2 * 2 * 4 * 2
    assert costs_looped.paged_bytes_per_token(c) == 2 * 3 * 32
    assert costs_looped.attn_core_bytes(c, 200.0) == 200 * 3 * 32
    assert costs_looped.decode_step_bytes(c, 200.0) == (
        (2 * 3 * 376 + 32 * 8) * 2 + 200 * 3 * 32)


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
    """Two whole ``jit_step`` programs of 2 fused steps; nanoseconds under
    each scope; a prefill's operations count nowhere."""
    step = "jit(step)/while/body/closed_call/"
    loop = step + "while/body/closed_call/"
    kernel = "/jit(paged_attention_pallas)/paged_attention/pallas_call"
    ops = [("fusion.1", loop + "ut_norm/mul", 1000.0, 6.0),
           ("fusion.2", loop + "ut_pass/attn_qkv/dot_general", 1010.0, 20.0),
           ("paged_attention.6", loop + "ut_pass/attn_core" + kernel, 1300.0,
            80.0),
           ("fusion.4", loop + "ut_pass/mlp/dot_general", 1450.0, 30.0),
           ("while.3", loop + "ut_pass/attn_core/while", 1300.0, 500.0),
           ("fusion.5", step + "head/dot_general", 1800.0, 50.0),
           ("fusion.6", loop + "ut_pass/mlp/dot_general", 3200.0, 70.0),
           ("fusion.8", "jit(prefill_batch)/while/body/ut_pass/mlp/dot",
            9000.0, 70.0)]
    modules = [("jit_step(1)", 900.0, 1000.0), ("jit_step(1)", 3000.0, 1000.0),
               ("jit_prefill_batch(2)", 8900.0, 500.0)]
    return {"window": (0.0, 10000.0),
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


SAMPLES = [
    {"occupancy": 1.0},                                   # an older engine
    {"decode_steps_done": 80, "row_steps_done": 320, "ut_passes": 640,
     "kv_pass_rows_read": 5000},
    {"decode_steps_done": 880, "row_steps_done": 3520, "ut_passes": 7040,
     "kv_pass_rows_read": 5000 + 800 * 2 * 950},
]


def test_readers_on_a_decoded_trace_with_fabricated_counters(monkeypatch):
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    read = harness.load_reader
    obs = _obs(SAMPLES)
    c = obs["config"]["config"]
    per = 1e3 * 1e-9 / 4
    # a loop's own event is no operation; two passes a step
    assert read("ut_pass_ms")(obs) == pytest.approx(
        (20 + 80 + 30 + 70) * per / 2)
    assert read("attn_core_ms")(obs) == pytest.approx(80 * per)
    assert read("passes_per_token")(obs) == 2.0
    kv_mid = 1000.0 - 100.0 * (1.0 - 0.4 / 2.0)
    assert costs_looped.pass_rows_mid(obs) == 2 * kv_mid
    assert read("attn_core_roofline.looped")(obs) == pytest.approx(
        100.0 * (2 * kv_mid * 3 * 32) / 1e9 / (80e-9 / 4))
    step_s = 1000e-9 / 2
    assert read("decode_step_roofline.looped")(obs) == pytest.approx(
        100.0 * costs_looped.decode_step_bytes(c, 2 * kv_mid) / 1e9 / step_s)
    # the engine's count of a pass's keys against the client's: 950 keys a
    # step and pass, 950 tokens at the window's middle
    assert obs["checks"]["pass_rows"] == {
        "program_rows_a_step": 950.0, "client_tokens_mid_window": 950.0,
        "agree": True}
    # a pass left out is bytes left out, and shows
    short = [dict(SAMPLES[1]), dict(SAMPLES[2], ut_passes=640 + 3200 * 1.5)]
    assert read("passes_per_token")(_obs(short)) == 1.5
    assert read("decode_step_roofline.looped")(_obs(short)) < \
        read("decode_step_roofline.looped")(_obs(SAMPLES))
    off = [dict(SAMPLES[1]), dict(SAMPLES[2],
                                  kv_pass_rows_read=5000 + 800 * 2 * 800)]
    obs = _obs(off)
    read("decode_step_roofline.looped")(obs)
    assert obs["checks"]["pass_rows"]["agree"] is False


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
        assert read(name)(_obs(plain)) is None, name
        if name != "passes_per_token":
            assert read(name)(_obs(SAMPLES, config=dense)) is None, name
    # the recorded trace has ``attn_core`` and no ``ut_pass``
    assert read("ut_pass_ms")(_obs(SAMPLES)) is None
    # a rehearsal: no peaks, no reduced trace, no xplane at all
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    for name in READERS:
        if name != "passes_per_token":
            assert read(name)(_obs(SAMPLES, peaks=None, trace=None)) is None


def _tiny_config(correct=None):
    from benchmark.lib import traffic
    from polyrl_tpu.models import decoder

    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tests"))
    from test_looped import file_keys

    cfg = decoder.get_config("ouro-tiny")
    sizes = file_keys(cfg)
    mix = harness.rehearsal(_ouro(),
                            traffic.load_mix("rollout-short-looped"))[1]
    per_page = costs_looped.paged_bytes_per_token(sizes) \
        * mix["engine"]["page_size"]
    config = {"preset": "ouro-tiny", "reference": "looped_gqa",
              "dtype": "float32", "config": sizes,
              "serve": {"kv_pool_bytes": 3400 * per_page},
              "correct": correct or {"logprob_mean_abs_diff_max": 1e-5,
                                     "logprob_max_abs_diff_max": 5e-5,
                                     "pass_kv_rel_diff_max": 1e-5}}
    return cfg, config, mix


def test_the_looped_plane_walks_a_tiny_model_of_the_family_end_to_end():
    """``harness.rehearsal`` walks every cell with a dense model, so this
    is the walk of ``planes/rollout_looped.py`` on a model of its own
    family, here on the CPU in float32: the ``ouro-tiny`` preset through
    the manager with the cell's mix at its rehearsal sizes: the
    log-probabilities and every pass's pages compared, and the engine's
    count of the passes' keys beside the client's."""
    import jax

    _cfg, config, mix = _tiny_config()
    assert mix["plane"] == "rollout_looped" and mix["engine"]["prefill_first"]
    cell = {"name": "ouro-tiny.rehearsal", "chips": 1}
    plane = harness.load_named("planes", mix["plane"])
    assert jax.default_backend() == "cpu"
    out = plane.run(cell, config, mix, harness.Device(1, True), 3141592653,
                    3.0, False, harness.CompileCounter(), time.monotonic())
    ref = out["checks"]["reference"]
    assert ref["ok"] and ref["failed_by"] == [], ref
    assert out["failed"] == 0 and out["checks"]["admitted"] == 4
    assert ref["sequences"] == 2 and ref["positions"] == 2 * 16
    assert all(n > 40 + 16 for n in ref["page_tokens"])
    assert len(ref["pass_kv_rel_diffs"][0]) == 3
    assert out["checks"]["engine_recoveries"] == 0
    assert out["checks"]["kernels"] == {"kv_write": ["scatter"],
                                        "paged_attention": ["ref"]}
    obs = out["observed"]
    obs.update(config=config, mix=mix)
    info = obs["server_info"][-1]
    assert info["ut_passes"] > 0 and info["kv_pass_rows_read"] > 0
    said = out["checks"]["window_counters"]
    assert said["passes_per_token"] == 3.0 and said["slot_yields"] == 0
    assert harness.load_reader("passes_per_token")(obs) == 3.0
    # the program's count of a pass's keys a step against the client's
    # tokens of context at the window's middle (to a quarter here: a tiny
    # context doubles inside a 3 s window whose counter samples lie half a
    # second apart; tests/test_looped.py holds the count exactly)
    agree = costs_looped.rows_agree(obs)
    assert agree["program_rows_a_step"] == pytest.approx(
        agree["client_tokens_mid_window"], rel=0.25), agree
    assert harness.verdict(out, True)
    out["checks"]["reference"]["ok"] = False
    assert not harness.verdict(out, True)


@pytest.mark.parametrize("fault,watched", [
    ("passes_crossed", "pass_kv_rel_diff"), ("stale_page", "pass_kv_rel_diff"),
    ("pass_one_on", "pass_kv_rel_diff"), ("low", "logprob_mean_abs_diff")])
def test_correct_comes_out_false(fault, watched):
    """The comparison's own controls, on the CPU at the tiny size. What is
    handed in as the system's is the reference's own, so the number that
    watches the fault alone decides: ``passes_crossed``: passes 2 and 3
    attend and keep pass 1's keys; ``stale_page``: one page of one pass
    holds what its last owner left; ``pass_one_on``: every pass's pages
    hold the next pass's rows; ``low``: the whole forward in int8 weights
    and an int8 cache."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder

    _cfg, config, _mix = _tiny_config()
    cfg = decoder.get_config("ouro-tiny", dtype=jnp.float32)
    plane = harness.load_named("planes", "rollout_looped")
    reference = harness.load_named("references", "looped_gqa")
    params = jax.tree_util.tree_map(
        lambda a: a * 4.0,
        decoder.init_params(jax.random.PRNGKey(1), cfg))
    c, limits = config["config"], config["correct"]
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 512, 40).tolist()
    toks = rng.integers(1, 512, 24).tolist()
    got = reference.trace(params, c, prompt + toks, 40, 16)
    samples = [(prompt, toks[:16], got["logprobs"].tolist())]
    held = [{"answer": toks, "pass_kv": got["pass_kv"]}]
    walked = plane.walk(reference, c, params, samples, held)
    sound = plane.compare(limits, samples, held, walked)
    assert sound["ok"] and sound["failed_by"] == [], sound
    assert sound["pass_kv_rel_diff"] == 0.0
    if fault == "low":
        low = plane.walk(reference, c, params, samples, held, control="low")
        diff = np.abs(low[0]["logprobs"] - got["logprobs"])
        assert diff.mean() > 1e-4 > limits["logprob_mean_abs_diff_max"]
        return
    if fault == "passes_crossed":
        crossed = reference.trace(params, c, prompt + toks, 40, 16,
                                  control=fault)
        rows = crossed["pass_kv"]
        np.testing.assert_array_equal(rows[2][1], rows[0][1])
        assert np.abs(crossed["logprobs"] - got["logprobs"]).max() > 0
    elif fault == "pass_one_on":
        rows = got["pass_kv"][1:] + got["pass_kv"][:1]
    else:
        rows = [tuple(x.copy() for x in one) for one in got["pass_kv"]]
        rows[1][1][8:12] = 7.0          # a page of 4 tokens, the last layer
    bad = plane.compare(limits, samples,
                        [{"answer": toks, "pass_kv": rows}], walked)
    assert not bad["ok"] and bad["failed_by"] == [watched]
    assert bad[watched] > 0.1


def test_the_cells_rehearsal_walk_passes():
    """``run.py --rehearse-cpu --seconds 4`` of the cell: the tiny dense
    model under this cell's plane, traffic and readers."""
    ran = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seconds", "4", "--seed", "2790000011"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert ran.returncode == 0, ran.stderr[-2000:]
    line = json.loads(ran.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "passed" and line["failed"] == 0
