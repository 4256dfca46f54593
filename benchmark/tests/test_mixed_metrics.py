"""``costs_mixed.py`` against the numbers of ISSUE 47, by hand and against
the parameter tree the program builds (to the unit: 33,442,430,976 for the
uncut model); the preset against the configuration's file, key for key;
the four readers this cell brings on a hand-made decoded trace with
fabricated counters, and None where a scope, a counter or a family key is
absent (the parent's program, a dense model under a ``--rehearse-cpu``
walk); the plane walked end to end on a tiny model of the family with the
engine's count of the full layers' keys against the client's; and walks in
which ``correct`` has to come out false, each by the limit that watches
its fault.

Run by hand: ``python -m pytest benchmark/tests -q``."""

import os
import time

import numpy as np
import pytest

from benchmark.lib import costs, costs_mixed, harness, xspans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = os.path.join(HERE, "tests", "data", "tiny_scoped_tpu.xplane.pb")
READERS = ("attn_core_roofline.mixed", "swa_core_roofline.mixed",
           "attn_proj_ms", "decode_step_roofline.mixed")
LISTED = ("attn_core_ms", "swa_core_ms", "moe_route_ms", "moe_experts_ms",
          "moe_experts_roofline", "expert_load_skew", "experts_held_share")
CELL = "laguna-xs.2.rollout-long-mixed"


def _laguna():
    return harness.load_config(os.path.join(HERE, "configs",
                                            "laguna-xs.2.json"))


def test_costs_of_the_published_sizes_are_the_issues_numbers():
    c = _laguna()["config"]
    assert costs_mixed.is_mixed(c)
    assert costs_mixed.kinds(c) == ["full"] + ["window"] * 3 + ["full"] \
        + ["window"] * 3 + ["full"]
    assert costs_mixed.sparse_layers(c) == 8
    assert costs_mixed.mixer_params(c, 48) == 29_458_432
    assert costs_mixed.mixer_params(c, 64) == 37_879_808
    assert costs_mixed.dense_mlp_params(c) == 50_331_648
    assert costs_mixed.expert_params(c) == costs_mixed.shared_params(c) \
        == 3_145_728
    assert costs_mixed.router_params(c) == 524_288
    # a sparse layer as held here: 32 + 1 experts and the router
    assert 33 * 3_145_728 + 524_288 == 104_333_312
    assert costs_mixed.weight_params(c) == 1_611_694_080 == (
        3 * 29_458_432 + 6 * 37_879_808 + 50_331_648 + 8 * 104_333_312
        + 411_041_792)
    assert round(costs_mixed.weight_params(c) * 2 / 1e9, 2) == 3.22
    # the uncut model: the published 33.4 B
    whole = costs_mixed.published(c)
    assert costs_mixed.weight_params(whole) == 33_442_430_976 == (
        10 * 29_458_432 + 30 * 37_879_808 + 50_331_648
        + 39 * (257 * 3_145_728 + 524_288) + 411_041_792)
    # the FULL layers' K and V a token; a slot's rings
    assert costs_mixed.kv_bytes_a_layer(c) == 4096
    assert costs_mixed.paged_bytes_per_token(c) == 12_288
    assert costs.kv_bytes_per_token(c) == 9 * 4096       # what it is NOT
    assert costs_mixed.ring_bytes(c) == 12_582_912 == 6 * 2 * 1024 * 1024
    serve = _laguna()["serve"]
    assert serve["kv_pool_bytes"] == 10_240 * 64 * 12_288 == 8_053_063_680
    assert round(65 * costs_mixed.ring_bytes(c) / 1e9, 2) == 0.82
    # a decode step at 0.52M cached tokens with every held expert hit:
    # pages 6.4 GB, rings 0.8, the weights but the embedding 2.8 of which
    # the experts 1.6: 12.3 ms at 819 GB/s, attention 72% of it
    parts = (costs_mixed.attn_core_bytes(c, 520e3),
             costs_mixed.swa_core_bytes(c, 64 * 6 * 512),
             costs_mixed.dense_bytes(c) + 8 * 32 * 6_291_456)
    least = costs_mixed.decode_step_bytes(c, 8 * 32, 520e3, 64 * 6 * 512)
    assert [round(p / 1e9, 2) for p in parts] == [6.39, 0.81, 2.81]
    assert least == sum(parts)
    assert round(1e3 * least / 819e9, 1) == 12.2
    assert round((parts[0] + parts[1]) / least, 2) == 0.72


def test_the_programs_tree_has_the_counted_parameters():
    """``deployment`` in the configuration's file: recounted from the tree
    the program builds, to the unit, the share and the uncut model; and
    what a token and a slot keep from the program's own cache
    specification."""
    import jax

    from polyrl_tpu.models import cache_spec, decoder

    raw = _laguna()
    c = raw["config"]

    def count(t):
        return sum(a.size for a in jax.tree_util.tree_leaves(t))

    for preset, sizes in ((raw["preset"], c),
                          ("laguna-xs.2", costs_mixed.published(c))):
        cfg = decoder.get_config(preset)
        tree = jax.eval_shape(
            lambda cfg=cfg: decoder.init_params(jax.random.PRNGKey(0), cfg))
        layers = tree["layers"]
        norms = count([layers["attn_norm"], layers["mlp_norm"],
                       tree["final_norm"]])
        assert norms == costs_mixed.norm_params(sizes)
        assert count(tree) - norms == costs_mixed.weight_params(sizes)
        full = costs_mixed.count(sizes, "full")
        assert count(layers["gqa"]) == \
            full * costs_mixed.mixer_params(sizes, 48)
        assert count(layers["gqa_window"]) == \
            costs_mixed.count(sizes, "window") \
            * costs_mixed.mixer_params(sizes, 64)
        assert count(layers["dense"]) == costs_mixed.dense_mlp_params(sizes)
        n = costs_mixed.sparse_layers(sizes)
        assert count(layers["moe"]) == n * (
            sizes["num_experts"] * costs_mixed.expert_params(sizes)
            + costs_mixed.shared_params(sizes)
            + costs_mixed.router_params(sizes))
        assert "router_bias" not in layers["moe"]
        assert tree["lm_head"].shape == (2048, 100_352)
        assert [{"gqa": "full", "gqa_window": "window"}[p.mixer]
                for p in cache_spec.layer_plan(cfg)] == \
            costs_mixed.kinds(sizes)
    cfg = decoder.get_config(raw["preset"])
    assert count(jax.eval_shape(lambda: decoder.init_params(
        jax.random.PRNGKey(0), decoder.get_config("laguna-xs.2")))) \
        == 33_442_430_976 + 165_888
    assert cache_spec.paged_bytes_per_token(cfg) == \
        costs_mixed.paged_bytes_per_token(c)
    assert cache_spec.slot_bytes(cfg) == costs_mixed.ring_bytes(c)
    assert cache_spec.experts_held(cfg) == tuple(c["experts_held"]) == (0, 32)
    assert list(cfg.kept_layers) == c["kept_layers"]


def test_the_preset_equals_the_configurations_file():
    """``harness.MODEL_FIELDS`` carries only the dense GQA keys, so the
    family's keys reach the program through the preset: held equal here by
    ``hf_loader.laguna_config`` of the file's keys (the uncut model's with
    ``published`` laid over them), and the cell's entries in
    ``BENCHMARK.json`` beside them."""
    import dataclasses
    import json

    from polyrl_tpu.models import decoder, hf_loader

    raw = _laguna()
    c = raw["config"]
    cfg = decoder.get_config(raw["preset"], **harness.model_overrides(raw))
    assert cfg == decoder.get_config(raw["preset"])     # nothing overridden
    whole = hf_loader.laguna_config(costs_mixed.published(c))
    assert whole == decoder.get_config("laguna-xs.2")
    assert decoder.cut_to_share(
        whole, c["kept_layers"], c["chips_sharing_a_layer"],
        vocabulary_shares=1) == cfg
    # the file as it stands is the share but for what a file cannot say
    # (which published layers, which experts of how many)
    got = hf_loader.laguna_config(c)
    assert dataclasses.replace(
        cfg, kept_layers=None, experts_held=None, num_experts=32,
        layer_types=cfg.layer_types[:9],
        num_heads_per_layer=cfg.num_heads_per_layer[:9]) == got
    with open(os.path.join(HERE, "configs", "laguna-xs.2.json")) as f:
        file = json.load(f)
    assert file["reduced"] == ["num_hidden_layers", "num_experts",
                               "layer_types", "mlp_layer_types",
                               "num_attention_heads_per_layer"]
    assert file["published"]["num_hidden_layers"] == 40
    assert file["published"]["num_experts"] == 256
    assert len(file["assumed"]) >= 4 and "8 v5e chips" in file["deployment"]
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-xs.2", "rollout-long-mixed", 1)
    entry = next(e for e in bench["configs"] if e["name"] == "laguna-xs.2")
    assert entry["reduced"] == file["reduced"]
    assert entry["source"] == file["source"]
    mine = {m["name"] for m in harness.cell_metrics(bench, CELL, "per_layer")}
    assert set(READERS) <= mine and set(LISTED) <= mine
    # the readers whose cost is another family's arithmetic do not list
    # the cell
    assert not {"attn_core_roofline", "decode_step_roofline",
                "swa_core_roofline", "decode_step_roofline.moe"} & mine
    # and no cell but this one lists a reader it brings
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]


TINY = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 2, "intermediate_size": 12, "vocab_size": 32,
        "num_hidden_layers": 3, "sliding_window": 4, "gating": True,
        "layer_types": ["full_attention", "sliding_attention",
                        "sliding_attention"],
        "num_attention_heads_per_layer": [4, 6, 6],
        "mlp_layer_types": ["dense", "sparse", "sparse"],
        "num_experts": 2, "num_experts_per_tok": 2,
        "moe_intermediate_size": 3, "shared_expert_intermediate_size": 5,
        "published": {"num_experts": 8}, "tie_word_embeddings": False}


def test_costs_of_a_hand_counted_tiny_case():
    c = TINY
    assert costs_mixed.kinds(c) == ["full", "window", "window"]
    # heads of 2 over 2 K/V heads: q 8 x 8, k and v 8 x 4 each, the gate
    # 8 x 4, o 8 x 8
    assert costs_mixed.mixer_params(c, 4) == 64 + 2 * 32 + 32 + 64
    assert costs_mixed.mixer_params(c, 6) == 96 + 2 * 32 + 48 + 96
    assert costs_mixed.outside_experts_params(c) == (
        224 + 2 * 304 + 3 * 8 * 12 + 2 * (8 * 8 + 3 * 8 * 5))
    assert costs_mixed.weight_params(c) == (
        costs_mixed.outside_experts_params(c) + 2 * 2 * 72 + 2 * 32 * 8)
    assert costs_mixed.paged_bytes_per_token(c) == 1 * 2 * 2 * 2 * 2
    assert costs_mixed.ring_bytes(c) == 2 * 4 * 16
    got = costs_mixed.decode_step_bytes(c, experts_hit=3, kv_tokens_read=100,
                                        window_rows=24)
    assert got == ((costs_mixed.outside_experts_params(c) + 32 * 8) * 2
                   + 3 * 72 * 2 + 100 * 16 + 24 * 16)


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
    kernel = "/jit(paged_attention_pallas)/paged_attention/pallas_call"
    ops = [("fusion.1", step + "attn_qkv/dot_general", 1000.0, 20.0),
           ("paged_attention.5", step + "swa_core" + kernel, 1200.0, 40.0),
           ("paged_attention.6", step + "attn_core" + kernel, 1300.0, 80.0),
           ("fusion.3", step + "attn_out/dot_general", 1400.0, 10.0),
           ("fusion.4", step + "mlp/moe_experts/custom-call", 1450.0, 30.0),
           ("fusion.6", step + "attn_qkv/dot_general", 3200.0, 70.0),
           ("fusion.8", "jit(prefill_extend)/attn_qkv/dot", 9000.0, 70.0)]
    modules = [("jit_step(1)", 900.0, 1000.0), ("jit_step(1)", 3000.0, 1000.0),
               ("jit_prefill_extend(2)", 8900.0, 500.0)]
    return {"window": (0.0, 10000.0),
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


SAMPLES = [
    {"occupancy": 1.0},                                   # an older engine
    {"decode_steps_done": 80, "paged_rows_read": 5000,
     "window_rows_read": 900, "moe_experts_hit": 100},
    {"decode_steps_done": 880, "paged_rows_read": 5000 + 800 * 950,
     "window_rows_read": 900 + 800 * 32, "moe_experts_hit": 100 + 800 * 3},
]


def test_readers_on_a_decoded_trace_with_fabricated_counters(monkeypatch):
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    read = harness.load_reader
    obs = _obs(SAMPLES)
    c = obs["config"]["config"]
    per = 1e3 * 1e-9 / 4
    assert read("attn_proj_ms")(obs) == pytest.approx((90 + 10) * per)
    assert read("swa_core_ms")(obs) == pytest.approx(40 * per)
    assert read("attn_core_ms")(obs) == pytest.approx(80 * per)
    assert read("moe_experts_ms")(obs) == pytest.approx(30 * per)
    assert costs_mixed.counted_per_step(obs, "window_rows_read") == 32.0
    kv_mid = 1000.0 - 100.0 * (1.0 - 0.4 / 2.0)
    assert read("swa_core_roofline.mixed")(obs) == pytest.approx(
        100.0 * (32 * 16) / 1e9 / (40e-9 / 4))
    assert read("attn_core_roofline.mixed")(obs) == pytest.approx(
        100.0 * (kv_mid * 16) / 1e9 / (80e-9 / 4))
    # the engine's count of the full layers' keys against the client's:
    # one full layer, 950 keys a step, 950 tokens at the window's middle
    assert obs["checks"]["paged_rows"] == {
        "program_rows_a_step": 950.0, "client_tokens_mid_window": 950.0,
        "agree": True}
    step_s = 1000e-9 / 2
    assert read("decode_step_roofline.mixed")(obs) == pytest.approx(
        100.0 * costs_mixed.decode_step_bytes(c, 3.0, kv_mid, 32.0) / 1e9
        / step_s)
    off = [dict(SAMPLES[1]), dict(SAMPLES[2],
                                  paged_rows_read=5000 + 800 * 800)]
    obs = _obs(off)
    read("attn_core_roofline.mixed")(obs)
    assert obs["checks"]["paged_rows"]["agree"] is False


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
    plain = [{"decode_steps_done": 80}, {"decode_steps_done": 880}]
    for name in READERS:
        assert read(name)(_obs(plain, config=dense)) is None, name
        assert read(name)(_obs(SAMPLES, config=dense)) is None, name
        if name != "attn_core_roofline.mixed":
            # (the pages' share needs the client's count alone; the
            # recorded trace has ``attn_core``, a whole step and no other
            # scope of this family)
            assert read(name)(_obs(plain)) is None, name
    assert read("swa_core_roofline.mixed")(_obs(SAMPLES)) is None
    assert read("attn_proj_ms")(_obs(SAMPLES)) is None
    # a rehearsal: no peaks, no reduced trace, no xplane at all
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    for name in READERS:
        assert read(name)(_obs(SAMPLES, peaks=None, trace=None)) is None, name


def _tiny_config(correct=None):
    import sys

    from benchmark.lib import traffic
    from polyrl_tpu.models import decoder

    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tests"))
    from test_mixed_gqa import file_keys

    cfg = decoder.get_config("mixed-tiny")
    sizes = file_keys(cfg)
    mix = harness.rehearsal(_laguna(),
                            traffic.load_mix("rollout-long-mixed"))[1]
    per_page = costs_mixed.paged_bytes_per_token(sizes) \
        * mix["engine"]["page_size"]
    config = {"preset": "mixed-tiny", "reference": "moe_gqa_mixed",
              "dtype": "float32", "config": sizes,
              "serve": {"kv_pool_bytes": 3400 * per_page},
              "correct": correct or {"logprob_mean_abs_diff_max": 1e-5,
                                     "logprob_max_abs_diff_max": 5e-5,
                                     "experts_rel_diff_max": 1e-5,
                                     "window_rel_diff_max": 1e-5}}
    return cfg, config, mix


def test_the_mixed_plane_walks_a_tiny_model_of_the_family_end_to_end():
    """``harness.rehearsal`` walks every cell with a dense model, so this
    is the walk of ``planes/rollout_mixed.py`` on a model of its own
    family, here on the CPU in float32: the ``mixed-tiny`` preset through
    the manager with the cell's mix at its rehearsal sizes (chunks held
    first, no prefix cache): the log-probabilities, the held experts and
    the first window layer's ring compared, and the engine's count of the
    full layers' keys beside the client's."""
    import jax

    _cfg, config, mix = _tiny_config()
    assert mix["plane"] == "rollout_mixed" and mix["engine"]["prefill_first"]
    cell = {"name": "mixed-tiny.rehearsal", "chips": 1}
    plane = harness.load_named("planes", mix["plane"])
    assert jax.default_backend() == "cpu"
    out = plane.run(cell, config, mix, harness.Device(1, True), 3141592653,
                    3.0, False, harness.CompileCounter(), time.monotonic())
    ref = out["checks"]["reference"]
    assert ref["ok"] and ref["failed_by"] == [], ref
    assert out["failed"] == 0 and out["checks"]["admitted"] == 4
    assert "level" in out["checks"]["setup_phases_s"]
    assert ref["sequences"] == 2 and ref["positions"] == 2 * 16
    assert all(n > 40 + 16 for n in ref["ring_tokens"])
    assert len(ref["window_rel_diffs"][0]) == 3
    assert ref["experts_positions"] > 16
    assert out["checks"]["engine_recoveries"] == 0
    assert out["checks"]["kernels"] == {"kv_write": ["scatter"],
                                        "paged_attention": ["ref"]}
    obs = out["observed"]
    obs.update(config=config, mix=mix)
    info = obs["server_info"][-1]
    assert info["paged_rows_read"] > 0 and info["window_rows_read"] > 0
    # the program's count of the full layers' keys a step and a layer
    # against the client's tokens of context at the window's middle (to a
    # quarter here: a tiny context doubles inside a 3 s window whose
    # counter samples lie half a second apart, so the two middles differ;
    # a count over both full layers would be 2x off. tests/
    # test_mixed_gqa.py holds the count exactly)
    agree = costs_mixed.rows_agree(obs)
    assert agree["program_rows_a_step"] == pytest.approx(
        agree["client_tokens_mid_window"], rel=0.25), agree
    assert costs_mixed.counted_per_step(obs, "window_rows_read") == \
        pytest.approx(3 * 4 * 8, rel=0.05)
    said = out["checks"]["window_counters"]
    assert 10.0 < said["experts_held_share"] < 50.0
    assert 0.0 < said["experts_hit_a_layer"] <= 4.0
    assert harness.verdict(out, True)
    out["checks"]["reference"]["ok"] = False
    assert not harness.verdict(out, True)


@pytest.mark.parametrize("fault,watched", [
    ("window_minus", "window_rel_diff"), ("stale_ring", "window_rel_diff"),
    ("ring_one_place_on", "window_rel_diff"),
    ("int8_experts", "experts_rel_diff"),
    ("low", "logprob_mean_abs_diff")])
def test_correct_comes_out_false(fault, watched):
    """The comparison's own controls, on the CPU at the tiny size. What is
    handed in as the system's is the reference's own, so the number that
    watches the fault alone decides: ``window_minus``: a window of one key
    fewer; ``stale_ring``: the ring of the token BEFORE the last (what a
    chunk boundary off by one leaves); ``ring_one_place_on``: every row
    one place on; ``int8_experts``: the held experts rounded to int8;
    ``low``: the whole forward in int8 weights and an int8 cache."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder

    _cfg, config, _mix = _tiny_config()
    cfg = decoder.get_config("mixed-tiny", dtype=jnp.float32)
    plane = harness.load_named("planes", "rollout_mixed")
    reference = harness.load_named("references", "moe_gqa_mixed")
    params = decoder.init_params(jax.random.PRNGKey(1), cfg)
    c, limits = config["config"], config["correct"]
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 512, 40).tolist()
    toks = rng.integers(1, 512, 24).tolist()
    window = cfg.sliding_window

    def as_held(tr, consumed, shift=0):
        """A reference trace in the form ``CBEngine.recurrent_state``
        hands out: the rings in layer order, a ring's rows at their
        positions modulo the window (``shift`` places on)."""
        rings = []
        for rows, first in tr["rings"]:
            ring = np.zeros((window, *rows.shape[1:]), np.float32)
            for i, row in enumerate(rows):
                ring[(first + i + shift) % window] = row
            rings.append(ring)
        return rings

    n = len(prompt) + len(toks)
    got = reference.trace(params, c, prompt + toks, 40, 16)
    samples = [(prompt, toks[:16], got["logprobs"].tolist())]
    held = [{"answer": toks, "states": as_held(got, n)}]
    walked = plane.walk(reference, cfg, params, c, samples, held)
    sound = plane.compare(reference, params, c, limits, samples, held,
                          walked)
    assert sound["ok"] and sound["failed_by"] == [], sound
    assert sound["window_rel_diff"] == 0.0
    assert sound["experts_rel_diff"] < 1e-6
    if fault in ("stale_ring", "ring_one_place_on", "window_minus"):
        if fault == "stale_ring":
            before = reference.trace(params, c, prompt + toks[:-1], 40, 16)
            rings = as_held(before, n - 1)
        elif fault == "ring_one_place_on":
            rings = as_held(got, n, shift=1)
        else:
            low = reference.trace(params, c, prompt + toks, 40, 16,
                                  control=fault)
            rings = as_held(low, n)
            assert np.abs(low["logprobs"] - got["logprobs"]).max() > 0
        bad = plane.compare(reference, params, c, limits, samples,
                            [{"answer": toks, "states": rings}], walked)
        assert not bad["ok"] and bad["failed_by"] == [watched]
        assert bad[watched] > 0.1
        return
    if fault == "int8_experts":
        rows = plane.experts_rel(reference, params, c, walked, control=fault)
        assert float(np.median(rows)) > 1e-3 > limits["experts_rel_diff_max"]
        return
    low = plane.walk(reference, cfg, params, c, samples, held, control="low")
    diff = np.abs(low[0]["logprobs"] - got["logprobs"])
    assert diff.mean() > 1e-4 > limits["logprob_mean_abs_diff_max"]
