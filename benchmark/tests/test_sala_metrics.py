"""``costs_sala.py`` against the numbers of ISSUE 56, by hand and against
the parameter tree and the pools the program builds; the preset against the
configuration's file, key for key; the readers this cell brings on a
hand-made decoded trace with fabricated counters, and None where a scope, a
counter or a family key is absent (the parent's program, a dense model
under a ``--rehearse-cpu`` walk); the plane walked end to end on a tiny
model of the family; walks in which ``correct`` has to come out false, each
by the limit that watches its fault; and the cell's own ``--rehearse-cpu``
walk.

Run by hand: ``python -m pytest benchmark/tests -q``."""

import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark.lib import costs_sala, harness, xspans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = os.path.join(HERE, "tests", "data", "tiny_scoped_tpu.xplane.pb")
READERS = ("sparse_select_ms", "lightning_core_ms", "lightning_proj_ms",
           "attn_core_roofline.sala", "sparse_select_roofline",
           "lightning_core_roofline", "decode_step_roofline.sala",
           "sparse_pages_per_row", "lightning_kernel_share")
COUNTED = ("sparse_pages_per_row", "lightning_kernel_share")
CELL = "minicpm-sala.rollout-long-sparse-linear"


def _sala():
    return harness.load_config(os.path.join(HERE, "configs",
                                            "minicpm-sala.json"))


def test_costs_of_the_published_sizes_are_the_issues_numbers():
    c = _sala()["config"]
    assert costs_sala.is_sala(c)
    assert costs_sala.kinds(c) == ["sparse"] + ["lightning"] * 6 + [
        "sparse", "sparse"] + ["lightning"] * 3
    assert costs_sala.mlp_params(c) == 201_326_592
    assert costs_sala.sparse_params(c) == 52_428_800 + 256
    assert costs_sala.lightning_params(c) == 83_886_080 + 384 + 32
    # ISSUE 56's 3,929,866,240 in matrices; norms and slopes beside them
    assert costs_sala.weight_params(c) == 3_929_866_240 + 25 * 4096 \
        + 3 * 256 + 9 * 416
    # a K/V pair and 64 B of float32 pooled keys a sparse layer (the issue
    # reckoned 32 B of bfloat16: 3,168)
    assert costs_sala.paged_bytes_per_token(c) == 3 * (1024 + 64) == 3264
    # nine states of 2 MiB and three tables of 128 pages a K/V head
    assert costs_sala.slot_bytes(c) == 9 * 2 * 2**20 + 3 * 2 * 129 * 4 \
        == 18_877_464
    assert costs_sala.page_bytes_a_head(c) == 32_768
    assert costs_sala.pooled_key_bytes(c) == 1024
    d = costs_sala.deployment(c, 96, _sala()["serve"]["kv_pool_bytes"])
    assert d["pages"] == 22_978 and d["pool_tokens"] == 22_977 * 64
    assert 7.85e9 < d["weight_bytes"] < 7.87e9
    assert 1.83e9 < d["state_bytes"] < 1.84e9
    assert 4.79e9 < d["pool_bytes"] <= 4.81e9
    # a step at 96 rows past dense_len with 1,000 pooled keys each: the 12
    # layers and the head, 64 pages a row, head and sparse layer, the states
    pages, scored, rows = 96 * 2 * 3 * 64.0, 96 * 3 * 1000.0, 96 * 9.0
    step = costs_sala.decode_step_bytes(c, pages, scored, rows)
    assert step == (2 * costs_sala.dense_params(c) + pages * 32_768
                    + scored * 1024 + rows * 2 * 2 * 2**20)
    assert 12.3e9 < step < 12.5e9
    peaks = {"bytes": 819e9, "flops": 197e12}
    # bytes decide everywhere: 16 FLOPs a K/V byte, under the ridge of 240
    assert costs_sala.least_seconds(
        peaks, costs_sala.attn_core_bytes(c, pages),
        costs_sala.attn_core_flops(c, pages)) == pages * 32_768 / 819e9


def test_the_programs_tree_has_the_counted_parameters():
    import jax

    from polyrl_tpu.models import cache_spec, decoder

    config = _sala()
    c = config["config"]
    cfg = decoder.get_config("minicpm-sala")
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    sizes = {jax.tree_util.keystr(p): math.prod(a.shape)
             for p, a in jax.tree_util.tree_leaves_with_path(tree)}
    assert sum(sizes.values()) == costs_sala.weight_params(c)
    assert sum(n for k, n in sizes.items() if "'sparse'" in k) \
        == 3 * costs_sala.sparse_params(c)
    assert sum(n for k, n in sizes.items() if "'lightning'" in k) \
        == 9 * costs_sala.lightning_params(c)
    assert cache_spec.paged_bytes_per_token(cfg) \
        == costs_sala.paged_bytes_per_token(c)
    assert cache_spec.slot_bytes(cfg) == costs_sala.slot_bytes(c)
    d = costs_sala.deployment(c, 96, config["serve"]["kv_pool_bytes"])
    pools = jax.eval_shape(lambda: decoder.make_paged_pools(
        cfg, d["pages"], 64, slots=97))
    nbytes = lambda t: sum(math.prod(a.shape) * a.dtype.itemsize
                           for a in jax.tree_util.tree_leaves(t))
    assert nbytes(pools[0]) == d["pool_bytes"]
    assert nbytes(pools[1]) == d["state_bytes"]


def test_the_preset_equals_the_configurations_file():
    """Key for key: every size the harness hands on as an override is the
    preset's own, the loader of the file's keys gives the preset, and the
    file holds the catalog's keys but for the two it says it reduced."""
    from polyrl_tpu.models import decoder, hf_loader

    config = _sala()
    with open(os.path.join(HERE, "configs", "minicpm-sala.json")) as f:
        raw = json.load(f)
    preset = decoder.get_config(config["preset"])
    assert config["preset"] == "minicpm-sala"
    assert raw["reduced"] == ["num_hidden_layers", "mixer_types"]
    for key, field in harness.MODEL_FIELDS.items():
        if key in config["config"]:
            got = getattr(preset, "head_dim_" if field == "head_dim"
                          else field)
            assert got == config["config"][key], key
    assert decoder.get_config(
        config["preset"], **harness.model_overrides(config)) == preset
    assert hf_loader.minicpm_sala_config(raw) == preset
    whole = raw["published_mixer_types"]
    assert whole == list(preset.mixer_types) and raw["mixer_types"] \
        == whole[9:21]
    assert raw["published_num_hidden_layers"] == len(whole) == 32
    sp = raw["sparse_config"]
    assert (sp["kernel_size"], sp["kernel_stride"], sp["block_size"],
            sp["topk"], sp["init_blocks"], sp["window_size"],
            sp["dense_len"]) == (
        preset.sparse_kernel_size, preset.sparse_kernel_stride,
        preset.sparse_block_size, preset.sparse_topk,
        preset.sparse_init_blocks, preset.sparse_window_size,
        preset.sparse_dense_len)
    assert (raw["scale_emb"], raw["scale_depth"], raw["dim_model_base"]) == (
        preset.scale_emb, preset.scale_depth, preset.dim_model_base)
    assert (raw["hidden_size"], raw["intermediate_size"],
            raw["num_attention_heads"], raw["num_key_value_heads"],
            raw["head_dim"], raw["lightning_nh"], raw["lightning_head_dim"],
            raw["vocab_size"]) == (4096, 16384, 32, 2, 128, 32, 128, 73448)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if '"MiniCPM-SALA"' in l)
        assert raw["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in raw["reduced"]:
                assert raw[key] == value, key
        assert whole == row["config"]["mixer_types"]
    bench = harness.load_benchmark()
    listed = [m["name"] for m in harness.cell_metrics(bench, CELL,
                                                      "per_layer")]
    assert set(READERS) <= set(listed)
    assert {"attn_core_ms", "attn_qkv_ms", "attn_out_ms", "mlp_dense_ms",
            "decode_step_ms", "head_sample_ms", "yield_share"} <= set(listed)
    assert not {"moe_route_ms", "swa_core_ms", "kda_core_ms"} & set(listed)
    mix = json.load(open(os.path.join(HERE, "traffic",
                                      "rollout-long-sparse-linear.json")))
    assert mix["engine"]["page_size"] == sp["block_size"]
    assert mix["offered_requests"] == mix["engine"]["max_slots"] == 96


TINY = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 4, "lightning_nh": 2, "lightning_head_dim": 4,
        "num_hidden_layers": 3, "intermediate_size": 5, "vocab_size": 32,
        "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn"],
        "sparse_config": {"kernel_size": 4, "kernel_stride": 2,
                          "block_size": 4, "topk": 2, "init_blocks": 1,
                          "window_size": 4, "dense_len": 8}}


def test_costs_of_a_hand_counted_tiny_case():
    c = TINY
    # q 8x16, k and v 8x8 each, gate and o 8x16 each, two norms of 4
    assert costs_sala.sparse_params(c) == 128 + 128 + 2 * 128 + 8
    # five 8x8 products, three norms of 4, two slopes
    assert costs_sala.lightning_params(c) == 5 * 64 + 12 + 2
    assert costs_sala.weight_params(c) == (
        520 + 2 * 334 + 3 * (120 + 16) + 2 * 32 * 8 + 8)
    assert costs_sala.paged_bytes_per_token(c) == 2 * 2 * 4 * 2 + 2 * 4 * 4 // 2
    # two states, and one table of 2 pages and a count of keys a K/V head
    assert costs_sala.slot_bytes(c) == 2 * 2 * 16 * 4 + 2 * 3 * 4
    assert costs_sala.attn_core_bytes(c, 10.0) == 10 * 2 * 4 * 4 * 2
    assert costs_sala.select_bytes(c, 7.0) == 7 * 2 * 4 * 4
    assert costs_sala.lightning_core_bytes(c, 3.0) == 3 * 2 * 128


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
    ops = [("fusion.1", step + "attn_qkv/dot_general", 1010.0, 20.0),
           ("fusion.2", step + "sparse_select/gather", 1040.0, 40.0),
           ("paged_attention.6", step + "attn_core" + kernel, 1300.0, 80.0),
           ("fusion.3", step + "lightning_proj/dot_general", 1400.0, 30.0),
           ("lightning_state.2", step + "lightning_core/pallas_call", 1450.0,
            60.0),
           ("fusion.5", step + "head/dot_general", 1800.0, 50.0),
           ("fusion.6", step + "sparse_select/reduce", 3200.0, 24.0),
           ("fusion.8", "jit(prefill_batch)/sparse_select/dot", 9000.0, 70.0)]
    modules = [("jit_step(1)", 900.0, 1000.0), ("jit_step(1)", 3000.0, 1000.0),
               ("jit_prefill_batch(2)", 8900.0, 500.0)]
    return {"window": (0.0, 10000.0),
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


SAMPLES = [
    {"occupancy": 1.0},                                   # an older engine
    {"decode_steps_done": 80, "row_steps_done": 320, "sparse_pages_read": 100,
     "sparse_pooled_scored": 50, "sparse_dense_rows": 0,
     "lightning_state_rows": 640, "lightning_kernel_steps": 80},
    {"decode_steps_done": 880, "row_steps_done": 3520,
     "sparse_pages_read": 100 + 3200 * 2 * 2,
     "sparse_pooled_scored": 50 + 800 * 4 * 30, "sparse_dense_rows": 0,
     "lightning_state_rows": 640 + 800 * 8, "lightning_kernel_steps": 880},
]


def test_readers_on_a_decoded_trace_with_fabricated_counters(monkeypatch):
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    read = harness.load_reader
    obs = _obs(SAMPLES)
    c = obs["config"]["config"]
    per = 1e3 * 1e-9 / 4
    assert read("sparse_select_ms")(obs) == pytest.approx((40 + 24) * per)
    assert read("lightning_core_ms")(obs) == pytest.approx(60 * per)
    assert read("lightning_proj_ms")(obs) == pytest.approx(30 * per)
    assert read("attn_core_ms")(obs) == pytest.approx(80 * per)
    # 2 pages a row, head and sparse layer (one sparse layer, two heads)
    assert read("sparse_pages_per_row")(obs) == 2.0
    assert read("lightning_kernel_share")(obs) == 100.0
    pages, scored, rows = 16.0, 120.0, 8.0
    assert read("attn_core_roofline.sala")(obs) == pytest.approx(
        100.0 * costs_sala.attn_core_bytes(c, pages) / 1e9 / (80e-9 / 4))
    assert read("sparse_select_roofline")(obs) == pytest.approx(
        100.0 * costs_sala.select_bytes(c, scored) / 1e9 / (64e-9 / 4))
    assert read("lightning_core_roofline")(obs) == pytest.approx(
        100.0 * costs_sala.lightning_core_bytes(c, rows) / 1e9 / (60e-9 / 4))
    step_s = 1000e-9 / 2
    assert read("decode_step_roofline.sala")(obs) == pytest.approx(
        100.0 * costs_sala.decode_step_bytes(c, pages, scored, rows) / 1e9
        / step_s)
    # rows that attend densely show as more pages a row
    more = [dict(SAMPLES[1]), dict(SAMPLES[2],
                                   sparse_pages_read=100 + 3200 * 2 * 5)]
    assert read("sparse_pages_per_row")(_obs(more)) == 5.0


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
        if name not in COUNTED + ("attn_core_roofline.sala",
                                  "decode_step_roofline.sala"):
            # the recorded trace has ``attn_core`` and whole steps, and
            # none of this family's own scopes
            assert read(name)(_obs(SAMPLES)) is None, name
    assert read("sparse_pages_per_row")(_obs(SAMPLES, config=dense)) is None
    # a rehearsal: no peaks, no reduced trace, no xplane at all
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    for name in READERS:
        if name not in COUNTED:
            assert read(name)(_obs(SAMPLES, peaks=None, trace=None)) is None


def _tiny_config(correct=None):
    from benchmark.lib import traffic
    from polyrl_tpu.models import decoder

    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tests"))
    from test_sala import file_keys

    cfg = decoder.get_config("minicpm-sala-tiny")
    sizes = file_keys(cfg)
    mix = harness.rehearsal(
        _sala(), traffic.load_mix("rollout-long-sparse-linear"))[1]
    per_page = costs_sala.paged_bytes_per_token(sizes, 4) \
        * mix["engine"]["page_size"]
    config = {"preset": "minicpm-sala-tiny",
              "reference": "sala_sparse_linear", "dtype": "float32",
              "config": sizes, "serve": {"kv_pool_bytes": 1700 * per_page},
              "correct": correct or {"logprob_mean_abs_diff_max": 1e-5,
                                     "logprob_max_abs_diff_max": 5e-5,
                                     "state_rel_diff_max": 1e-5,
                                     "selected_set_diff_max": 0.0,
                                     "pooled_rel_diff_max": 1e-5}}
    return cfg, config, mix


@pytest.mark.parametrize("fault", ["", "first_pages", "no_head_offset"])
def test_the_sala_plane_walks_a_tiny_model_of_the_family_end_to_end(fault):
    """``harness.rehearsal`` walks every cell with a dense model, so this
    is the walk of ``planes/rollout_sala.py`` on a model of its own family,
    here on the CPU in float32: the ``minicpm-sala-tiny`` preset through the
    manager with the cell's mix at its rehearsal sizes (pages of 8, the
    tiny block): log-probabilities, the first lightning layer's state, the
    pooled keys the pages hold and the table of pages that the timed decode
    step itself attended compared, every row past the tiny ``dense_len``.
    With a ``fault`` planted in the PROGRAM's table
    (``control_sala_on_chip.plant``: the engine compiles the altered step)
    the same walk has to come out ``correct: false`` by
    ``selected_set_diff``, while the program's choice run again from the
    store after the window still agrees with the reference."""
    import jax

    import control_sala_on_chip as control

    _cfg, config, mix = _tiny_config()
    assert mix["plane"] == "rollout_sala" and mix["engine"]["prefill_first"]
    assert mix["engine"]["page_size"] == 8
    cell = {"name": "minicpm-sala-tiny.rehearsal", "chips": 1}
    plane = harness.load_named("planes", mix["plane"])
    assert jax.default_backend() == "cpu"
    undo = control.plant(fault) if fault else lambda: None
    try:
        out = plane.run(cell, config, mix, harness.Device(1, True),
                        3141592653, 3.0, False, harness.CompileCounter(),
                        time.monotonic())
    finally:
        undo()
    ref = out["checks"]["reference"]
    if fault:
        assert not ref["ok"] and "selected_set_diff" in ref["failed_by"], ref
        assert min(ref["selected_set_diffs"]) >= 0.25
        assert ref["selected_again_diff"] == 0.0
        assert not harness.verdict(out, True)
        return
    assert ref["ok"] and ref["failed_by"] == [], ref
    assert ref["selected_again_diff"] == 0.0
    assert out["failed"] == 0 and out["checks"]["admitted"] == 4
    assert ref["sequences"] == 2 and ref["positions"] == 2 * 16
    assert all(n > 40 + 16 for n in ref["state_tokens"])
    assert ref["selected_set_diffs"] == [0.0, 0.0]
    assert out["checks"]["engine_recoveries"] == 0
    assert out["checks"]["kernels"] == {"kv_write": ["scatter"],
                                        "paged_attention": ["ref"]}
    obs = out["observed"]
    obs.update(config=config, mix=mix)
    info = obs["server_info"][-1]
    assert info["sparse_pages_read"] > 0 and info["lightning_state_rows"] > 0
    said = out["checks"]["window_counters"]
    assert said["sparse_pages_per_row"] == 4.0 and said["slot_yields"] == 0
    assert said["sparse_dense_rows"] == 0.0
    assert harness.load_reader("sparse_pages_per_row")(obs) == 4.0
    assert harness.load_reader("lightning_kernel_share")(obs) == 0.0
    assert harness.verdict(out, True)
    out["checks"]["reference"]["ok"] = False
    assert not harness.verdict(out, True)


@pytest.mark.parametrize("fault,watched", [
    ("state_bf16", "state_rel_diff"), ("no_decay", "state_rel_diff"),
    ("first_blocks", "selected_set_diff"),
    ("pooled_unwritten", "pooled_rel_diff"),
    ("low", "logprob_mean_abs_diff")])
def test_correct_comes_out_false(fault, watched):
    """The comparison's own controls, on the CPU at the tiny size. What is
    handed in as the system's is the reference's own under the control, so
    the number that watches the fault decides."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder

    _cfg, config, _mix = _tiny_config()
    cfg = decoder.get_config("minicpm-sala-tiny", dtype=jnp.float32)
    plane = harness.load_named("planes", "rollout_sala")
    control = harness.load_named("planes", "rollout_sala")
    reference = harness.load_named("references", "sala_sparse_linear")
    params = decoder.init_params(jax.random.PRNGKey(1), cfg)
    params["layers"]["sparse"]["q_norm"] = \
        params["layers"]["sparse"]["q_norm"] * 3.0
    c, limits = config["config"], dict(config["correct"])
    limits["logprob_mean_abs_diff_max"] = 1e-6
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 512, 100).tolist()
    toks = rng.integers(1, 512, 28).tolist()
    got = reference.trace(params, c, prompt + toks, 100, 16)

    def store(pooled):
        pad = -len(pooled) % 2
        rows = np.pad(pooled, ((0, pad + 2), (0, 0), (0, 0)))
        return rows.reshape(-1, 4, 16)

    pages = np.arange(5, 5 + 16)

    def table(chosen):
        """A slot's row ``[Hkv, W + 1]`` as a decode step leaves it for the
        blocks ``chosen`` [Hkv, 16] of a row of 128 tokens in ``pages``,
        head g's offset by ``40 g``."""
        rows = np.zeros((2, 5), np.int32)
        for g, took in enumerate(chosen):
            at = np.flatnonzero(took)
            rows[g, :len(at)] = pages[at] + 40 * g
            rows[g, -1] = 8 * len(at)
        return rows

    def held_of(tr, chosen):
        return [{"answer": toks, "states": [tr["state"]],
                 "pooled": store(tr["pooled"]), "picked": table(chosen),
                 "pages": pages, "pool_pages": 40}]

    samples = [(prompt, toks[:16], got["logprobs"].tolist())]
    held = held_of(got, got["chosen"])
    walked = plane.walk(reference, cfg, params, c, samples, held)
    sound = plane.compare(limits, c, samples, held, walked)
    assert sound["ok"] and sound["failed_by"] == [], sound
    assert sound["selected_again_diff"] == 0.0
    low = reference.trace(params, c, prompt + toks, 100, 16, fault)
    chosen = low["chosen"] if fault == "first_blocks" \
        else control.program_choice(cfg, params, toks[-1],
                                    store(low["pooled"]), 128)
    held = held_of(low, chosen)
    samples = [(prompt, toks[:16], low["logprobs"].tolist())]
    walked[0]["chosen_mine"] = plane.step_choice(c, held[0]["picked"], pages,
                                                 128, 40)
    out = plane.compare(limits, c, samples, held, walked)
    assert not out["ok"] and watched in out["failed_by"], out
    # a table that is no table the kernel could read as the reference's
    # choice counts for nothing: a page of another row, a head's offset
    # left out, the blocks out of order, a key too many
    good = table(got["chosen"])
    spoilt = [good.copy() for _ in range(4)]
    spoilt[0][0, 1] = 3
    spoilt[1][1, :4] -= 40
    spoilt[2][1, :2] = good[1, 1::-1]
    spoilt[3][0, 4] += 1
    for bad in spoilt:
        took = plane.step_choice(c, bad, pages, 128, 40)
        assert plane.set_diff(took, got["chosen"]) == 0.5, bad
    np.testing.assert_array_equal(
        plane.step_choice(c, good, pages, 128, 40), got["chosen"])


def test_the_cells_own_rehearsal_passes():
    root = os.path.dirname(HERE)
    got = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seconds", "3"], cwd=root, capture_output=True,
        text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "passed"
