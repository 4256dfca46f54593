"""``costs_sambay.py`` against the numbers of ISSUE 43, by hand and against
the parameter tree the program builds; the preset against the
configuration's file, key for key; the eight readers this cell brings on a
hand-made decoded trace with fabricated counters, and None where a scope,
a counter or a family key is absent (the parent's program, a dense model
under a ``--rehearse-cpu`` walk); the plane walked end to end on a tiny
model of the family with the engine's count of the shared pool's keys
against the client's; and walks in which ``correct`` has to come out
false.

Run by hand: ``python -m pytest benchmark/tests -q``."""

import os
import time

import numpy as np
import pytest

from benchmark.lib import costs, costs_sambay, harness, xspans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = os.path.join(HERE, "tests", "data", "tiny_scoped_tpu.xplane.pb")
READERS = ("ssm_proj_ms", "swa_core_ms", "swa_core_roofline",
           "attn_core_roofline.sambay", "diff_mix_ms", "gmu_ms",
           "decode_step_roofline.sambay")
CELL = "phi-4-mini-flash-reasoning.rollout-long-shared-kv"


def _phi():
    return harness.load_config(os.path.join(
        HERE, "configs", "phi-4-mini-flash-reasoning.json"))


def test_costs_of_the_published_sizes_are_the_issues_numbers():
    c = _phi()["config"]
    assert costs_sambay.is_sambay(c)
    kinds = costs_sambay.kinds(c)
    assert [kinds.count(k) for k in ("ssm", "swa", "full", "gmu", "cross")] \
        == [9, 8, 1, 7, 7]
    assert kinds[16:18] == ["ssm", "full"] and kinds[17] == "full"
    assert round(costs_sambay.mlp_params(c) / 1e6, 1) == 78.6
    assert round(costs_sambay.ssm_params(c) / 1e6, 1) == 41.2
    assert round(costs_sambay.attn_params(c) / 1e6, 1) == 19.7
    assert round(costs_sambay.cross_params(c) / 1e6, 1) == 13.1
    assert round(costs_sambay.gmu_params(c) / 1e6, 1) == 26.2
    # 3.85 B parameters, 7.70 GB in bf16 (the issue's own count, in
    # millions a kind, is 3.851 B; the tree's, to the parameter, is this)
    assert costs_sambay.weight_params(c) == 3_852_562_944
    assert round(costs_sambay.weight_params(c) * 2 / 1e9, 2) == 7.71
    # ONE layer's K and V a token; a slot's rings and states
    assert costs_sambay.paged_bytes_per_token(c) == 5120
    assert costs.kv_bytes_per_token(c) == 32 * 5120     # what it is NOT
    assert costs_sambay.ring_bytes(c) == 20_971_520
    assert costs_sambay.state_bytes(c) == 9 * (327_680 + 30_720) == 3_225_600
    assert costs_sambay.shared_readers(c) == 8
    serve = _phi()["serve"]
    assert serve["kv_pool_bytes"] == 10_986 * 64 * 5120
    # a decode step at 640k cached tokens: the shared pool eight times
    # over is 70% of 37.4 GB, 46 ms at 819 GB/s
    parts = (costs_sambay.attn_core_bytes(c, 640e3),
             costs_sambay.swa_core_bytes(c, 128 * 8 * 512),
             costs_sambay.ssm_core_bytes(c, 128 * 9))
    least = costs_sambay.decode_step_bytes(c, 640e3, 128 * 8 * 512, 128 * 9)
    assert [round(p / 1e9, 2) for p in parts] == [26.21, 2.68, 0.75]
    assert round(least / 1e9, 1) == 37.4
    assert round(parts[0] / least, 2) == 0.70
    assert round(1e3 * least / 819e9, 1) == 45.7


def test_the_programs_tree_has_the_counted_parameters():
    """``deployment`` in the configuration's file: recounted from the tree
    the program builds, and what a token and a slot keep from the
    program's own cache specification."""
    import jax

    from polyrl_tpu.models import cache_spec, decoder

    cfg = decoder.get_config(_phi()["preset"])
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    c = _phi()["config"]

    def count(t):
        return sum(a.size for a in jax.tree_util.tree_leaves(t))

    layers = tree["layers"]
    assert count(tree) == costs_sambay.weight_params(c)
    assert count(layers["ssm"]) == 9 * costs_sambay.ssm_params(c)
    assert count(layers["attn"]) == 9 * costs_sambay.attn_params(c)
    assert count(layers["cross"]) == 7 * costs_sambay.cross_params(c)
    assert count(layers["gmu"]) == 7 * costs_sambay.gmu_params(c)
    assert count(layers["dense"]) == 32 * costs_sambay.mlp_params(c)
    assert "lm_head" not in tree
    assert cache_spec.paged_bytes_per_token(cfg) == \
        costs_sambay.paged_bytes_per_token(c)
    assert cache_spec.slot_bytes(cfg) == costs_sambay.slot_bytes(c) \
        == 24_197_120
    assert [{"ssm_mem": "ssm", "diff": "full"}.get(p.mixer, p.mixer)
            for p in cache_spec.layer_plan(cfg)] == costs_sambay.kinds(c)
    assert cache_spec.ssm_dims(cfg) == (
        costs_sambay.inner(c), costs_sambay.SSM["state"],
        costs_sambay.SSM["conv"],
        c["hidden_size"] // costs_sambay.SSM["rank_divisor"])


def test_the_preset_equals_the_configurations_file():
    """``harness.MODEL_FIELDS`` carries only the dense GQA keys, so the
    family's keys reach the program through the preset: held equal here,
    and the cell's entries in ``BENCHMARK.json`` beside them."""
    from polyrl_tpu.models import decoder

    raw = _phi()
    c = raw["config"]
    cfg = decoder.get_config(raw["preset"], **harness.model_overrides(raw))
    assert cfg == decoder.get_config(raw["preset"])     # nothing overridden
    assert (cfg.mb_per_layer, cfg.sliding_window, cfg.rms_norm_eps) == (
        c["mb_per_layer"], c["sliding_window"], c["layer_norm_eps"])
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
            cfg.num_heads, cfg.num_kv_heads, cfg.vocab_size,
            cfg.max_position_embeddings) == (
        c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"],
        c["num_attention_heads"], c["num_key_value_heads"], c["vocab_size"],
        c["max_position_embeddings"])
    assert cfg.head_dim_ == 64 and "head_dim" not in c
    assert cfg.tie_word_embeddings and not c["lm_head_bias"] \
        and not c["mlp_bias"]
    assert raw["reduced"] == [] and cfg.kept_layers is None
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-reasoning", "rollout-long-shared-kv", 1)
    mine = {m["name"] for m in harness.cell_metrics(bench, CELL, "per_layer")}
    assert set(READERS) <= mine and "attn_core_ms" in mine
    # the readers whose cost is ``costs.py``'s dense arithmetic (a K/V pair
    # in each of the file's 32 layers) do not list the cell
    assert not {"attn_core_roofline", "decode_step_roofline"} & mine


TINY = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 12, "vocab_size": 32, "num_hidden_layers": 8,
        "mb_per_layer": 2, "sliding_window": 4, "tie_word_embeddings": True}


def test_costs_of_a_hand_counted_tiny_case():
    c = TINY
    assert costs_sambay.kinds(c) == ["ssm", "swa", "ssm", "swa", "ssm",
                                     "full", "gmu", "cross"]
    # inner 16, state 16, 4 taps, rank 0 (8 // 16): W_in 8 x 32, taps 64 and
    # a bias of 16, W_x 16 x 32, dt's bias 16, A 256, D 16, W_out 128
    assert costs_sambay.ssm_params(c) == 256 + 64 + 16 + 512 + 0 + 16 \
        + 256 + 16 + 128
    # heads of 2: W_qkv 8 x 16 and 16, W_o 64 and 8, lambdas and norm 12
    assert costs_sambay.attn_params(c) == 128 + 16 + 64 + 8 + 12
    assert costs_sambay.cross_params(c) == 64 + 8 + 64 + 8 + 12
    assert costs_sambay.gmu_params(c) == 256
    assert costs_sambay.paged_bytes_per_token(c) == 2 * 2 * 2 * 2
    assert costs_sambay.ring_bytes(c) == 2 * 4 * 16
    assert costs_sambay.state_bytes(c) == 3 * (16 * 16 * 4 + 3 * 16 * 2)
    got = costs_sambay.decode_step_bytes(c, kv_tokens_read=100,
                                         window_rows=24, rows_x_layers=9)
    assert got == (costs_sambay.dense_params(c) * 2 + 2 * 100 * 16
                   + 24 * 16 + 2 * 9 * 1024 + 2 * 9 * 96)


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
    ops = [("fusion.1", step + "ssm_proj/dot_general", 1000.0, 20.0),
           ("fusion.2", step + "ssm_core/mul", 1100.0, 60.0),
           ("paged_attention.5", step + "swa_core" + kernel, 1200.0, 40.0),
           ("paged_attention.6", step + "attn_core" + kernel, 1300.0, 80.0),
           ("fusion.3", step + "diff_mix/sub", 1400.0, 10.0),
           ("fusion.4", step + "gmu/dot_general", 1450.0, 30.0),
           ("fusion.5", step + "ssm_core/mul", 3100.0, 50.0),
           ("fusion.6", step + "ssm_proj/dot_general", 3200.0, 70.0),
           ("fusion.8", "jit(prefill_extend)/ssm_core/while", 9000.0, 70.0)]
    modules = [("jit_step(1)", 900.0, 1000.0), ("jit_step(1)", 3000.0, 1000.0),
               ("jit_prefill_extend(2)", 8900.0, 500.0)]
    return {"window": (0.0, 10000.0),
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


SAMPLES = [
    {"occupancy": 1.0},                                   # an older engine
    {"decode_steps_done": 80, "ssm_state_rows": 700,
     "shared_kv_rows_read": 5000, "window_rows_read": 900},
    {"decode_steps_done": 880, "ssm_state_rows": 700 + 800 * 12,
     "shared_kv_rows_read": 5000 + 800 * 2 * 950,
     "window_rows_read": 900 + 800 * 32},
]


def test_readers_on_a_decoded_trace_with_fabricated_counters(monkeypatch):
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    read = harness.load_reader
    obs = _obs(SAMPLES)
    c = obs["config"]["config"]
    per = 1e3 * 1e-9 / 4
    assert read("ssm_proj_ms")(obs) == pytest.approx(90 * per)
    assert read("swa_core_ms")(obs) == pytest.approx(40 * per)
    assert read("attn_core_ms")(obs) == pytest.approx(80 * per)
    assert read("diff_mix_ms")(obs) == pytest.approx(10 * per)
    assert read("gmu_ms")(obs) == pytest.approx(30 * per)
    assert costs_sambay.counted_per_step(obs, "ssm_state_rows") == 12.0
    kv_mid = 1000.0 - 100.0 * (1.0 - 0.4 / 2.0)
    assert read("swa_core_roofline")(obs) == pytest.approx(
        100.0 * (32 * 16) / 1e9 / (40e-9 / 4))
    assert read("attn_core_roofline.sambay")(obs) == pytest.approx(
        100.0 * (2 * kv_mid * 16) / 1e9 / (80e-9 / 4))
    # the engine's count of the shared pool's keys against the client's:
    # two readers, 950 keys a step each, 950 tokens at the window's middle
    assert obs["checks"]["shared_kv_rows"] == {
        "program_rows_a_step": 950.0, "client_tokens_mid_window": 950.0,
        "agree": True}
    step_s = 1000e-9 / 2
    assert read("decode_step_roofline.sambay")(obs) == pytest.approx(
        100.0 * costs_sambay.decode_step_bytes(c, kv_mid, 32.0, 12.0) / 1e9
        / step_s)
    off = [dict(SAMPLES[1]), dict(SAMPLES[2],
                                  shared_kv_rows_read=5000 + 800 * 2 * 800)]
    obs = _obs(off)
    read("attn_core_roofline.sambay")(obs)
    assert obs["checks"]["shared_kv_rows"]["agree"] is False


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
    scopeless = ("attn_core_roofline.sambay", "decode_step_roofline.sambay")
    for name in READERS:
        assert read(name)(_obs(plain, config=dense)) is None, name
        assert read(name)(_obs(SAMPLES, config=dense)) is None, name
        if name != "attn_core_roofline.sambay":
            # (the shared pool's share needs the client's count alone)
            assert read(name)(_obs(plain)) is None, name
    # the recorded trace has ``attn_core`` and a whole step, so the two
    # shares that need no scope of this family read it
    for name in scopeless:
        assert read(name)(_obs(SAMPLES)) is not None
    for name in set(READERS) - set(scopeless):
        assert read(name)(_obs(SAMPLES)) is None, name
    # a rehearsal: no peaks, no reduced trace, no xplane at all
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    for name in READERS:
        assert read(name)(_obs(SAMPLES, peaks=None, trace=None)) is None, name


def _tiny_config(correct=None):
    from benchmark.lib import traffic
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("sambay-tiny")
    sizes = {
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "layer_norm_eps": cfg.rms_norm_eps,
        "mb_per_layer": cfg.mb_per_layer,
        "sliding_window": cfg.sliding_window,
        "tie_word_embeddings": cfg.tie_word_embeddings}
    mix = harness.rehearsal(_phi(),
                            traffic.load_mix("rollout-long-shared-kv"))[1]
    per_page = costs_sambay.paged_bytes_per_token(sizes) \
        * mix["engine"]["page_size"]
    config = {"preset": "sambay-tiny", "reference": "sambay_diff",
              "dtype": "float32", "config": sizes,
              "serve": {"kv_pool_bytes": 3400 * per_page},
              "correct": correct or {"logprob_mean_abs_diff_max": 1e-5,
                                     "logprob_max_abs_diff_max": 5e-5,
                                     "state_rel_diff_max": 1e-5,
                                     "state_last_rel_diff_max": 1e-5,
                                     "window_rel_diff_max": 1e-5}}
    return cfg, config, mix


def test_the_sambay_plane_walks_a_tiny_model_of_the_family_end_to_end():
    """``harness.rehearsal`` walks every cell with a dense model, so this
    is the walk of ``planes/rollout_sambay.py`` on a model of its own
    family, here on the CPU in float32: the ``sambay-tiny`` preset through
    the manager with the cell's mix at its rehearsal sizes (chunks held
    first, no prefix cache): the log-probabilities, the last scan's state
    and the first window layer's ring compared, and the engine's count of
    the shared pool's keys beside the client's."""
    import jax

    _cfg, config, mix = _tiny_config()
    assert mix["plane"] == "rollout_sambay" and mix["engine"]["prefill_first"]
    cell = {"name": "sambay-tiny.rehearsal", "chips": 1}
    plane = harness.load_named("planes", mix["plane"])
    assert jax.default_backend() == "cpu"
    out = plane.run(cell, config, mix, harness.Device(1, True), 3141592653,
                    3.0, False, harness.CompileCounter(), time.monotonic())
    ref = out["checks"]["reference"]
    assert ref["ok"] and ref["failed_by"] == [], ref
    assert out["failed"] == 0 and out["checks"]["admitted"] == 4
    assert "level" in out["checks"]["setup_phases_s"]
    assert ref["sequences"] == 2 and ref["positions"] == 2 * 16
    assert all(n > 40 + 16 for n in ref["state_tokens"])
    assert len(ref["state_rel_diffs"][0]) == 4
    assert len(ref["window_rel_diffs"][0]) == 3
    assert out["checks"]["engine_recoveries"] == 0
    assert out["checks"]["kernels"] == {"kv_write": ["scatter"],
                                        "paged_attention": ["ref"]}
    obs = out["observed"]
    obs.update(config=config, mix=mix)
    info = obs["server_info"][-1]
    assert info["ssm_state_rows"] > 0 and info["window_rows_read"] > 0
    # the program's count of the shared pool's keys a step and a reader
    # against the client's tokens of context at the window's middle
    # (to a quarter here: a tiny context doubles inside a 3 s window whose
    # counter samples lie half a second apart, so the two middles differ;
    # a count over all three readers would be 3x off. The chip's agree to
    # 0.1%, and tests/test_sambay.py holds the count exactly)
    agree = costs_sambay.rows_agree(obs)
    assert agree["program_rows_a_step"] == pytest.approx(
        agree["client_tokens_mid_window"], rel=0.25), agree
    assert costs_sambay.counted_per_step(obs, "ssm_state_rows") == \
        pytest.approx(4 * 4, rel=0.05)
    assert costs_sambay.counted_per_step(obs, "window_rows_read") == \
        pytest.approx(3 * 4 * 8, rel=0.05)
    assert harness.verdict(out, True)
    out["checks"]["reference"]["ok"] = False
    assert not harness.verdict(out, True)


@pytest.mark.parametrize("fault", ["state_bf16", "window_plus",
                                   "window_minus", "stale_ring",
                                   "stale_state"])
def test_correct_comes_out_false(fault):
    """The comparison's own controls, on the CPU at the tiny size. What is
    handed in as the system's is the reference's own, so the number that
    watches the fault alone decides: ``state_bf16``: every Mamba state
    rounded to bfloat16 after each token; ``window_plus`` /
    ``window_minus``: a window of one key more or fewer; ``stale_ring`` /
    ``stale_state``: the ring, or the state, of the token BEFORE the last
    (what a chunk boundary off by one leaves)."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder

    _cfg, config, _mix = _tiny_config()
    cfg = decoder.get_config("sambay-tiny", dtype=jnp.float32)
    plane = harness.load_named("planes", "rollout_sambay")
    reference = harness.load_named("references", "sambay_diff")
    params = decoder.init_params(jax.random.PRNGKey(1), cfg)
    c, limits = config["config"], config["correct"]
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 512, 40).tolist()
    toks = rng.integers(1, 512, 24).tolist()

    def as_held(tr, consumed):
        """A reference trace in the form ``CBEngine.recurrent_state``
        hands out: states and rings in layer order, a ring's rows at
        their positions modulo the window."""
        window = cfg.sliding_window
        rings = []
        for rows, first in tr["rings"]:
            ring = np.zeros((window, *rows.shape[1:]), np.float32)
            for i, row in enumerate(rows[-window:]):
                ring[(consumed - min(len(rows), window) + i) % window] = row
            rings.append(ring)
        states = []
        for s, r in zip(tr["states"][:-1], rings):
            states += [s, r]
        return states + [tr["states"][-1]]

    n = len(prompt) + len(toks)
    got = reference.trace(params, c, prompt + toks, 40, 16)
    samples = [(prompt, toks[:16], got["logprobs"].tolist())]
    held = [{"answer": toks, "states": as_held(got, n)}]
    walked = plane.walk(reference, c, params, samples, held)
    sound = plane.compare(limits, samples, held, walked)
    assert sound["ok"] and sound["failed_by"] == []
    assert sound["state_rel_diff"] == sound["window_rel_diff"] == 0.0
    if fault.startswith("stale"):
        before = reference.trace(params, c, prompt + toks[:-1], 40, 16)
        old = as_held(before, n - 1)
        mixed = [o if (o.ndim == 3) == (fault == "stale_ring") else s
                 for s, o in zip(held[0]["states"], old)]
        bad = plane.compare(limits, samples,
                            [{"answer": toks, "states": mixed}], walked)
        watched = (["window_rel_diff"] if fault == "stale_ring"
                   else ["state_rel_diff", "state_last_rel_diff"])
        assert not bad["ok"] and bad["failed_by"] == watched
        assert all(bad[k] > 0.01 for k in watched)
        return
    low = reference.trace(params, c, prompt + toks, 40, 16, control=fault)
    mine = as_held(low, n) if fault == "state_bf16" else None
    if mine is None:
        # a window of another size: its rows in the form compare takes
        bad = plane.compare(limits, samples, held, walked)
        bad_rel = [plane.ring_rel(a, b)
                   for a, b in zip(low["rings"], got["rings"])]
        assert all(r > 0.1 for r in bad_rel)
        assert not bad_rel[0] <= limits["window_rel_diff_max"]
        assert bad["ok"]
        return
    bad = plane.compare(limits, samples, [{"answer": toks, "states": mine}],
                        walked)
    assert not bad["ok"] and "state_rel_diff" in bad["failed_by"]
    assert "window_rel_diff" not in bad["failed_by"]
    assert bad["state_rel_diff"] > 1e-3 and bad["window_rel_diff"] < 1e-2


@pytest.mark.parametrize("seed", [0, 1])
def test_the_slow_channels_tell_a_bfloat16_state_apart(seed):
    """Why ``state_rel_diff`` is taken over the channels that step least:
    against the float32 reference, the program in bfloat16 (a float32
    state from bfloat16 inputs) reads LESS there than over the whole state
    (its inputs' errors average out over a long memory), and the reference
    with a bfloat16 state reads MORE (a rounding a token adds up over it):
    the two stand further apart, at the tiny model as on the chip
    (PERF.md section 4)."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder, hybrid

    _cfg, config, _mix = _tiny_config()
    plane = harness.load_named("planes", "rollout_sambay")
    reference = harness.load_named("references", "sambay_diff")
    cfg = decoder.get_config("sambay-tiny", dtype=jnp.bfloat16)
    params = decoder.init_params(jax.random.PRNGKey(seed), cfg)
    n = 1536            # past the longest memory, 1 / 0.001 tokens
    tokens = np.random.default_rng(seed).integers(1, 512, n)
    _x, states, _kept = hybrid.run_sequence(
        params, cfg, params["embed"][jnp.asarray(tokens)][None],
        jnp.arange(n)[None], jnp.ones((1, n), bool))
    mine = np.asarray(states[0][0][0], np.float32).T          # [I, N]
    sound = reference.trace(params, config["config"], tokens.tolist(),
                            n - 8, 8)
    low = reference.trace(params, config["config"], tokens.tolist(), n - 8,
                          8, "state_bf16")["states"][0]
    rel = plane.hybrid.rel
    whole = float(rel(mine, sound["states"][0])), \
        float(rel(low, sound["states"][0]))
    slow = plane.slow_state_rel(mine, sound), plane.slow_state_rel(low, sound)
    assert len(sound["slow"][0]) == mine.shape[0] // 4
    assert slow[0] < whole[0] < whole[1] < slow[1]
    assert slow[1] / slow[0] > 3 * whole[1] / whole[0]


@pytest.mark.parametrize("control,watched", [
    ("state_bf16", "state_rel_diff"), ("window_plus", "window_rel_diff"),
    ("window_minus", "window_rel_diff"), ("low", "logprob_mean_abs_diff")])
def test_the_on_chip_controls_fail_by_the_limit_that_watches_them(control,
                                                                  watched):
    """``tests/control_sambay_on_chip.py``'s patch of the plane, here on
    the tiny model with the reference's own numbers as the system's: the
    altered reference in the program's place reads false by the watched
    limit alone (the limits on what lies downstream of a window held
    loose: at a window of 8 one key is an eighth of a head), and the sound comparison beside it
    stays true."""
    import importlib.util
    import types

    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder

    spec = importlib.util.spec_from_file_location(
        "control_sambay_on_chip",
        os.path.join(HERE, "tests", "control_sambay_on_chip.py"))
    control_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control_mod)
    real = harness.load_named("planes", "rollout_sambay")
    plane = types.SimpleNamespace(walk=real.walk, compare=real.compare,
                                  hybrid=real.hybrid, ring_rel=real.ring_rel,
                                  slow_state_rel=real.slow_state_rel)
    control_mod.in_the_programs_place(plane, [control])
    _cfg, config, _mix = _tiny_config()
    cfg = decoder.get_config("sambay-tiny", dtype=jnp.float32)
    reference = harness.load_named("references", "sambay_diff")
    params = decoder.init_params(jax.random.PRNGKey(2), cfg)
    c = config["config"]
    limits = dict(config["correct"], logprob_max_abs_diff_max=1.0)
    # a window of 8 keys that is one off moves the layers above it, and a
    # bfloat16 state in every scan moves the last scan's inputs too
    limits["state_last_rel_diff_max"] = 1.0
    if watched != "logprob_mean_abs_diff":
        limits["logprob_mean_abs_diff_max"] = 1.0
    if watched != "state_rel_diff":
        limits["state_rel_diff_max"] = 1.0
    if control == "low":    # int8 weights and rows move everything
        limits["window_rel_diff_max"] = 1.0
    rng = np.random.default_rng(1)
    prompt, toks = (rng.integers(1, 512, n).tolist() for n in (40, 24))
    got = reference.trace(params, c, prompt + toks, 40, 16)
    # the engine's rows as the reference has them: states [I, N] and rings
    # with token t at row t % window, in layer order
    window, n = cfg.sliding_window, 64
    rows = []
    for s, (ring, first) in zip(got["states"], got["rings"]):
        held = np.zeros((window, *ring.shape[1:]), np.float32)
        for i, row in enumerate(ring):
            held[(first + i) % window] = row
        rows += [s, held]
    rows.append(got["states"][-1])
    samples = [(prompt, toks[:16], got["logprobs"].tolist())]
    held = [{"answer": toks, "states": rows}]
    assert first + len(ring) == n
    walked = plane.walk(reference, c, params, samples, held)
    out = plane.compare(limits, samples, held, walked)
    assert out["sound"]["ok"] and not out["ok"]
    assert list(out["controls"]) == [control]
    assert out["controls"][control]["failed_by"] == [watched] \
        == out["failed_by"]
