"""``costs_moe.py`` by hand, and the five readers of the MoE cell: on a
hand-made decoded trace with fabricated counters, on the scoped trace
recorded on a TPU (which has no MoE scope), and None where a scope or a
counter is absent (a dense model, a parent without them).

Run by hand: ``python -m pytest benchmark/tests -q``."""

import os

import pytest

from benchmark.lib import costs, costs_moe, harness, xspans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPED = os.path.join(HERE, "tests", "data", "tiny_scoped_tpu.xplane.pb")
READERS = ("moe_route_ms", "moe_experts_ms", "moe_experts_roofline",
           "decode_step_roofline.moe", "expert_load_skew")


def test_costs_of_a_hand_counted_tiny_case():
    c = {"hidden_size": 8, "moe_intermediate_size": 4, "num_experts": 6,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
         "num_hidden_layers": 3, "vocab_size": 32}
    assert costs_moe.expert_bytes(c) == 3 * 8 * 4 * 2 == 192
    assert costs_moe.experts_bytes(c, 5.5) == 5.5 * 192
    # a layer: wq 8*8, wk and wv 8*4 each, wo 8*8, router 8*6; head 32*8
    assert costs_moe.dense_params(c) == 3 * (64 + 32 + 32 + 64 + 48) + 256
    kv = costs.kv_bytes_per_token(c)
    assert kv == 2 * 3 * 1 * 4 * 2
    assert costs_moe.decode_step_bytes(c, 10, 100) == \
        2 * 976 + 10 * 192 + 100 * kv


def test_costs_of_the_published_sizes():
    c = harness.load_config(
        os.path.join(HERE, "configs", "qwen3-30b-a3b.json"))["config"]
    assert costs_moe.expert_bytes(c) == 9437184              # 9.44 MB
    # every expert of the 7 layers hit: 8.46 GB
    assert costs_moe.experts_bytes(c, 128 * 7) == pytest.approx(8.456e9,
                                                                rel=1e-3)
    # attention 18.9M and router 0.26M a layer, head 311M
    assert costs_moe.dense_params(c) == 7 * (18874368 + 262144) + 311164928
    assert costs.kv_bytes_per_token(c) == 14 * 1024
    # the cell's pool: 4700 pages of 64 tokens
    assert 4700 * 64 * costs.kv_bytes_per_token(c) == 4312268800


def _obs(samples, **over):
    config = {"hidden_size": 8, "moe_intermediate_size": 4, "num_experts": 6,
              "num_experts_per_tok": 2, "num_attention_heads": 2,
              "num_key_value_heads": 1, "head_dim": 4,
              "num_hidden_layers": 3, "vocab_size": 32}
    obs = {"config": {"config": config}, "peaks": {"bytes": 1e9},
           "mix": {"engine": {"steps_per_dispatch": 2}},
           "window": (0.0, 10.0),
           "trace": {"window_s": 4.0},
           "kv_tokens_at_end": 1000.0, "tokens_in_window": 100.0,
           "server_info": samples}
    obs.update(over)
    return obs


def _trace():
    """Two whole ``jit_step`` programs of 2 fused steps; under
    ``moe_route`` 30 ns and under ``moe_experts`` 400 ns in all, one
    operation outside every program, one container event."""
    step = "jit(step)/while/body/closed_call/mlp/"
    ops = [("fusion.1", step + "moe_route/top_k", 1000.0, 10.0),
           ("sort.2", step + "moe_route/sort", 1010.0, 20.0),
           ("grouped_matmul.3", step + "moe_experts/jit(grouped_matmul_pallas)"
            "/grouped_matmul/pallas_call", 1100.0, 150.0),
           ("fusion.4", step + "moe_experts/gather", 1300.0, 50.0),
           ("grouped_matmul.3", step + "moe_experts/jit(grouped_matmul_pallas)"
            "/grouped_matmul/pallas_call", 3100.0, 200.0),
           ("while.5", step + "moe_experts/while", 1000.0, 900.0),
           ("fusion.6", "jit(prefill)/mlp/moe_experts/gather", 9000.0, 70.0)]
    modules = [("jit_step(1)", 900.0, 1000.0), ("jit_step(1)", 3000.0, 1000.0),
               ("jit_prefill(2)", 8900.0, 500.0)]
    return {"window": (0.0, 10000.0),
            "device": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


SAMPLES = [
    {"occupancy": 1.0},                                   # an older engine
    {"decode_steps_done": 80, "moe_routed": 1000, "moe_experts_hit": 400,
     "moe_load_max": 300},
    {"decode_steps_done": 880, "moe_routed": 10600, "moe_experts_hit": 4400,
     "moe_load_max": 2700},
]


def test_readers_on_a_decoded_trace_with_fabricated_counters(monkeypatch):
    monkeypatch.setattr(xspans, "load", lambda path=None: _trace())
    read = harness.load_reader
    obs = _obs(SAMPLES)
    c = obs["config"]["config"]
    # 2 programs of 2 fused steps
    assert read("moe_route_ms")(obs) == pytest.approx(1e3 * 30e-9 / 4)
    assert read("moe_experts_ms")(obs) == pytest.approx(1e3 * 400e-9 / 4)
    hit = 4000 / 800                       # experts hit a step, all layers
    assert costs_moe.experts_hit_per_step(obs) == hit
    assert read("moe_experts_roofline")(obs) == pytest.approx(
        100.0 * (hit * 192 / 1e9) / 100e-9)
    kv_mid = 1000.0 - 100.0 * (1.0 - 0.4 / 2.0)
    step_s = 1000e-9 / 2                   # decode_step_ms: whole programs
    assert read("decode_step_ms")(obs) == pytest.approx(1e3 * step_s)
    assert read("decode_step_roofline.moe")(obs) == pytest.approx(
        100.0 * costs_moe.decode_step_bytes(c, hit, kv_mid) / 1e9 / step_s)
    # the busiest expert's 2400 rows of 9600, 6 experts: 1.5 times the mean
    assert read("expert_load_skew")(obs) == pytest.approx(2400 * 6 / 9600)


def test_a_count_of_more_experts_than_the_model_has_is_a_fault():
    """``moe_experts_hit`` is the program's own number: all that can be
    held against it here is the model's size, 6 experts x 3 layers."""
    over = [dict(SAMPLES[1]), dict(SAMPLES[2], moe_experts_hit=400 + 19 * 800)]
    with pytest.raises(ValueError, match="fewer"):
        costs_moe.experts_hit_per_step(_obs(over))
    full = [dict(SAMPLES[1]), dict(SAMPLES[2], moe_experts_hit=400 + 18 * 800)]
    assert costs_moe.experts_hit_per_step(_obs(full)) == 18


def test_readers_return_none_without_scopes_or_counters(monkeypatch):
    read = harness.load_reader
    # the trace recorded on a TPU has attn_core and mlp, no MoE scope; a
    # dense model's engine (or a parent) has no MoE counter
    monkeypatch.setattr(xspans, "load",
                        lambda path=None, _load=xspans.load: _load(SCOPED))
    dense = _obs([{"decode_steps_done": 80}, {"decode_steps_done": 880}])
    for name in READERS:
        assert read(name)(dense) is None, name
    # counters without the scopes (a trace of another program)
    assert read("expert_load_skew")(_obs(SAMPLES)) == pytest.approx(1.5)
    assert read("moe_experts_roofline")(_obs(SAMPLES)) is None
    assert read("decode_step_roofline.moe")(_obs(SAMPLES)) is not None
    # a rehearsal: no peaks, no reduced trace, no xplane at all
    monkeypatch.setattr(xspans, "load", lambda path=None: None)
    for name in READERS[:4]:
        assert read(name)(_obs(SAMPLES, peaks=None, trace=None)) is None, name
