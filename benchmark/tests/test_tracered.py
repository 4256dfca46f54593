"""The trace reduction on a hand-made trace and on a small trace recorded
on a TPU v5e (``data/tiny_tpu.xplane.pb``: a few matmuls under a
``bench/window`` span with a host sleep between them)."""

import os

import pytest

from benchmark.lib import tracered

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert tracered.union_length(iv) == 30
    assert tracered.gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert tracered.gaps(iv, 8, 35) == [(20, 30)]
    assert tracered.gaps([], 0, 5) == [(0, 5)]


def test_op_key_merges_the_layers_instances():
    a = ("%fusion.5560 = f32[526336]{0:T(1024)} fusion(f32[257,8,16,128]"
         "{3,2,1,0:T(8,128)S(1)} %fusion.5559, s32[526336]{0:T(1024)S(1)} "
         "%bitcast.5047), kind=kCustom, calls=%fused_computation.191.clone")
    b = a.replace("5560", "5194").replace("5559", "5193")
    assert tracered.op_key(a) == tracered.op_key(b) == (
        "fusion f32[526336] <- f32[257,8,16,128],s32[526336]")
    k = ("%paged_kv_write_pallas = (bf16[8,1295,64,128]{3,2,1,0}, "
         "bf16[8,1295,64,128]{3,2,1,0}) custom-call(s32[257]{0} %c, "
         "s32[257]{0} %d)")
    assert tracered.op_key(k).startswith("paged_kv_write_pallas bf16[8,1295")
    assert tracered.op_key("plain") == "plain"


def _trace():
    ops = [("%while.3 = s32[] while(s32[] %a)", 100.0, 400.0),   # spans its body
           ("fusion.1", 100.0, 50.0), ("copy.2", 150.0, 50.0),
           ("fusion.1", 400.0, 100.0), ("fusion.1", 900.0, 300.0)]
    mods = [("jit_step(1)", 100.0, 100.0), ("jit_step(1)", 400.0, 100.0),
            ("jit_prefill(2)", 900.0, 300.0)]
    host = [("bench/window", 0.0, 1000.0),          # main thread, sleeping
            ]
    loop = [("$cb_engine.py:1647 _loop_iter", 90.0, 900.0),
            ("PjitFunction(step)", 95.0, 10.0),
            ("$cb_engine.py:2610 _emit_entry", 210.0, 180.0),
            ("$threading.py:300 wait", 520.0, 360.0),
            ("PjitFunction(step)", 395.0, 10.0)]
    return {"device": {"/device:TPU:0": {"XLA Ops": ops,
                                         "XLA Modules": mods}},
            "host": {"main": host, "loop": loop}}


def test_reduce_busy_idle_top_ops_and_gap_labels():
    r = tracered.reduce(_trace())
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: 100-500 (the loop), 900-1000 (clipped at the window's end)
    assert r["busy_s"] == pytest.approx(500e-9)
    assert r["device_ops"][0][0] == "fusion.1"
    assert not any(n.startswith("while") for n, _ in r["device_ops"])
    assert r["device_ops"][0][1] == pytest.approx(250e-9)
    gaps = dict(r["idle_gaps"])
    # 500-900 is under a bare wait, so the enclosing loop iteration names
    # it; 0-100 has no host span over its middle half
    assert gaps["$cb_engine.py:1647 _loop_iter"] == pytest.approx(400e-9)
    assert gaps["no host span"] == pytest.approx(100e-9)
    steps = [d for _p, n, _s, d in r["modules"] if n.startswith("jit_step")]
    assert steps == [pytest.approx(100e-9)] * 2


def test_reduce_refuses_a_trace_without_device_work():
    t = _trace()
    t["device"] = {}
    with pytest.raises(ValueError):
        tracered.reduce(t)


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA, "tiny_tpu.xplane.pb")),
                    reason="no recorded trace")
def test_reduce_a_recorded_tpu_trace():
    r = tracered.reduce(tracered.load(os.path.join(DATA, "tiny_tpu.xplane.pb")))
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]
    assert any(n.startswith("jit_") for _p, n, _s, _d in r["modules"])
