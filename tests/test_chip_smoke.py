"""``chip_smoke.py``: the CPU rehearsal of its legs runs green, and it cannot
be talked into passing without a chip or with a kernel switch set. Plus the
two seams it leans on: where the compile cache goes, and that a kernel
dispatcher on a TPU backend raises instead of rerouting."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
SWITCHES = ("POLYRL_PAGED_ATTN", "POLYRL_KV_WRITE", "POLYRL_GROUPED_ATTN",
            "POLYRL_PEAK_TFLOPS")


def _run_smoke(*args, env_extra=None, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in SWITCHES and k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"   # this sandbox; the script must not care
    env.update(env_extra or {})
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_cpu_rehearsal_of_legs_a_and_b_is_green_but_not_a_pass():
    proc = _run_smoke("--rehearse-cpu")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    out = proc.stdout
    assert "REHEARSAL" in out
    assert "leg A passed" in out and "leg B passed" in out
    assert "leg B depth CUT to 1 of 2 layers" in out
    # on the CPU every dispatcher takes its jnp path, and the table says so
    assert ("kernels: paged_attention=ref, kv_write=scatter, grouped=ref, "
            "train_attention=dense") in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"rehearsal": "passed",
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert "ok" not in last            # cannot be mistaken for a pass


def test_without_a_tpu_it_fails_before_doing_any_work():
    proc = _run_smoke(timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""   # no result, no leg started
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("switch,value", [
    ("POLYRL_KV_WRITE", "scatter"), ("POLYRL_PEAK_TFLOPS", "918")])
def test_refuses_to_start_with_a_kernel_switch_set(switch, value):
    proc = _run_smoke("--rehearse-cpu", env_extra={switch: value},
                      timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert switch in proc.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script is nothing without the program: copied away from the
    repo it exits non-zero and prints no result."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(SMOKE).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, str(lone), "--rehearse-cpu"],
                          env=env, cwd=tmp_path, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# -- where the compile cache goes -------------------------------------------


def test_cache_dir_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    from polyrl_tpu.utils import xla_cache

    monkeypatch.setenv(xla_cache.ENV_VAR, str(tmp_path / "placed"))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    assert xla_cache.configure_compile_cache() == str(tmp_path / "placed")
    assert updates == []               # nothing set in code
    assert os.environ[xla_cache.ENV_VAR] == str(tmp_path / "placed")


def test_cache_dir_defaults_to_the_checkout(monkeypatch):
    from polyrl_tpu.utils import xla_cache

    monkeypatch.delenv(xla_cache.ENV_VAR, raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    first = xla_cache.configure_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", first)]
    assert xla_cache.ENV_VAR not in os.environ   # and the variable stays unset
    # fixed: nothing of this process, this moment or this host in the path
    assert xla_cache.configure_compile_cache() == first
    for part in (str(os.getpid()), "tmp"):
        assert part not in first.replace(REPO, "")


def test_cache_entries_counts_executables(tmp_path):
    from polyrl_tpu.utils.xla_cache import cache_entries

    assert cache_entries(str(tmp_path / "absent")) == 0
    (tmp_path / "jit_f-abc-cache").write_bytes(b"x")
    (tmp_path / "jit_f-abc-atime").write_bytes(b"x")
    assert cache_entries(str(tmp_path)) == 1


# -- dispatchers: platform and shape choose; a TPU failure raises ------------


def _paged_case():
    rng = np.random.default_rng(0)
    hkv, n, ps, d, s = 2, 8, 8, 16, 3
    pools = [jnp.asarray(rng.standard_normal((hkv, n, ps, d)), jnp.float32)
             for _ in range(2)]
    q = jnp.asarray(rng.standard_normal((s, 2 * hkv, d)), jnp.float32)
    table = jnp.asarray([[1, 2], [3, 0], [4, 5]], jnp.int32)
    lens = jnp.asarray([9, 3, 16], jnp.int32)
    upd = jnp.asarray(rng.standard_normal((s, hkv, d)), jnp.float32)
    return pools, q, table, lens, upd


def test_on_cpu_dispatchers_take_their_oracles_and_say_so(monkeypatch):
    from polyrl_tpu.ops import dispatch, flash
    from polyrl_tpu.ops import paged_attention as pa

    for v in SWITCHES:
        monkeypatch.delenv(v, raising=False)
    (kp, vp), q, table, lens, upd = _paged_case()
    dispatch.reset()
    out = pa.paged_attention(q, kp, vp, table, lens)
    np.testing.assert_array_equal(
        out, pa.paged_attention_ref(q, kp, vp, table, lens))
    page, off = jnp.asarray([1, 3, 5], jnp.int32), jnp.asarray([1, 3, 0])
    pa.paged_kv_write(kp, vp, page, off, upd, upd)
    groups = (jnp.asarray([[0, 2]], jnp.int32), jnp.asarray([[1]], jnp.int32),
              jnp.asarray([8], jnp.int32))
    table_g = table.at[2, 0].set(1)
    got = pa.grouped_paged_attention(q, kp, vp, table_g, lens, *groups)
    np.testing.assert_array_equal(got, pa.grouped_paged_attention_ref(
        q, kp, vp, table_g, lens, *groups))
    x = jnp.ones((1, 128, 2, 128), jnp.float32)
    flash.flash_attention_train(x, x, x, jnp.ones((1, 128), jnp.int32))
    assert dispatch.taken() == {
        "paged_attention": ("ref",), "kv_write": ("scatter",),
        "grouped": ("ref",), "train_attention": ("dense",)}


def test_on_tpu_a_kernel_that_fails_raises_instead_of_rerouting(monkeypatch):
    """With the backend reporting "tpu" the dispatchers choose the Pallas
    kernels; one that raises (here: a refused lowering) propagates. No
    probe, no ``except``, no scatter/oracle behind it."""
    from polyrl_tpu.ops import dispatch, flash
    from polyrl_tpu.ops import paged_attention as pa

    for v in SWITCHES:
        monkeypatch.delenv(v, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    class Refused(RuntimeError):
        pass

    def refuse(*_a, **_k):
        raise Refused("Mosaic failed to compile TPU kernel")

    (kp, vp), q, table, lens, upd = _paged_case()
    dispatch.reset()
    monkeypatch.setattr(pa, "paged_kv_write_pallas", refuse)
    monkeypatch.setattr(pa, "grouped_paged_attention_pallas", refuse)
    monkeypatch.setattr(pa, "paged_attention_lib", refuse)
    idx = jnp.zeros((3,), jnp.int32)
    with pytest.raises(Refused):
        pa.paged_kv_write(kp, vp, idx, idx, upd, upd)
    with pytest.raises(Refused):
        pa.grouped_paged_attention(
            q, kp, vp, table, lens, jnp.asarray([[0, 2]], jnp.int32),
            jnp.asarray([[1]], jnp.int32), jnp.asarray([8], jnp.int32))
    with pytest.raises(Refused):
        pa.paged_attention(q, kp, vp, table, lens)
    # the choice was the kernel's, and was recorded as such
    assert dispatch.taken() == {"kv_write": ("pallas",),
                                "grouped": ("pallas",),
                                "paged_attention": ("lib",)}
    # training attention: shapes that tile take flash (and would raise off
    # a real TPU); shapes that do not are dense, and recorded as dense
    assert flash.supports_flash(256, 128)
    assert not flash.supports_flash(250, 128)
    assert not flash.supports_flash(256, 64)
    x = jnp.ones((1, 256, 2, 128), jnp.float32)
    with pytest.raises(Exception):
        flash.flash_attention_train(x, x, x, jnp.ones((1, 256), jnp.int32))
    assert dispatch.taken()["train_attention"] == ("flash",)


def test_no_except_around_a_pallas_call_or_probe():
    src = open(os.path.join(REPO, "polyrl_tpu", "ops",
                            "paged_attention.py")).read()
    assert "except" not in src
    assert "_pallas_kv_write_supported" not in src
