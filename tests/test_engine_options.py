"""The engine's options are declared once (``polyrl_tpu/engine_options.py``)
and every entry point reaches every one of them: the config section, the
``serve`` command line, ``create_server`` and ``CBEngine`` itself. Builds
no engine except where said."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu import config as cfg_lib
from polyrl_tpu.engine_options import (OPTION_NAMES, EngineOptions,
                                       options_of)
from polyrl_tpu.rollout import serve

# a value other than the default for every option, valid together with the
# other options' defaults
NON_DEFAULT = {
    "max_slots": 8, "page_size": 128, "max_seq_len": 4096, "num_pages": 77,
    "prompt_buckets": (256, 512), "steps_per_dispatch": 4,
    "pipeline_depth": 2, "prefill_chunk": 128, "prefill_first": True,
    "prefix_pages_floor": 4, "spec_tokens": 2,
    "spec_rounds": 3, "salvage_partials": False, "admit_wave": 3,
    "admit_reorder_window": 0, "group_share": False,
    "decode_group_share": False, "group_preref_ttl_s": 5.5,
    "kv_ledger": False, "kv_cold_after_dispatches": 17, "kv_spill": False,
    "kv_spill_host_gb": 0.5, "kv_spill_high_watermark": 0.95,
    "kv_spill_low_watermark": 0.5, "loop_profile": False,
}


def _as_override(name, value) -> str:
    text = (",".join(map(str, value)) if isinstance(value, tuple)
            else str(value).lower())
    return f"rollout.{name}={text}"


def _as_flags(name, value) -> list[str]:
    flag = name.replace("_", "-")
    if isinstance(value, bool):
        return [f"--{flag}" if value else f"--no-{flag}"]
    if isinstance(value, tuple):
        return [f"--{flag}", *map(str, value)]
    return [f"--{flag}", str(value)]


@pytest.mark.parametrize(
    "field", dataclasses.fields(EngineOptions), ids=lambda f: f.name)
def test_every_option_is_reached_from_the_config_and_from_serve(field):
    value = NON_DEFAULT[field.name]
    assert value != field.default
    want = {**dataclasses.asdict(EngineOptions()), field.name: value}
    cfg = cfg_lib.load_config(overrides=[_as_override(field.name, value)])
    assert cfg.rollout.engine_options() == want
    args = serve.build_parser().parse_args(_as_flags(field.name, value))
    assert options_of(args) == want


def test_the_three_sets_of_defaults_are_one():
    defaults = dataclasses.asdict(EngineOptions())
    assert tuple(defaults) == OPTION_NAMES
    assert set(NON_DEFAULT) == set(OPTION_NAMES)
    assert cfg_lib.RolloutSection().engine_options() == defaults
    assert options_of(serve.build_parser().parse_args([])) == defaults
    assert defaults["max_seq_len"] == 16384
    assert defaults["pipeline_depth"] == 16


def test_an_unknown_option_is_a_type_error_at_both_doors():
    from polyrl_tpu.rollout.cb_engine import CBEngine

    with pytest.raises(TypeError, match="max_slot"):
        CBEngine(None, None, max_slot=4)
    with pytest.raises(TypeError, match="max_slot"):
        serve.create_server("tiny", max_slot=4)
    # and a value the options refuse is refused before any engine exists
    with pytest.raises(ValueError, match="prefill_chunk"):
        CBEngine(None, None, page_size=8, prompt_buckets=(16,),
                 prefill_chunk=12)


def test_colocated_train_engine_gets_every_config_option():
    """``train.py`` builds its colocated engine from
    ``rollout.engine_options()``: ``rollout.spec_tokens`` (which the parent
    dropped in colocated mode), and the keys that had no config entry."""
    from polyrl_tpu import train
    from polyrl_tpu.models import decoder
    from polyrl_tpu.utils.tokenizer import ByteTokenizer

    cfg = cfg_lib.load_config(overrides=[
        "model.dtype=float32", "rollout.max_slots=2", "rollout.page_size=8",
        "rollout.max_seq_len=64", "rollout.prompt_buckets=16",
        "rollout.num_pages=17", "rollout.spec_tokens=2",
        "rollout.spec_rounds=3", "rollout.steps_per_dispatch=2",
        "rollout.pipeline_depth=3"])
    mcfg = decoder.get_config("tiny", dtype=jnp.float32)
    params = decoder.init_params(jax.random.PRNGKey(0), mcfg)
    eng = train._build_rollout(cfg, mcfg, params, ByteTokenizer(), [])
    try:
        assert (eng.spec_tokens, eng.spec_rounds) == (2, 3)
        assert (eng.steps_per_dispatch, eng.pipeline_depth) == (2, 3)
        assert (eng.num_pages, eng.max_seq_len) == (17, 64)
    finally:
        eng.stop()


def test_the_environment_cannot_reroute_a_dispatcher(monkeypatch):
    """``POLYRL_PAGED_ATTN=ref`` used to send a TPU run to the jnp oracle;
    the dispatcher reads the platform alone."""
    from polyrl_tpu.ops import dispatch
    from polyrl_tpu.ops import paged_attention as pa

    monkeypatch.setenv("POLYRL_PAGED_ATTN", "ref")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the chip's kernel cannot run here; the oracle stands in for it
    monkeypatch.setattr(
        pa, "paged_attention_pallas",
        lambda *a: pa.paged_attention_ref(*a))
    rng = np.random.default_rng(0)
    kp, vp = (jnp.asarray(rng.standard_normal((2, 8, 8, 16)), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(rng.standard_normal((3, 4, 16)), jnp.float32)
    table = jnp.asarray([[1, 2], [3, 0], [4, 5]], jnp.int32)
    lens = jnp.asarray([9, 3, 16], jnp.int32)
    dispatch.reset()
    pa.paged_attention(q, kp, vp, table, lens)
    assert dispatch.taken() == {"paged_attention": ("lib",)}
