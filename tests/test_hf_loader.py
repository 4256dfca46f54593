"""HF checkpoint loading: logits parity against transformers itself.

The strongest possible correctness check for the model stack: build a tiny
randomly-initialized HF model (llama and qwen3 architectures), save it as
safetensors, load it through ``hf_loader`` into the decoder pytree, and
compare full-sequence logits against the torch reference forward."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import decoder
from polyrl_tpu.models.hf_loader import config_from_hf, load_hf_params

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


def _save_tiny_hf(tmp_path, arch: str):
    common = dict(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=False,
        attention_bias=False,
    )
    if arch == "qwen3":
        hf_cfg = transformers.Qwen3Config(**common)
    elif arch == "qwen2":
        common.pop("head_dim")
        common.pop("attention_bias")  # qwen2 has qkv bias unconditionally
        hf_cfg = transformers.Qwen2Config(**common)
    else:
        common.pop("head_dim")
        hf_cfg = transformers.LlamaConfig(**common)
    torch.manual_seed(0)
    model = transformers.AutoModelForCausalLM.from_config(hf_cfg)
    model = model.eval()
    if arch == "qwen2":
        # HF zero-inits biases; randomize so the bias path is actually
        # exercised numerically, not just structurally
        with torch.no_grad():
            for layer in model.model.layers:
                for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                             layer.self_attn.v_proj):
                    proj.bias.normal_(0.0, 0.1)
    out_dir = tmp_path / arch
    model.save_pretrained(out_dir, safe_serialization=True)
    return model, str(out_dir)


@pytest.mark.parametrize("arch", ["llama", "qwen3", "qwen2"])
def test_hf_logits_parity(tmp_path, arch):
    model, ckpt = _save_tiny_hf(tmp_path, arch)
    cfg = config_from_hf(ckpt, dtype=jnp.float32)
    assert cfg.num_layers == 2 and cfg.num_kv_heads == 2
    assert cfg.use_qk_norm == (arch == "qwen3")
    assert cfg.attention_bias == (arch == "qwen2")
    params = load_hf_params(ckpt, cfg)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)
    with torch.no_grad():
        want = model(torch.from_numpy(ids).long()).logits.numpy()

    positions = np.broadcast_to(np.arange(12, dtype=np.int32), (2, 12))
    mask = np.ones((2, 12), np.float32)
    got, _ = decoder.forward(params, cfg, jnp.asarray(ids),
                             jnp.asarray(positions), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_hf_shape_mismatch_raises(tmp_path):
    _, ckpt = _save_tiny_hf(tmp_path, "llama")
    bad_cfg = decoder.get_config("tiny", dtype=jnp.float32)  # wrong shapes
    with pytest.raises((ValueError, KeyError)):
        load_hf_params(ckpt, bad_cfg)


def test_config_from_hf_llama3_rope(tmp_path):
    cfg_json = {
        "vocab_size": 100, "hidden_size": 16, "intermediate_size": 32,
        "num_hidden_layers": 1, "num_attention_heads": 2,
        "num_key_value_heads": 1, "rope_theta": 500000.0,
        "model_type": "llama", "tie_word_embeddings": False,
        "rope_scaling": {"rope_type": "llama3", "factor": 8.0,
                         "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                         "original_max_position_embeddings": 8192},
    }
    d = tmp_path / "l3"
    d.mkdir()
    (d / "config.json").write_text(json.dumps(cfg_json))
    cfg = config_from_hf(str(d))
    assert cfg.rope_scaling is not None and cfg.rope_scaling.factor == 8.0


def test_train_entry_builds_from_hf_checkpoint(tmp_path):
    """train.py's model plane accepts model.hf_path and returns pretrained
    (non-random-init) params with the checkpoint's architecture."""
    from polyrl_tpu import train as train_mod
    from polyrl_tpu.config import load_config

    _, ckpt = _save_tiny_hf(tmp_path, "llama")
    cfg = load_config(None, [f"model.hf_path={ckpt}", "model.dtype=float32"])
    mcfg, params = train_mod._build_model(cfg)
    assert mcfg.vocab_size == 128 and mcfg.num_layers == 2
    # pretrained embed, not the seed-0 random init
    rand = decoder.init_params(jax.random.PRNGKey(cfg.trainer.seed), mcfg)
    assert not np.allclose(np.asarray(params["embed"]),
                           np.asarray(rand["embed"]))


# -- the zaya family (models/hybrid.py's CCA decoder) -----------------------


def test_zaya_config_is_the_published_preset():
    """``config_from_hf`` on ZAYA1-8B's published config.json (the
    benchmark's file holds every key of it) gives the ``zaya1-8b`` preset,
    but for the depth the file cuts."""
    import dataclasses
    import json
    import os

    from polyrl_tpu.models import hf_loader

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "zaya1-8b.json")) as f:
        hf = json.load(f)
    assert hf["model_type"] == "zaya"
    got = hf_loader.zaya_config(hf)
    want = decoder.get_config("zaya1-8b-depth12")
    assert got == dataclasses.replace(want, kept_layers=None)
    whole = hf_loader.zaya_config({**hf, **hf["published"]})
    assert whole == decoder.get_config("zaya1-8b")
    with pytest.raises(NotImplementedError, match="sliding window"):
        hf_loader.zaya_config({**hf, "sliding_window": 4096})


def test_zaya_checkpoint_is_refused_until_its_key_map_exists(tmp_path):
    """A ``zaya`` checkpoint's config.json is read (``config_from_hf``),
    its tensors are not: ``load_hf_params`` refuses by name what it has no
    key map for, before it opens a shard."""
    import json
    import os

    from polyrl_tpu.models import hf_loader

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "zaya1-8b.json")) as f:
        hf = json.load(f)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = hf_loader.config_from_hf(str(tmp_path))
    assert cfg.cca_time0 == 2 and cfg.router_hidden_size == 256
    with pytest.raises(NotImplementedError, match="no key map for a zaya"):
        hf_loader.load_hf_params(str(tmp_path))


# -- the SambaY family (models/hybrid.py; phi4flash) ------------------------


def _phi4flash_keys() -> dict:
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        return json.load(f)


def test_phi4flash_config_is_the_published_preset():
    """``phi4flash_config`` on Phi-4-mini-flash-reasoning's published
    config.json (the benchmark's file holds every key of it) gives the
    preset, whole; a bias the decoder has not written is refused."""
    from polyrl_tpu.models import hf_loader

    hf = _phi4flash_keys()
    assert hf["model_type"] == "phi4flash" and hf["reduced"] == []
    assert hf_loader.phi4flash_config(hf) == \
        decoder.get_config("phi-4-mini-flash-reasoning")
    with pytest.raises(NotImplementedError, match="mlp_bias"):
        hf_loader.phi4flash_config({**hf, "mlp_bias": True})


def test_phi4flash_checkpoint_is_refused_until_its_key_map_exists(tmp_path):
    import json

    from polyrl_tpu.models import hf_loader

    (tmp_path / "config.json").write_text(json.dumps(_phi4flash_keys()))
    cfg = hf_loader.config_from_hf(str(tmp_path))
    assert cfg.mb_per_layer == 2 and cfg.sliding_window == 512
    with pytest.raises(NotImplementedError,
                       match="no key map for a phi4flash"):
        hf_loader.load_hf_params(str(tmp_path))


# -- the laguna family (models/mixers/gqa.py) --------------------------------


def _laguna_keys() -> dict:
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-xs.2.json")) as f:
        return json.load(f)


def test_laguna_config_is_the_published_preset():
    """``laguna_config`` on Laguna-XS.2's published config.json (the
    benchmark's file holds every key of it, the depth, the experts and the
    per-layer lists cut and the published ones under ``published``) gives
    the ``laguna-xs.2`` preset, and the share cut from it is the
    benchmark's; what the decoder has not written is refused."""
    from polyrl_tpu.models import hf_loader

    hf = _laguna_keys()
    assert hf["model_type"] == "laguna"
    whole = hf_loader.laguna_config({**hf, **hf["published"]})
    assert whole == decoder.get_config("laguna-xs.2")
    assert decoder.cut_to_share(
        whole, hf["kept_layers"], hf["chips_sharing_a_layer"],
        vocabulary_shares=1) == decoder.get_config("laguna-xs.2-share8")
    full, window = (r for _kind, r in whole.rope_parameters)
    assert full.scaling.rope_type == "yarn" and window.scaling is None
    assert full.scaling.attention_factor == 1.4158883083359672
    assert (full.partial_rotary_factor, window.partial_rotary_factor) == \
        (0.5, 1.0)
    with pytest.raises(NotImplementedError, match="attention_bias"):
        hf_loader.laguna_config({**hf, "attention_bias": True})
    with pytest.raises(NotImplementedError, match="mlp_layer_types"):
        hf_loader.laguna_config(
            {**hf, "mlp_layer_types": ["sparse", "dense"] + ["sparse"] * 7})


def test_laguna_checkpoint_is_refused_until_its_key_map_exists(tmp_path):
    import json

    from polyrl_tpu.models import hf_loader

    (tmp_path / "config.json").write_text(json.dumps(_laguna_keys()))
    cfg = hf_loader.config_from_hf(str(tmp_path))
    assert cfg.layer_types[:2] == ("full_attention", "sliding_attention")
    assert cfg.sliding_window == 512 and cfg.attn_head_gate
    with pytest.raises(NotImplementedError, match="no key map for a laguna"):
        hf_loader.load_hf_params(str(tmp_path))


# -- the minicpm_sala family (sparse and lightning layers) -------------------


def test_minicpm_sala_config_is_the_published_preset(tmp_path):
    """``minicpm_sala_config`` on the benchmark's file (the catalog's keys,
    cut to published layers 9-20) gives the ``minicpm-sala`` preset, on
    the published keys alone the whole model; a checkpoint's config.json
    is read, its tensors are refused by name until a key map exists."""
    import json
    import os

    from polyrl_tpu.models import hf_loader

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "minicpm-sala.json")) as f:
        hf = json.load(f)
    assert hf["model_type"] == "minicpm_sala"
    assert hf_loader.minicpm_sala_config(hf) == decoder.get_config(
        "minicpm-sala")
    whole = {**hf, "num_hidden_layers": 32,
             "mixer_types": hf["published_mixer_types"]}
    for key in ("published_num_hidden_layers", "published_layers_kept",
                "published_mixer_types"):
        del whole[key]
    assert hf_loader.minicpm_sala_config(whole) == decoder.get_config(
        "minicpm-sala", num_layers=32, kept_layers=None)
    with pytest.raises(NotImplementedError, match="rope in its softmax"):
        hf_loader.minicpm_sala_config({**hf, "attn_use_rope": True})
    with pytest.raises(ValueError, match="no run of the published"):
        hf_loader.minicpm_sala_config({**hf, "published_layers_kept": [8, 19]})
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = hf_loader.config_from_hf(str(tmp_path))
    assert cfg.mixer_types and cfg.kept_layers == tuple(range(9, 21))
    with pytest.raises(NotImplementedError,
                       match="no key map for a minicpm_sala"):
        hf_loader.load_hf_params(str(tmp_path))


# -- the nemotron_h family (layers of one sublayer) ---------------------------


def test_nemotron_h_config_is_the_published_preset(tmp_path):
    """``nemotron_h_config`` on the benchmark's file (the catalog's keys
    with one chip's share of eight: 16 experts held, an eighth of the
    vocabulary) gives the ``nemotron-3-nano-30b-a3b-share8`` preset, on the
    published keys alone the whole model; the file holds every number of
    the catalog's row but the two it lists as ``reduced``; a checkpoint's
    config.json is read, its tensors are refused by name until a key map
    exists."""
    import json
    import os

    from polyrl_tpu.models import hf_loader

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        hf = json.load(f)
    assert hf["model_type"] == "nemotron_h"
    assert hf["reduced"] == ["n_routed_experts", "vocab_size"]
    assert hf_loader.nemotron_h_config(hf) == decoder.get_config(
        "nemotron-3-nano-30b-a3b-share8")
    whole = {**hf, **hf["published"]}
    for key in ("published", "experts_held"):
        del whole[key]
    assert hf_loader.nemotron_h_config(whole) == decoder.get_config(
        "nemotron-3-nano-30b-a3b")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert hf["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if hf[k] != v} \
            == set(hf["reduced"])
        assert {k: row["config"][k] for k in hf["reduced"]} == hf["published"]
    with pytest.raises(NotImplementedError, match=r"characters \['-'\]"):
        hf_loader.nemotron_h_config(
            {**hf, "hybrid_override_pattern": "-" + hf[
                "hybrid_override_pattern"][1:]})
    with pytest.raises(NotImplementedError, match="relu2"):
        hf_loader.nemotron_h_config({**hf, "mlp_hidden_act": "silu"})
    with pytest.raises(NotImplementedError, match="mamba_proj_bias"):
        hf_loader.nemotron_h_config({**hf, "mamba_proj_bias": True})
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = hf_loader.config_from_hf(str(tmp_path))
    assert cfg.hybrid_override_pattern and cfg.experts_held == (0, 16)
    with pytest.raises(NotImplementedError,
                       match="no key map for a nemotron_h"):
        hf_loader.load_hf_params(str(tmp_path))
