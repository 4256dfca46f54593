"""Stable names on the device trace: every jitted program has a function
name of its own (its ``XLA Modules`` name, ``jit_<name>``), the forward
pass carries the scopes a trace reduction reads device time by, each
``pallas_call`` we own has a ``name=``, and the scopes are metadata only
(the decode step compiles to the same HLO without them)."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyrl_tpu.models import decoder
from polyrl_tpu.rollout.cb_engine import CBEngine

SCOPES = ("attn_qkv", "attn_core", "attn_out", "mlp", "head")
# the dense decoder and one preset a family of mixers (``FAMILIES`` below)
STEP_PRESETS = ("tiny", "moe-tiny", "hybrid-tiny", "mla-moe-tiny", "cca-tiny",
                "sambay-tiny", "mixed-tiny", "ouro-tiny", "minicpm-sala-tiny",
                "nemotron-h-tiny")
ENGINE_PROGRAMS = {
    "step": lambda e: e._get_step(False, 2),
    "spec_step": lambda e: e._get_spec_step(False, 3, 2),
    "prefill_one": lambda e: e._get_prefill(16, False),
    "prefill_batch": lambda e: e._get_prefill_batch(16, 2, False),
    "prefill_extend": lambda e: e._get_prefill_extend(16, 1),
    "prefill_suffix": lambda e: e._get_prefill_suffix(16, 1, False),
    "prefill_suffix_batch":
        lambda e: e._get_prefill_suffix_batch(16, 2, 1, False),
}


@pytest.fixture(scope="module")
def tiny():
    cfg = decoder.get_config("tiny")
    return cfg, decoder.init_params(jax.random.PRNGKey(0), cfg)


def _engine(tiny, **kw):
    cfg, params = tiny
    return CBEngine(cfg, params, max_slots=4, page_size=8, max_seq_len=64,
                    prompt_buckets=(16,), num_pages=32,
                    steps_per_dispatch=2, **kw)


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _lower_step(eng):
    eng._ensure_dev_state()
    st = eng._dev_state
    args = (eng.params, eng._pools[0], eng._pools[1], eng._rng,
            st["page_table"], st["seq_lens"], st["last_tokens"],
            st["n_generated"], st["budgets"], st["active"], st["temps"],
            st["top_ps"], st["top_ks"], st["stop_table"])
    return eng._get_step(False, 2).__wrapped__.lower(*_shapes(args))


def _lower_prefill(eng):
    eng._ensure_dev_state()
    state = {k: eng._dev_state[k] for k in eng._STATE_KEYS}
    packed = jnp.asarray(eng._sink_pad_row(16))
    return eng._get_prefill(16, False).__wrapped__.lower(
        *_shapes((eng.params, eng._pools[0], eng._pools[1], packed,
                  eng._rng)), **_shapes(state))


def _lower_actor_update(tiny):
    from polyrl_tpu.trainer.actor import ActorConfig, StreamActor

    cfg, params = tiny
    actor = StreamActor(cfg, ActorConfig(lr=1e-3, remat=False),
                        jax.tree_util.tree_map(jnp.copy, params))
    b, t, r = 2, 8, 4
    batch = {
        "input_ids": np.ones((b, t), np.int32),
        "positions": np.tile(np.arange(t, dtype=np.int32), (b, 1)),
        "attention_mask": np.ones((b, t), np.float32),
        "responses": np.ones((b, r), np.int32),
        "response_mask": np.ones((b, r), np.float32),
        "advantages": np.ones((b, r), np.float32),
        "old_log_probs": np.zeros((b, r), np.float32),
    }
    fn = actor._build_update(True)
    return fn.lower(actor.params, actor.opt_state, actor.accum_grads,
                    batch, jnp.asarray(1.0, jnp.float32))


@pytest.mark.parametrize("program", ["step", "prefill_one", "actor_update"])
def test_lowered_text_carries_the_program_name_and_every_scope(tiny, program):
    if program == "actor_update":
        lowered = _lower_actor_update(tiny)
        scopes = SCOPES
    else:
        eng = _engine(tiny)
        lowered = _lower_step(eng) if program == "step" \
            else _lower_prefill(eng)
        scopes = SCOPES + ("sample",)
    text = lowered.as_text(debug_info=True)
    assert f"module @jit_{program} " in text
    for scope in scopes:
        # a scope is a component of the operations' name path
        # (inside a scan's body the path starts at the body; under a
        # gradient the component reads ``jvp(<scope>)``)
        assert re.search(rf'loc\("(?:[^"]*[/(])?{scope}\)*/', text), \
            (program, scope)


def test_the_step_that_samples_in_the_head_keeps_both_scopes(tiny,
                                                            monkeypatch):
    """``head_sample_ms`` reads scopes ``head`` and ``sample`` inside
    ``jit_step``: the fused step has its kernel under ``head`` (with the
    final norm) and the key split, the stop check and the ``where``s under
    ``sample``."""
    monkeypatch.setattr(decoder, "samples_in_head",
                        lambda cfg, params, use_filters, many: True)
    text = _lower_step(_engine(tiny)).as_text(debug_info=True)
    assert "module @jit_step " in text
    for scope in SCOPES + ("sample",):
        assert re.search(rf'loc\("(?:[^"]*[/(])?{scope}\)*/', text), scope
    assert re.search(r'loc\("[^"]*head/[^"]*head_sample', text)
    assert not re.search(r'loc\("[^"]*sample/[^"]*head_sample', text)


def test_every_engine_program_has_a_name_of_its_own(tiny):
    """``decode_step_ms`` matches ``jit_step``: only the fused decode
    program may start with ``step``, and no two programs share a name."""
    eng = _engine(tiny, spec_tokens=2)
    names = {kind: fn(eng).__wrapped__.__name__
             for kind, fn in ENGINE_PROGRAMS.items()}
    assert names == {k: k for k in ENGINE_PROGRAMS}
    assert [n for n in names.values() if n.startswith("step")] == ["step"]


def test_trainer_programs_are_named_by_role(tiny):
    from polyrl_tpu.trainer.actor import (ActorConfig, ReferencePolicy,
                                          StreamActor)
    from polyrl_tpu.trainer.critic import (CriticConfig, StreamCritic,
                                           init_critic_params)

    cfg, params = tiny
    actor = StreamActor(cfg, ActorConfig(lr=1e-3, remat=False),
                        jax.tree_util.tree_map(jnp.copy, params))
    assert actor._build_update(False).__wrapped__.__name__ == "actor_update"
    ref = ReferencePolicy(cfg, params)
    assert ref._fn.__wrapped__.__name__ == "ref_logprob"
    critic = StreamCritic(cfg, CriticConfig(),
                          init_critic_params(jax.random.PRNGKey(1), cfg))
    assert critic._build_update(False).__wrapped__.__name__ == \
        "critic_update"
    b, t, r = 2, 8, 4
    critic.compute_values({
        "input_ids": np.ones((b, t), np.int32),
        "positions": np.tile(np.arange(t, dtype=np.int32), (b, 1)),
        "attention_mask": np.ones((b, t), np.float32),
        "responses": np.ones((b, r), np.int32)})
    assert critic._value_fn.__wrapped__.__name__ == "critic_value"
    actor.compute_log_prob({
        "input_ids": np.ones((b, t), np.int32),
        "positions": np.tile(np.arange(t, dtype=np.int32), (b, 1)),
        "attention_mask": np.ones((b, t), np.float32),
        "responses": np.ones((b, r), np.int32),
        "response_mask": np.ones((b, r), np.float32)})
    assert actor._logprob_fns[True].__wrapped__.__name__ == "actor_logprob"


@pytest.mark.parametrize("preset", STEP_PRESETS)
def test_decode_step_hlo_is_the_same_without_the_scopes(preset, monkeypatch):
    """Scopes are metadata: with ``jax.named_scope`` made a no-op the
    decode step of every family lowers to the same StableHLO and compiles
    to the same HLO, metadata aside — so no device number can move."""
    with_scopes = _lower_step(_family_engine(preset))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _lower_step(_family_engine(preset))
    assert '"head/' in with_scopes.as_text(debug_info=True)
    assert '"head/' not in without.as_text(debug_info=True)
    assert with_scopes.as_text() == without.as_text()

    def hlo(lowered):
        text = lowered.compile().as_text()
        text = re.sub(r',?\s*metadata=\{[^{}]*\}', "", text)
        return re.sub(r"\n\s*(FileNames|FunctionNames|FileLocations|"
                      r"StackFrames)\n(\s*\d+ .*\n)*", "\n", text)

    a, b = hlo(with_scopes), hlo(without)
    assert "op_name" not in a
    assert a == b


def test_each_pallas_call_we_own_has_a_name():
    from polyrl_tpu.ops import paged_attention as pa

    def names(fn, *args):
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append(eqn.params["name"])
                for v in eqn.params.values():
                    inner = getattr(v, "jaxpr", v)
                    if hasattr(inner, "eqns"):
                        walk(inner)

        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return found

    from polyrl_tpu.ops import fused_sample as fs

    assert names(
        lambda *a: fs.head_sample_pallas(*a, interpret=True),
        jnp.zeros((2, 16)), jnp.zeros((16, 256)), jax.random.PRNGKey(0),
        jnp.ones((2,))) == ["head_sample"]
    hkv, n, ps, d, s = 2, 8, 8, 128, 2
    pool = jnp.zeros((hkv, n, ps, d), jnp.float32)
    q = jnp.zeros((s, 4, d), jnp.float32)
    table = jnp.zeros((s, 4), jnp.int32)
    lens = jnp.ones((s,), jnp.int32)
    assert names(lambda *a: pa.paged_attention_pallas(*a, interpret=True),
                 q, pool, pool, table, lens) == ["paged_attention"]
    upd = jnp.zeros((s, hkv, d), jnp.float32)
    assert names(lambda *a: pa.paged_kv_write_pallas(*a, interpret=True),
                 pool, pool, lens, lens, upd, upd) == ["paged_kv_write"]
    g_slots = jnp.asarray([[0, 1]], jnp.int32)
    g_pages = jnp.asarray([[1]], jnp.int32)
    g_lens = jnp.asarray([ps], jnp.int32)
    assert names(
        lambda *a: pa.grouped_paged_attention_pallas(*a, interpret=True),
        q, pool, pool, table, lens + ps, g_slots, g_pages, g_lens) == [
            "grouped_prefix", "grouped_suffix"]
    from polyrl_tpu.ops import kda_state

    # the event ``kda_state`` in a device trace is what says the one-pass
    # state update ran (it notes no key in ops/dispatch.py)
    rows = jnp.zeros((s, 4, d), jnp.float32)
    assert names(
        lambda *a: kda_state.kda_state_pallas(*a, interpret=True),
        jnp.zeros((3, 4, d, d), jnp.float32), rows, rows, rows, rows,
        jnp.zeros((s, 4), jnp.float32)) == ["kda_state"]
    from polyrl_tpu.ops import ssd_state

    # likewise ``ssd_state`` for a Mamba-2 layer's (``ssd_kernel_steps``)
    cols = jnp.zeros((s, 2, 16), jnp.float32)
    assert names(
        lambda *a: ssd_state.ssd_state_pallas(*a, interpret=True),
        jnp.zeros((3, 2, 16, d), jnp.float32), rows[:, :2], rows[:, :2],
        cols, cols) == ["ssd_state"]
    from polyrl_tpu.ops import mla_proj

    # and ``mla_absorb`` / ``mla_unabsorb`` that a step's MLA layers
    # multiplied ``wkv_b`` in the stack (``mla_proj_kernel_steps`` beside)
    stack = jnp.zeros((2, d, 4 * 2 * d), jnp.float32)
    assert names(
        lambda *a: mla_proj.absorb(*a, layer=1, interpret=True),
        rows, stack) == ["mla_absorb"]
    assert names(
        lambda *a: mla_proj.unabsorb(*a, layer=1, interpret=True),
        rows, stack) == ["mla_unabsorb"]


# -- the families of mixers (models/mixers) -----------------------------------

# preset -> (the benchmark cell of its family, the load a decode step counts
# by its ``server_info`` names, as the accepted cells' programs lay it out)
_MOE = ("moe_routed", "moe_experts_hit", "moe_load_max")
_HYBRID = _MOE + ("moe_choices", "kda_state_rows", "mla_rows_read")
FAMILIES = {
    "moe-tiny": ("qwen3-30b-a3b.rollout-wide", _MOE),
    "hybrid-tiny": ("ling-3.0-flash.rollout-long-wide", _HYBRID),
    "mla-moe-tiny": ("dots.vlm1.rollout-long-latent", _HYBRID),
    "cca-tiny": ("zaya1-8b.rollout-wide-cca", _HYBRID + ("cca_tail_rows",)),
    "sambay-tiny": ("phi-4-mini-flash-reasoning.rollout-long-shared-kv",
                    ("ssm_state_rows", "shared_kv_rows_read",
                     "window_rows_read")),
    "mixed-tiny": ("laguna-xs.2.rollout-long-mixed",
                   _MOE + ("moe_choices", "paged_rows_read",
                           "kda_state_rows", "mla_rows_read",
                           "window_rows_read")),
    "ouro-tiny": ("ouro-2.6b.rollout-short-looped",
                  ("paged_rows_read", "ut_passes", "kv_pass_rows_read")),
    "minicpm-sala-tiny": ("minicpm-sala.rollout-long-sparse-linear",
                          ("sparse_pages_read", "sparse_pooled_scored",
                           "sparse_dense_rows", "lightning_state_rows")),
    "nemotron-h-tiny": ("nemotron-3-nano-30b-a3b.rollout-wide-ssd",
                        _MOE + ("moe_choices", "paged_rows_read",
                                "kda_state_rows", "mla_rows_read",
                                "ssd_state_rows")),
}


def _scopes_read(cell: str) -> set:
    """The scopes that ``BENCHMARK.json``'s per-layer metrics of ``cell``
    read device time by (``xspans.scope_seconds`` in their files)."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        metrics = json.load(f)["per_layer"]
    found = set()
    for m in metrics:
        if cell in m["workloads"] and m["source"] == "device_trace":
            with open(os.path.join(root, "benchmark", "layer_metrics",
                                   m["name"] + ".py")) as f:
                found.update(re.findall(
                    r'scope_seconds\([^"]*"(\w+)",\s*"jit_step"\)', f.read()))
    return found


def _family_engine(preset: str):
    cfg = decoder.get_config(preset, dtype=jnp.float32)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    return CBEngine(cfg, params, max_slots=4, page_size=8, max_seq_len=64,
                    prompt_buckets=(16,), num_pages=32, steps_per_dispatch=2,
                    kv_cache_dtype=jnp.float32)


# -- the step's scope declaration (models/scopes.py) --------------------------

# what a lowered line is besides an operation the device runs: constants,
# a region's or a function's terminator, a call (its callee's operations
# are walked with the call's path before theirs) and the loops themselves
_NOT_OPERATIONS = ("stablehlo.constant", "stablehlo.return", "return",
                   "func.return", "call", "func.call", "stablehlo.while",
                   "stablehlo.case")
# the only operations of a step under no scope: a ``lax.scan``'s own (its
# counter, the slice of what it scans over, the stacking of its outputs),
# which JAX emits directly in the loop's condition and body, outside the
# body's function
_SCANS_OWN = re.compile(
    r"(?:/while/(?:cond|body))?/"
    r"(?:lt|add|broadcast_in_dim|dynamic_update_slice|dynamic_slice|squeeze)")


def _step_paths(text: str) -> list:
    """(operation, scope path) of every operation of a lowered program
    that carries a named location. ``as_text(debug_info=True)`` names an
    operation by the scopes open around it INSIDE its function; the
    scopes around a private function's call stand at the call, so the
    functions are walked from ``main`` down, a call's path before its
    callee's, once a distinct path."""
    locs = dict(re.findall(r'^(#loc\d*) = loc\((.*)\)$', text, re.M))

    def name_of(ref):
        m = re.match(r'"([^"]*)"\(', locs.get(ref, ""))
        return m.group(1) if m else None

    funcs, cur = {}, None
    for line in text.splitlines():
        m = re.match(r'\s*func\.func (?:public|private) @([\w.]+)\(', line)
        if m:
            cur = funcs.setdefault(m.group(1), [])
            continue
        m = re.search(r'loc\((#loc\d*)\)\s*$', line)
        if cur is None or m is None or line.lstrip().startswith("}"):
            continue
        op = re.match(r'\s*(?:%[\w:#]+(?:, %[\w:#]+)* = )?"?([\w.]+)"?', line)
        callee = re.search(r'call @([\w.]+)\(', line)
        cur.append((op.group(1), name_of(m.group(1)),
                    callee.group(1) if callee else None))
    out, seen = [], set()

    def walk(fn, ctx):
        if (fn, ctx) in seen:
            return
        seen.add((fn, ctx))
        for op, name, callee in funcs[fn]:
            path = ctx + ("/" + name if name else "")
            if callee is not None:
                walk(callee, path)
            elif name is not None and op not in _NOT_OPERATIONS:
                out.append((op, path))

    walk("main", "")
    return out


@pytest.mark.parametrize("fused", [False, True], ids=["sampler", "fused"])
@pytest.mark.parametrize("preset", STEP_PRESETS)
def test_every_operation_of_a_step_lies_under_one_declared_leaf(
        preset, fused, monkeypatch):
    """``models/scopes.py``'s invariant, a family: every operation of the
    lowered decode step that carries a location lies under exactly ONE
    declared leaf scope, inside no container but those the declaration
    lets that leaf sit in; the exceptions are a scan's own operations.
    ``benchmark/lib/account.py`` partitions a traced step by these
    leaves, and what is left under none is what XLA made itself."""
    from polyrl_tpu.models.scopes import CONTAINER_SCOPES, LEAF_SCOPES

    if fused:
        monkeypatch.setattr(decoder, "samples_in_head",
                            lambda cfg, params, use_filters, many: True)
    text = _lower_step(_family_engine(preset)).as_text(debug_info=True)
    found = _step_paths(text)
    assert len(found) > 500
    seen = set()
    for op, path in found:
        parts = re.findall(r"[\w.]+", path)
        leaves = {c for c in parts if c in LEAF_SCOPES}
        seen |= leaves
        if not leaves:
            # what follows the innermost function's call, or the program
            own = path.rsplit("/closed_call", 1)[-1].removeprefix(
                "/jit(step)")
            assert _SCANS_OWN.fullmatch(own), (preset, op, path)
            continue
        assert len(leaves) == 1, (preset, op, path)
        for box in (c for c in parts if c in CONTAINER_SCOPES):
            assert leaves <= set(CONTAINER_SCOPES[box]), (preset, op, path)
    assert {"embed", "glue", "head", "sample"} <= seen
    if preset == "ouro-tiny":
        assert "ut_norm" in seen


def test_the_declaration_holds_every_scope_the_models_open():
    """One declaration: every scope that ``polyrl_tpu/models`` and the
    engine's step open by name is a declared leaf or container, every
    declared leaf is opened somewhere (none is stale), a container's
    leaves are leaves, and a mixer's record names declared leaves for its
    pages and its slot (``Mixer.__post_init__`` refuses another)."""
    import glob
    import os

    from polyrl_tpu.models import scopes
    from polyrl_tpu.models.mixers import MIXERS
    from polyrl_tpu.models.mixers.base import Mixer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    opened = set()
    for path in (glob.glob(os.path.join(root, "polyrl_tpu", "models",
                                        "**", "*.py"), recursive=True)
                 + [os.path.join(root, "polyrl_tpu", "rollout",
                                 "cb_engine.py")]):
        with open(path) as f:
            opened.update(re.findall(r'named_scope\("(\w+)"\)', f.read()))
    declared = set(scopes.LEAF_SCOPES) | set(scopes.CONTAINER_SCOPES)
    assert opened == declared, opened ^ declared
    assert len(set(scopes.LEAF_SCOPES)) == len(scopes.LEAF_SCOPES)
    assert not set(scopes.LEAF_SCOPES) & set(scopes.CONTAINER_SCOPES)
    for leaves in scopes.CONTAINER_SCOPES.values():
        assert set(leaves) <= set(scopes.LEAF_SCOPES)
    for rec in MIXERS.values():
        for name in (rec.pages_scope, rec.slot_scope):
            assert name == "" or name in scopes.LEAF_SCOPES, (rec.name, name)
        assert rec.pages_scope or rec.slot_scope or rec.name in (
            "gmu", "cross")
    with pytest.raises(ValueError, match="not declared"):
        Mixer("other", cache=None, slot_scope="other_core")
    assert set(STEP_PRESETS[1:]) == set(FAMILIES)


@pytest.mark.parametrize("preset", list(FAMILIES))
def test_a_familys_programs_carry_the_scopes_and_the_load_its_cell_reads(
        preset):
    """What an edit of a mixer must keep: the decode step and the prefill
    chunk carry every scope that the per-layer metrics of the family's
    benchmark cell read (a lost scope shows as a metric of None on the
    chip and nowhere else), and the step's load vector has the entries and
    the order that ``server_info`` names it by."""
    from polyrl_tpu.models import hybrid
    from polyrl_tpu.obs.engine_profile import CUMULATIVE_KEYS

    cell, load = FAMILIES[preset]
    scopes = _scopes_read(cell)
    assert {"head", "sample"} < scopes and len(scopes) >= 4
    eng = _family_engine(preset)
    cfg = eng.cfg
    assert hybrid.load_names(cfg) == load
    assert hybrid.load_width(cfg) == len(load)
    # where ``server_info`` has them: a routed model's in ``moe_info``,
    # the rest among the profiler's cumulative counters
    if cfg.num_experts:
        assert tuple(eng.moe_info()) == tuple(
            n for n in load if n not in CUMULATIVE_KEYS)
    else:
        assert eng.moe_info() == {} and set(load) <= set(CUMULATIVE_KEYS)
    step = _lower_step(eng).as_text(debug_info=True)
    prefill = _lower_prefill(eng).as_text(debug_info=True)
    assert "module @jit_step " in step
    for text in (step, prefill):
        for scope in scopes:
            assert re.search(rf'loc\("(?:[^"]*[/(])?{scope}\)*/', text), \
                (preset, scope, text is step)
