"""Decoders whose layers differ in kind (``cache_spec.layer_plan``): what
threads the layers together and is the same for every kind. A kind of
mixer is a module under ``models/mixers/`` behind one record
(``mixers.base.Mixer``), and every loop here looks the layer's record up
and calls it: nothing in this file knows a kind by its name. The families
that run through it: the Ling-3.0 / Ring hybrid (``bailing_hybrid``: KDA
and MLA layers, a routed MLP with a sigmoid router behind leading dense
layers; ``mixers/kda.py``, ``mixers/mla.py``); DeepSeek-V3's decoder
(dots.vlm1 / dots.llm1: MLA in every layer, the same routed MLP); ZAYA1's
(``zaya``: CCA in every layer, ``mixers/cca.py``, a top-1 routed MLP whose
router is an MLP on a latent that each layer hands to the next, both
sublayers' residuals scaled; every one of its layers is alike, so the
stacked scan would fit its trainer's forward: it lives here because the
slot state and a second tensor carried beside the residual stream are what
this file's unrolled loop threads); the SambaY family (``phi4flash``:
Mamba-1 scans, window attention, one full-attention layer whose K/V the
cross-decoder's layers read, gated memory units, LayerNorm with a bias;
``mixers/ssm.py``, ``mixers/diff.py``); and Laguna's (``laguna``: rope'd
softmax GQA in every layer, full layers in pages and window layers in
rings with a head count and a rope a kind, a softmax router's scaled
weights beside a shared expert; ``mixers/gqa.py``); and a looped model's
(``ouro``: ONE stack of ``gqa`` layers with a norm after each sublayer too,
run ``ut_steps`` times a token with the final norm between passes: the
passes are a scan of the program around the layers below,
``_pass_indices``, and a pass finds its own pages by an offset,
``cache_spec.pass_offset``); and MiniCPM-SALA's (``minicpm_sala``:
block-sparse softmax attention whose pages carry pooled keys beside the
K/V pair, among linear-attention layers with a float32 state in the slot,
under muP's scalings of the embedding, of each sublayer's output and of
the head's input; ``mixers/sparse.py``, ``mixers/lightning.py``); and
Nemotron-H's (``nemotron_h``: every layer ONE norm, ONE sublayer and ONE
residual, a Mamba-2 mixer, attention without positions or routed experts
of two matrices under ``relu(.)^2`` by a published pattern:
``cache_spec.one_sublayer``; a layer without a mixer or without an MLP
skips that half here, nothing stands in for it; ``mixers/mamba2.py``).

One block a kind, parameters stacked per kind::

    params["exit_gate"] = {w [d, 1], b [1]}            a looped model's
    params["layers"] = {
      "attn_norm", "mlp_norm": [L, d]                  every layer
      "norm": [L, d]                                   in their place where
                                                       a layer is ONE sublayer
      "attn_post_norm", "mlp_post_norm": [L, d]        ``sandwich_norm``
      <a mixer's stack>: its module's docstring, with what its family
                         keeps beside the stacks ([L, ..]: residual scales,
                         norm biases)
      "dense": {w_gate w_up [Ld, d, f], w_down [Ld, f, d]}
      "moe":   {router [Ls, d, E_all], router_bias [Ls, E_all] float32
                (the sigmoid router's and the router MLP's),
                we_gate we_up [Ls, E_held, d, fe], we_down [Ls, E_held, fe, d],
                ws_gate ws_up [Ls, d, fs], ws_down [Ls, fs, d]}
               (``mlp_hidden_act`` relu2: no we_gate and no ws_gate, an
                expert is ``relu(x we_up)^2 we_down``)
               (with ``router_hidden_size`` R: router_down [Ls, d, R],
                router_gamma [Ls] float32, router_norm [Ls, R],
                router_w1 router_w2 [Ls, R, R], router [Ls, R, E_all])
    }"""

from __future__ import annotations

import collections
import dataclasses

import jax
import jax.numpy as jnp

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.blocks import (EXPERT_KEYS, _gather_slabs_kv, _head,
                                      _latent_route, _moe_mlp,
                                      _scatter_pages_kv, _scatter_slabs,
                                      experts_in_kernel, norm)
from polyrl_tpu.models.mixers import MIXERS
from polyrl_tpu.models.mixers.base import Chunk, Kept, Load, SlotRows, Step
from polyrl_tpu.models.quant import mm

# what a router's latent keeps of the layer before's (init_params)
ROUTER_GAMMA = 0.5


# -- parameters -----------------------------------------------------------------


def _counts(cfg) -> collections.Counter:
    """Layers a stack of mixer weights and a kind of MLP holds."""
    plan = cache_spec.layer_plan(cfg)
    return collections.Counter(
        [MIXERS[p.mixer].stack for p in plan if p.mixer]
        + [p.mlp for p in plan if p.mlp])


def kind_index(cfg) -> list[tuple[int, int]]:
    """For each layer: (its index among the layers of its mixer's stack,
    its index among the layers of its MLP's kind); 0 for a half the
    layer lacks."""
    seen: dict = {}
    out = []
    for p in cache_spec.layer_plan(cfg):
        mixer = MIXERS[p.mixer].stack if p.mixer else None
        i, j = seen.get(mixer, 0), seen.get(p.mlp, 0)
        for kind, at in ((mixer, i), (p.mlp, j)):
            if kind is not None:
                seen[kind] = at + 1
        out.append((i, j))
    return out


class _Draw:
    """What ``init_params`` hands a mixer's ``init`` to draw with: every
    draw takes the next key of ``fold_in``'s counter, so a leaf's key is
    its place in the order of the draws."""
    std = 0.02

    def __init__(self, rng: jax.Array, cfg):
        self._rng, self._dtype, self._drawn = rng, cfg.dtype, 0

    def _key(self):
        self._drawn += 1
        return jax.random.fold_in(self._rng, self._drawn)

    def normal(self, *shape, dtype=None, scale=1.0):
        return (jax.random.normal(self._key(), shape, jnp.float32)
                * (self.std * scale)).astype(dtype or self._dtype)

    def uniform(self, *shape):
        return jax.random.uniform(self._key(), shape)

    def ones(self, *shape):
        return jnp.ones(shape, self._dtype)


def init_params(rng: jax.Array, cfg) -> dict:
    """Normal(0.02) matrices, unit norms, as ``decoder.init_params``; each
    stack of mixer weights as its module's ``init`` draws it, in the
    table's order."""
    n = _counts(cfg)
    d, L = cfg.hidden_size, cfg.num_layers
    draw = _Draw(rng, cfg)
    norm, ones = draw.normal, draw.ones
    layers: dict = ({"norm": ones(L, d)} if cache_spec.one_sublayer(cfg)
                    else {"attn_norm": ones(L, d), "mlp_norm": ones(L, d)})
    if cfg.sandwich_norm:
        layers.update(attn_post_norm=ones(L, d), mlp_post_norm=ones(L, d))
    for rec in MIXERS.values():
        if rec.init is not None and n[rec.stack] and rec.stack not in layers:
            layers.update(rec.init(cfg, n[rec.stack], draw))
    if n["dense"]:
        f = cfg.intermediate_size
        layers["dense"] = {"w_gate": norm(n["dense"], d, f),
                           "w_up": norm(n["dense"], d, f),
                           "w_down": norm(n["dense"], f, d)}
    if n["moe"]:
        s, fe = n["moe"], cfg.moe_intermediate_size
        fs = cfg.moe_shared_expert_intermediate_size
        held = cache_spec.experts_held(cfg)[1]
        r = cfg.router_hidden_size
        # the router MLP's matrices by their fan-in: at 0.02 three layers
        # shrink a normed latent to logits a hundredth wide
        fan = {"scale": r ** -0.5 / draw.std} if r else {}
        router = ({"router_down": norm(s, d, r),
                   "router_gamma": jnp.full((s,), ROUTER_GAMMA, jnp.float32),
                   "router_norm": ones(s, r),
                   "router_w1": norm(s, r, r, **fan),
                   "router_w2": norm(s, r, r, **fan),
                   "router": norm(s, r, cfg.num_experts, **fan)} if r
                  else {"router": norm(s, d, cfg.num_experts)})
        # the bias that enters the choice: the sigmoid router's and the
        # router MLP's; the softmax router has none
        if r or cfg.scoring_func == "sigmoid":
            router["router_bias"] = norm(s, cfg.num_experts,
                                         dtype=jnp.float32)
        # ``relu2``: an expert is two matrices, no gate
        gated = cfg.mlp_hidden_act != "relu2"
        layers["moe"] = {
            **router,
            **({"we_gate": norm(s, held, d, fe)} if gated else {}),
            "we_up": norm(s, held, d, fe), "we_down": norm(s, held, fe, d),
        }
        if fs:
            if gated:
                layers["moe"]["ws_gate"] = norm(s, d, fs)
            layers["moe"].update(ws_up=norm(s, d, fs), ws_down=norm(s, fs, d))
    params = {"embed": norm(cfg.vocab_size, d), "final_norm": ones(d),
              "layers": layers}
    if "attn_norm_bias" in layers:
        # a model of LayerNorms has one at the end too
        params["final_norm_bias"] = jnp.zeros((d,), cfg.dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm(d, cfg.vocab_size)
    if cache_spec.passes(cfg) > 1:
        # lambda_t = sigmoid(h_t w + b), a pass's share of the exit; it
        # enters an output only where a token leaves the loop early, which
        # no path here does (``hf_loader.ouro_config``)
        params["exit_gate"] = {"w": norm(d, 1),
                               "b": jnp.zeros((1,), cfg.dtype)}
    return params


def param_specs(cfg) -> dict:
    """PartitionSpec tree matching ``init_params``: matmul weights shard
    like the dense decoder's (fsdp x tp), the experts over ``ep``, the
    small per-head vectors replicate."""
    from jax.sharding import PartitionSpec as P

    from polyrl_tpu.parallel.mesh import EP, FSDP, TP

    col, row, rep2 = P(None, FSDP, TP), P(None, TP, FSDP), P(None, None)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    rows = {"w_down", "ws_down"}.union(*(m.row_parallel
                                         for m in MIXERS.values()))
    whole = {"router"}.union(*(m.replicated for m in MIXERS.values()))

    def spec(path, leaf):
        name = path[-1].key
        if name in ("we_gate", "we_up"):
            return P(None, EP, FSDP, TP)
        if name == "we_down":
            return P(None, EP, TP, FSDP)
        if name == "embed":
            return P(TP, FSDP)
        if name == "lm_head":
            return P(FSDP, TP)
        if leaf.ndim == 3 and name.startswith(("w", "router")) \
                and name != "router_bias":
            if name in whole:
                return P(None, FSDP, None)
            return row if name in rows else col
        return P(*([None] * leaf.ndim)) if leaf.ndim != 2 else rep2

    return jax.tree_util.tree_map_with_path(spec, shapes)



# -- the layer loop -------------------------------------------------------------


def _embed(cfg, params, ids):
    """The tokens' rows of the embedding, times ``scale_emb`` (muP)."""
    x = params["embed"][ids]
    if cfg.scale_emb == 1.0:
        return x
    return (x.astype(jnp.float32) * cfg.scale_emb).astype(x.dtype)


def _branch(cfg, out):
    """A sublayer's output as it joins the residual stream: under muP
    (``scale_depth`` > 0) times ``scale_depth / sqrt(depth)``, the depth
    the published model's and not a cut's."""
    if not cfg.scale_depth:
        return out
    scale = cfg.scale_depth / cache_spec.published_depth(cfg) ** 0.5
    return (out.astype(jnp.float32) * scale).astype(out.dtype)


def _residual(x, out, res):
    """A sublayer's residual: ``x + out``, or with ``res`` [4, d] = (a_r,
    b_r, a_o, b_o) the scaled ``(a_r x + b_r) + (a_o out + b_o)``."""
    if res is None:
        return x + out
    a_r, b_r, a_o, b_o = res.astype(jnp.float32)
    return (a_r * x.astype(jnp.float32) + b_r
            + a_o * out.astype(jnp.float32) + b_o).astype(x.dtype)


def _post(cfg, layers: dict, name: str, out, l: int):
    """A sublayer's output under its sandwich norm ``name``, as it is for
    a model without one."""
    if name not in layers:
        return out
    return norm(layers, name, out, cfg.rms_norm_eps, l)


def _layer_params(cfg, layers: dict, l: int) -> tuple[dict, dict]:
    """(mixer weights, MLP weights) of layer ``l``: slices of the stacks
    of its kinds, None for a half the layer lacks; the routed experts stay
    whole stacks (``moe_mm`` takes the layer's index among the sparse
    layers)."""
    plan = cache_spec.layer_plan(cfg)[l]
    i, j = kind_index(cfg)[l]
    mixer = plan.mixer and jax.tree_util.tree_map(
        lambda a: a[i], layers[MIXERS[plan.mixer].stack])
    mlp = plan.mlp and {k: v if k in EXPERT_KEYS
                        else jax.tree_util.tree_map(lambda a: a[j], v)
                        for k, v in layers[plan.mlp].items()}
    return mixer, mlp


def _norm_names(cfg) -> tuple[str, str]:
    """The norms before a layer's mixer and before its MLP: the one
    ``norm`` of a layer of one sublayer."""
    return (("norm", "norm") if cache_spec.one_sublayer(cfg)
            else ("attn_norm", "mlp_norm"))


def _res(layers, name: str, l: int):
    """Layer ``l``'s residual scales, None for a model without them."""
    return layers[name][l] if name in layers else None


def router_carry(cfg, lead: tuple):
    """What the first layer's router is handed: zeros [*lead, R] float32,
    None for a router without a carried latent."""
    r = cfg.router_hidden_size
    return jnp.zeros((*lead, r), jnp.float32) if r else None


def _mlp(cfg, x, layers, l, mlp_lp, valid, carry=None):
    """The MLP sublayer of layer ``l`` with its residual: (x, the routed
    block's load or None, the router's latent for the next layer or
    None). ``carry`` [..., R]: the layer before's latent of each token."""
    plan = cache_spec.layer_plan(cfg)[l]
    j = kind_index(cfg)[l][1]
    with jax.named_scope("mlp"):
        with jax.named_scope("glue"):
            res = _res(layers, "mlp_res", l)
            h = norm(layers, _norm_names(cfg)[1], x, cfg.rms_norm_eps, l)
        if plan.mlp == "dense":
            with jax.named_scope("mlp_dense"):
                gate = jax.nn.silu(
                    mm(h, mlp_lp["w_gate"]).astype(jnp.float32))
                out = mm(gate.astype(h.dtype) * mm(h, mlp_lp["w_up"]),
                         mlp_lp["w_down"])
            with jax.named_scope("glue"):
                out = _post(cfg, layers, "mlp_post_norm", out, l)
                return _residual(x, _branch(cfg, out), res), None, carry
        shape = h.shape
        with jax.named_scope("glue"):
            rows = h.reshape(-1, shape[-1])
            v = valid.reshape(-1) if valid is not None else None
        route = None
        if carry is not None:
            with jax.named_scope("moe_route"):
                *route, latent = _latent_route(
                    cfg, rows, mlp_lp, carry.reshape(rows.shape[0], -1))
                carry = latent.reshape(carry.shape)
        out, load = _moe_mlp(cfg, rows, mlp_lp, v, j, route)
        with jax.named_scope("glue"):
            out = _post(cfg, layers, "mlp_post_norm", out.reshape(shape), l)
            return _residual(x, _branch(cfg, out), res), load, carry


def _form(p, form: str):
    """The ``sequence`` or ``step`` form of layer ``p``'s mixer."""
    fn = getattr(MIXERS[p.mixer], form)
    if fn is None:
        raise NotImplementedError(
            f"mixer {p.mixer!r} beside other kinds of layer")
    return fn


def _keepers(cfg) -> tuple[list, list]:
    """(The records of the layers that keep pages of their own, of the
    layers that keep a slot), each in the order of ``make_pools``' tuple."""
    pages, slots = [], []
    for p in cache_spec.layer_plan(cfg):
        c = cache_spec.layer_cache(cfg, p)
        if cache_spec.paged_part(c) is not None:
            pages.append(MIXERS[p.mixer])
        if cache_spec.slot_part(c) is not None:
            slots.append(MIXERS[p.mixer])
    return pages, slots


def _pass_indices(cfg):
    """What a looped model's loop over its passes scans over: the passes'
    indices as an ARRAY. Not ``fori_loop``'s own counter: on a v5e the
    program that read ``t > 0`` (``_next_pass``) off that counter put the
    first pass under the final norm too, in the step and in the chunk, and
    left the reference by 0.64 where this one leaves it by 0.008; the CPU
    runs both alike (my chip runs, PR 51: ``PERF.md`` section 6)."""
    with jax.named_scope("glue"):
        return jnp.arange(cache_spec.passes(cfg), dtype=jnp.int32)


def _pass_offset(cfg, paged, t):
    """What pass ``t`` adds to a page's number in the pools ``paged``."""
    return cache_spec.pass_offset(cfg, jax.tree_util.tree_leaves(paged)[0], t)


def _next_pass(cfg, params, x, t):
    """What pass ``t`` of a looped model starts from: the pass before's
    output under the model's final norm, the embedding for the first. (The
    last pass's output meets that norm in the head.)"""
    with jax.named_scope("ut_norm"):
        return jnp.where(t > 0, norm(params, "final_norm", x,
                                     cfg.rms_norm_eps), x)


def run_sequence(params, cfg, x, positions, valid, states=None,
                 prefix=None, remat: bool = False):
    """``run_plan`` for every pass of the model (``cache_spec.passes``):
    the plan once for all but a looped model, whose passes are a scan over
    ``run_plan`` with the final norm between them, with neither a state
    nor a prefix; what its chunk keeps in pages comes stacked, a pass a
    leading row."""
    if cache_spec.passes(cfg) == 1:
        return run_plan(params, cfg, x, positions, valid, states, prefix,
                        remat)
    if states is not None or prefix is not None:
        raise NotImplementedError("a looped model's passes over a state or "
                                  "a prefix: hybrid.prefill runs them")

    def one_pass(x, t):
        x = _next_pass(cfg, params, x, t)
        with jax.named_scope("ut_pass"):
            x, _states, kept = run_plan(params, cfg, x, positions, valid,
                                        remat=remat)
        return x, kept

    x, kept = jax.lax.scan(one_pass, x, _pass_indices(cfg))
    return x, [], kept


def run_plan(params, cfg, x, positions, valid, states=None,
             prefix=None, remat: bool = False):
    """Every layer over whole (chunks of) sequences ``x`` [B, T, d] with
    right padding (``valid`` [B, T]): the trainer's forward and the
    engine's prefill. ``states``: for each layer that keeps a slot, in
    order, its rows at the chunk's start as its ``read_slot`` gives them,
    zeros when None. ``prefix``: for each layer that keeps pages, in
    order, (what the tokens before the chunk keep there [B, Tp, ..]:
    latent rows or a (k, v) pair; how many of them are real [B]), none
    when None. Returns (x, new states, what this chunk keeps in pages a
    paged layer). What layers hand to later layers (``Chunk.hands``: the
    router's latent, which starts from zero at the first layer, and what
    the mixers hand on) never leaves the call: it is a token's own, layer
    to layer."""
    layers = params["layers"]
    plan = cache_spec.layer_plan(cfg)
    b, t, _ = x.shape
    new_states, kept_pages = [], []
    with jax.named_scope("moe_route"):
        hands = {"latent": router_carry(cfg, (b, t))}
    index = cache_spec.pool_index(cfg)
    for l, p in enumerate(plan):
        at_pages, at_slot = index[l]

        def layer(x, hands, l=l, p=p, at_pages=at_pages, at_slot=at_slot):
            with jax.named_scope("glue"):
                mixer_lp, mlp_lp = _layer_params(cfg, layers, l)
            kept, latent = Kept(), hands["latent"]
            if p.mixer is not None:
                with jax.named_scope("glue"):
                    h_in = norm(layers, _norm_names(cfg)[0], x,
                                cfg.rms_norm_eps, l)
                    st = states[at_slot] \
                        if states is not None and at_slot is not None \
                        else _zero_state(cfg, p, b, x.dtype)
                pre = None if prefix is None or at_pages is None \
                    else prefix[at_pages]
                out, kept = _form(p, "sequence")(
                    cfg, p, mixer_lp, h_in,
                    Chunk(positions, valid, st, pre, hands))
                with jax.named_scope("glue"):
                    out = _post(cfg, layers, "attn_post_norm", out, l)
                    x = _residual(x, _branch(cfg, out),
                                  _res(layers, "attn_res", l))
            if p.mlp is not None:
                x, _load, latent = _mlp(cfg, x, layers, l, mlp_lp, valid,
                                        latent)
            return (x, {**hands, **kept.hands, "latent": latent}, kept.pages,
                    kept.slot)

        x, hands, pages, slot = (jax.checkpoint(layer) if remat
                                 else layer)(x, hands)
        if pages is not None:
            kept_pages.append(pages)
        if at_slot is not None:
            new_states.append(slot)
    return x, new_states, kept_pages


def _zero_state(cfg, p, b: int, dtype):
    """What a layer's slot holds before a sequence's first token (a
    window layer's ring: nothing, None)."""
    slot = cache_spec.slot_part(cache_spec.layer_cache(cfg, p, dtype))
    if slot is None or isinstance(slot, cache_spec.Ring):
        return None
    return tuple(jnp.zeros((b, *shape), dt) for _n, shape, dt in slot.arrays)


def forward(params, cfg, input_ids, positions, attn_mask, remat=False,
            logits_for=None):
    """``decoder.forward`` without a cache for a model of several kinds of
    layer. A recurrent state starts from zero at a row's first valid
    token, so padding may stand on either side."""
    with jax.named_scope("glue"):
        valid = attn_mask > 0
    with jax.named_scope("embed"):
        x = _embed(cfg, params, input_ids)
    x, _states, _lat = run_sequence(params, cfg, x, positions, valid,
                                    remat=remat)
    return _head(cfg, params, x, logits_for)


# -- the engine's paths ---------------------------------------------------------


def _token_rows(page_ids, ps: int):
    """Rows of a pool's flat ``[N * ps, w]`` view that the pages
    ``page_ids`` [B, n] hold, in order: [B, n * ps]."""
    b, n = page_ids.shape
    return (page_ids[:, :, None] * ps
            + jnp.arange(ps, dtype=jnp.int32)[None, None, :]).reshape(b, n * ps)


def _gather_pages(pool, page_ids):
    """``pool`` [1, N, ps, w], ``page_ids`` [B, n] -> rows [B, n*ps, w].
    By token rows of the flat view, as decode writes them
    (``decoder._scatter_token_kv``): a gather or scatter of whole pages
    made the compiler lay the pool out anew, one copy of it a use."""
    _one, n, ps, w = pool.shape
    return pool.reshape(n * ps, w)[_token_rows(page_ids, ps)]


def _scatter_tokens(pool, page_ids, rows, valid):
    """Write ``rows`` [B, T, w] to the pages ``page_ids`` [B, T // ps] of
    ``pool``; a padded position (``valid`` [B, T] false) goes to the null
    page."""
    _one, n, ps, w = pool.shape
    at = jnp.where(valid, _token_rows(page_ids, ps), 0).reshape(-1)
    flat = pool.reshape(n * ps, w).at[at].set(
        rows.reshape(-1, w).astype(pool.dtype))
    return flat.reshape(pool.shape)



def prefill(params, cfg, ids, lens, prefix_len, pools, prefix_page_ids,
            page_ids, slots):
    """A chunk of ``B`` prompts ``ids`` [B, pb] (``lens`` [B] real tokens,
    right padded) that continue ``prefix_len`` tokens (a scalar: 0 for a
    prompt's first chunk) already in ``prefix_page_ids`` [B, n_pre] and in
    the state rows ``slots`` [B]: what the chunk keeps in pages goes to
    ``page_ids`` [B, pb // page], the slots' state after the chunk to
    ``slots``. Returns (pools, last-token logits [B, V]). A state is read
    only where ``prefix_len`` > 0: a slot's first chunk starts from zero,
    whatever the last request left there."""
    paged, state = pools
    b, pb = ids.shape
    with jax.named_scope("glue"):
        valid = jnp.arange(pb)[None, :] < lens[:, None]
        positions = jnp.broadcast_to(
            prefix_len + jnp.arange(pb, dtype=jnp.int32), (b, pb))
        at = SlotRows(slots, prefix_len, lens, prefix_len == 0)
    keep_pages, keep_slot = _keepers(cfg)
    states = []
    for rows, rec in zip(state, keep_slot):
        with jax.named_scope(rec.slot_scope):
            states.append(rec.read_slot(cfg, rows, at))

    def gathered(paged, off=None):
        """What the prefix's pages hold, a paged layer; None for none.
        ``off``: what a pass adds to a page's number to find its own."""
        if not prefix_page_ids.shape[1]:
            return None
        with jax.named_scope("glue"):
            pre_len = jnp.broadcast_to(prefix_len, (b,))
        prefix = []
        for pool, rec in zip(paged, keep_pages):
            with jax.named_scope(rec.pages_scope):
                prefix.append((_gather_prefix(
                    rec, pool, prefix_page_ids if off is None
                    else prefix_page_ids + off), pre_len))
        return prefix

    def scattered(paged, kept, off=None):
        written = []
        for pool, new, rec in zip(paged, kept, keep_pages):
            with jax.named_scope(rec.pages_scope):
                if rec.scatter is not None:
                    written.append(rec.scatter(cfg, pool, prefix_page_ids,
                                               page_ids, new, prefix_len))
                    continue
                written.append(_scatter_chunk(
                    rec, pool, page_ids if off is None else page_ids + off,
                    new, valid))
        return tuple(written)

    if cache_spec.passes(cfg) == 1:
        prefix = gathered(paged)
        with jax.named_scope("embed"):
            x = _embed(cfg, params, ids)
        x, new_states, kept = run_plan(params, cfg, x, positions, valid,
                                       states, prefix)
        paged = scattered(paged, kept)
    else:
        def one_pass(carry, t):
            """Pass ``t`` over the chunk, from its own pages into them."""
            x, paged = carry
            with jax.named_scope("glue"):
                off = _pass_offset(cfg, paged, t)
            x = _next_pass(cfg, params, x, t)
            with jax.named_scope("ut_pass"):
                x, _states, kept = run_plan(params, cfg, x, positions, valid,
                                            prefix=gathered(paged, off))
                return (x, scattered(paged, kept, off)), None

        new_states = []
        with jax.named_scope("embed"):
            x = _embed(cfg, params, ids)
        (x, paged), _ = jax.lax.scan(one_pass, (x, paged),
                                     _pass_indices(cfg))
    written = []
    for rows, new, was, rec in zip(state, new_states, states, keep_slot):
        with jax.named_scope(rec.slot_scope):
            written.append(rec.write_slot(cfg, rows, at, new, was))
    logits = _head(cfg, params, x, jnp.maximum(lens - 1, 0))
    return (paged, tuple(written)), logits


def _gather_prefix(rec, pool, page_ids):
    """What the pages ``page_ids`` [B, n] of the ``pool`` of a layer of
    the kind ``rec`` hold: a latent pool's rows [B, n * ps, w], a K/V
    pair's (k, v) each [B, n * ps, H, w] (by slabs or by rows:
    ``Mixer.pages_by_slabs``)."""
    if not isinstance(pool, tuple):
        return _gather_pages(pool, page_ids)
    return (_gather_slabs_kv if rec.pages_by_slabs else _gather_kv)(
        pool, page_ids)


def _scatter_chunk(rec, pool, page_ids, kept, valid):
    """``pool`` with a chunk's ``kept`` written to its pages ``page_ids``
    [B, T // ps]. (A K/V pair's padded position lands in the tail of the
    row's last page or in the null page, where no length reaches it.)"""
    if not isinstance(pool, tuple):
        return _scatter_tokens(pool, page_ids, kept, valid)
    if rec.pages_by_slabs:
        return tuple(_scatter_slabs(a, page_ids, x)
                     for a, x in zip(pool, kept))
    return _scatter_kv(pool, page_ids, kept)


def _gather_kv(pool, page_ids):
    """A K/V pair of ``[Hkv, N, ps, D]`` pools, ``page_ids`` [B, n] -> (k,
    v) each [B, n*ps, Hkv, D]: whole pages, as the uniform decoder's
    prefix gather takes them."""
    def one(a):
        hkv, _n, ps, d = a.shape
        b, n = page_ids.shape
        return a[:, page_ids].transpose(1, 2, 3, 0, 4).reshape(
            b, n * ps, hkv, d)

    return one(pool[0]), one(pool[1])


def _scatter_kv(pool, page_ids, kv):
    """Write a chunk's (k, v), each [B, T, Hkv, D], to the pages
    ``page_ids`` [B, T // ps] of a K/V pair of pools, whole pages at a
    time (``blocks._scatter_pages_kv``). A padded position lands in the
    tail of the row's last page or in the null page, where no length
    reaches it."""
    def one(a, new):
        hkv, _n, ps, d = a.shape
        b, t = new.shape[:2]
        pages = new.reshape(b * (t // ps), ps, hkv, d).transpose(2, 0, 1, 3)
        return _scatter_pages_kv(a, page_ids.reshape(-1), pages)

    return one(pool[0], kv[0]), one(pool[1], kv[1])



# the routed MLP's entries of the load a decode step counts
# (``blocks._moe_mlp``), and, in a model of several kinds of layer, every
# (row, choice) of live rows whether or not its expert is held here
MOE_LOAD = ("moe_routed", "moe_experts_hit", "moe_load_max")
MOE_CHOICES = "moe_choices"
# a looped model's: live rows times the passes a step ran, and the keys a
# step's rows attend over times the passes that read a cache of their own
UT_LOAD = ("ut_passes", "kv_pass_rows_read")


def load_names(cfg) -> tuple[str, ...]:
    """The entries of the load a decode step counts, by their
    ``server_info`` names, in the vector's order: the routed MLP's, then
    what the records of the plan's mixers say they move
    (``Mixer.counts``), in the table's order."""
    if cache_spec.is_uniform(cfg):
        return MOE_LOAD if cfg.num_experts else ()
    plan = cache_spec.layer_plan(cfg)
    routed = any(p.mlp == "moe" for p in plan)
    kinds = {p.mixer for p in plan}
    names = [*MOE_LOAD, MOE_CHOICES] if routed else []
    for rec in MIXERS.values():
        if rec.name in kinds or (routed and rec.counts_in_routed):
            names += [n for n in rec.counts if n not in names]
    if cache_spec.passes(cfg) > 1:
        names += UT_LOAD
    return tuple(names)


def load_width(cfg) -> int:
    return len(load_names(cfg))


def step_counters(cfg, rows: int, one_chip: bool = True) -> tuple[str, ...]:
    """The share counters (``engine_profile.CUMULATIVE_KEYS``) that every
    decode step of ``rows`` rows moves: those of the kernels its layers
    take (``Mixer.kernel``), and the routed MLP's where its experts take
    their rows by table (``blocks.experts_in_kernel``; on a mesh of
    several chips they keep the tiled form)."""
    kinds = dict.fromkeys(p.mixer for p in cache_spec.layer_plan(cfg)
                          if p.mixer)
    routed = (("moe_gather_kernel_steps",)
              if one_chip and experts_in_kernel(cfg, rows) else ())
    return tuple(MIXERS[k].kernel[0] for k in kinds
                 if MIXERS[k].kernel and MIXERS[k].kernel[1](cfg, rows)
                 ) + routed


def held_state(cfg, state: tuple, slot: int) -> list:
    """What ``CBEngine.recurrent_state`` reads of the engine's slot
    ``slot``: one float32 array on the host a layer that keeps a slot, in
    order, as its mixer's ``held`` reads ``state``'s arrays."""
    return [rec.held(cfg, arrays, slot)
            for arrays, rec in zip(state, _keepers(cfg)[1])]


def paged_decode(params, cfg, tokens, positions, pools, page_table, seq_lens,
                 active=None, head_fn=None):
    """``decoder.forward_paged_decode`` for a model of several kinds of
    layer: one token a slot. A row without a request leaves its state
    rows as they are and writes what it would keep in pages to the null
    page. A looped model's passes are a loop of the program around the
    plan's layers, the pools carried through it and written in place; pass
    ``t`` reads and writes the pages ``cache_spec.pass_offset`` past the
    table's."""
    layers = params["layers"]
    plan = cache_spec.layer_plan(cfg)
    paged, state = list(pools[0]), list(pools[1])
    s = tokens.shape[0]
    ps = jax.tree_util.tree_leaves(paged)[0].shape[2]
    # where every layer's token goes, and what the step counts
    with jax.named_scope("glue"):
        live = jnp.ones((s,), bool) if active is None else active
        write_page = jnp.where(
            live, page_table[jnp.arange(s), seq_lens // ps], 0)
        write_off = jnp.where(live, seq_lens % ps, 0)
        attn_lens = jnp.where(live, seq_lens + 1, 0)
        n_live = jnp.sum(live.astype(jnp.int32))
        rows_read = jnp.sum(attn_lens)

    with jax.named_scope("embed"):
        x = _embed(cfg, params, tokens)
    load = Load(load_names(cfg))
    with jax.named_scope("moe_route"):
        hands = {"latent": router_carry(cfg, (s,))}
    index = cache_spec.pool_index(cfg)
    ctx = Step(positions, seq_lens, live, page_table, ps, write_page,
               write_off, attn_lens, n_live, rows_read, load, per={})
    for kind in dict.fromkeys(p.mixer for p in plan if p.mixer):
        if MIXERS[kind].per_step is not None:
            ctx.per[kind] = MIXERS[kind].per_step(cfg, ctx)

    def once(x, paged, state, ctx, hands):
        """The plan's layers, once: (x, paged, state)."""
        for l, p in enumerate(plan):
            at_pages, at_slot = index[l]
            with jax.named_scope("glue"):
                mixer_lp, mlp_lp = _layer_params(cfg, layers, l)
            kept, moe, latent = Kept(), None, hands["latent"]
            if p.mixer is not None:
                with jax.named_scope("glue"):
                    h_in = norm(layers, _norm_names(cfg)[0], x,
                                cfg.rms_norm_eps, l)
                own = dataclasses.replace(
                    ctx, pages=None if at_pages is None else paged[at_pages],
                    slot=None if at_slot is None else state[at_slot],
                    stack=layers[MIXERS[p.mixer].stack],
                    index=kind_index(cfg)[l][0], hands=hands)
                out, kept = _form(p, "step")(cfg, p, mixer_lp, h_in, own)
                if kept.pages is not None:
                    paged[at_pages] = kept.pages
                if kept.slot is not None:
                    state[at_slot] = kept.slot
                with jax.named_scope("glue"):
                    out = _post(cfg, layers, "attn_post_norm", out, l)
                    x = _residual(x, _branch(cfg, out),
                                  _res(layers, "attn_res", l))
            if p.mlp is not None:
                x, moe, latent = _mlp(cfg, x, layers, l, mlp_lp, active,
                                      latent)
            hands = {**hands, **kept.hands, "latent": latent}
            if moe is not None:
                with jax.named_scope("glue"):
                    load.vector = load.vector.at[:len(MOE_LOAD)].add(moe)
                    choices = n_live * cfg.num_experts_per_tok
                load.add(MOE_CHOICES, choices)
        return x, paged, state

    if cache_spec.passes(cfg) == 1:
        x, paged, state = once(x, paged, state, ctx, hands)
    else:
        def one_pass(carry, t):
            x, paged, load.vector = carry
            with jax.named_scope("glue"):
                off = _pass_offset(cfg, paged, t)
            x = _next_pass(cfg, params, x, t)
            with jax.named_scope("ut_pass"):
                with jax.named_scope("glue"):   # the pass's own pages
                    own = dataclasses.replace(
                        ctx, page_table=page_table + off,
                        write_page=write_page + off)
                x, paged, _state = once(x, list(paged), state, own, hands)
            load.add(UT_LOAD[0], n_live)
            load.add(UT_LOAD[1], rows_read)
            return (x, tuple(paged), load.vector), None

        (x, paged, load.vector), _ = jax.lax.scan(
            one_pass, (x, tuple(paged), load.vector), _pass_indices(cfg))
    return ((head_fn or _head)(cfg, params, x),
            (tuple(paged), tuple(state)), load.vector)
