"""The blocks that every decoder of this package is built from, whatever
the kinds of its layers: the RMS norm, the routed mixture MLP with its three
routers (``_moe_mlp``: softmax top-k, sigmoid ``noaux_tc``, and an MLP on
a latent that layers hand on, ``_latent_route``), the output head, and the row scatter of one token
into a paged pool. ``models/decoder.py`` (the uniform stacked-scan
decoder) and ``models/hybrid.py`` (layers of several kinds) both import
them from here, and neither imports the other's blocks; ``decoder``
re-exports the names it always had.

``cfg`` is a ``decoder.ModelConfig`` throughout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from polyrl_tpu.models import cache_spec
from polyrl_tpu.models.quant import (QuantWeight, expert_in, mm, moe_mm,
                                     moe_rows, unembed)
from polyrl_tpu.ops import grouped_matmul
from polyrl_tpu.ops.grouped_matmul import row_tables, row_tile, tiled_layout
from polyrl_tpu.parallel.mesh import EP, TP


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float) -> jnp.ndarray:
    """LayerNorm with a weight and a bias, in float32 as ``rms_norm``."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def norm(tree: dict, name: str, x: jnp.ndarray, eps: float,
         l: int | None = None) -> jnp.ndarray:
    """The norm ``name`` of ``tree`` (row ``l`` of a stack): a LayerNorm
    where the tree holds ``<name>_bias`` beside the weight, else the RMS
    norm."""
    pick = (lambda a: a) if l is None else (lambda a: a[l])
    if name + "_bias" in tree:
        return layer_norm(x, pick(tree[name]), pick(tree[name + "_bias"]),
                          eps)
    return rms_norm(x, pick(tree[name]), eps)


EXPERT_KEYS = ("we_gate", "we_up", "we_down")


# tables of at most this many rows are read by a one-hot product
_ONE_HOT_ROWS = 1024


def _take(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx]``: rows of a 2-D table, entries of a 1-D one. A TPU
    gather moves one row or one scalar at a time (12 ns a 4 KB row, 25 ns a
    scalar: a decode step's 2,560 tiled rows cost 45 us a layer, beside
    1.6 ms of experts; PERF.md section 6, PR 27), so a small table is read
    by a product with the one-hot of ``idx`` instead, which is exact (one
    term a row) and runs on the MXU. A large table (the trainer's tokens)
    is gathered."""
    n = table.shape[0]
    if n > _ONE_HOT_ROWS:
        return table[idx]
    hot = idx[:, None] == jnp.arange(n)[None, :]
    if table.ndim == 1:
        return jnp.sum(jnp.where(hot, table[None, :], 0), axis=1)
    return jnp.einsum("pn,nd->pd", hot.astype(table.dtype), table,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32).astype(table.dtype)


def _context_mesh():
    """The mesh set around this trace (``parallel.mesh.under``) when it
    has more than one device, else None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.size == 1 else mesh


def experts_in_kernel(cfg, rows: int) -> bool:
    """Whether ``_moe_mlp`` over ``rows`` tokens on one chip takes its
    experts' rows by table (``grouped_matmul.expert_rows``): the engine's
    question for its ``moe_gather_kernel_steps``; the shapes alone
    (``grouped_matmul.rows_by_table``) and a TPU decide."""
    return bool(cfg.num_experts) and grouped_matmul.in_kernel(
        rows, cfg.hidden_size, jnp.dtype(cfg.dtype).itemsize,
        rows * cfg.num_experts_per_tok, cache_spec.experts_held(cfg)[1])


def _expert_rows(x, experts, layer, token_of, weight, sizes):
    """``_expert_mix`` at a decode step's shapes on one TPU
    (``grouped_matmul.in_kernel``), where no tiled copy of the rows is
    made: the two kernels take a tile's rows from ``x`` by ``token_of``
    and add each product, times ``weight`` [N*k] (the routing weights in
    the sorted rows' order), into its token's sum themselves
    (``moe_rows``). A token's k terms are added in its experts' order."""
    tile = row_tile(token_of.shape[0], sizes.shape[0])
    return moe_rows(x, experts, row_tables(sizes, token_of, tile), weight,
                    tile, layer)


def _expert_mix(x, experts, layer, token_of, place, choice, top_p, sizes,
                first=0):
    """Each token's weighted sum over those of its k choices that fall on
    the experts ``experts`` holds, which are ``first`` onwards of all of
    them: [N, d] float32, zero for a token with no choice here.

    ``token_of`` [N*k]: the token of each choice in expert-sorted order;
    ``place`` [N*k]: each choice's place in that order; ``sizes`` [E]: all
    experts' rows. The local experts' rows are contiguous there. They are
    gathered into whole tiles an expert (``tiled_layout``; at most
    N*k + E*tile rows), run through the grouped SwiGLU and the grouped
    down projection, and each choice reads its row back."""
    n, d = x.shape
    m = token_of.shape[0]
    e_here = experts["we_down"].shape[-3]
    mine = jax.lax.dynamic_slice_in_dim(sizes, first, e_here)
    first_row = jnp.sum(jnp.where(jnp.arange(sizes.shape[0]) < first,
                                  sizes, 0))
    lay = tiled_layout(mine, m, row_tile(m, e_here))
    # a pad row reads the zero row appended to x
    rows = jnp.where(
        lay.live, _take(token_of, jnp.clip(first_row + lay.src, 0, m - 1)), n)
    xs = _take(jnp.concatenate([x, jnp.zeros((1, d), x.dtype)]), rows)
    ws_in, act = expert_in(experts)
    hidden = moe_mm(xs, ws_in, lay, layer, act)
    ys = moe_mm(hidden, (experts["we_down"],), lay, layer)
    here = choice - first            # invalid choices carry expert E
    is_here = (here >= 0) & (here < e_here)
    row = place - first_row + lay.shift[jnp.clip(here, 0, e_here - 1)]
    # a gather, not ``_take``: the kernel leaves the rows of tiles without
    # rows undefined, and a one-hot product would sum them in (0 x NaN)
    y = jnp.where(is_here[:, None], ys[jnp.clip(row, 0, xs.shape[0] - 1)], 0)
    return jnp.einsum("nkd,nk->nd", y.reshape(n, -1, d).astype(jnp.float32),
                      top_p)


def _expert_mix_sharded(mesh, x, experts, layer, *route):
    """``_expert_mix`` on a mesh, manual over every axis (a Mosaic kernel
    cannot be partitioned for it): each ``ep`` rank computes the rows of
    its own experts, each ``tp`` rank its own columns of gate and up and
    rows of down (SwiGLU is element-wise there), and the results, zero or
    partial elsewhere, are summed over ``ep`` and ``tp``. The experts'
    ``fsdp`` shards are gathered on the way in, as for any weight. The
    routing is one sort over all the tokens, so every rank of the data
    axes holds, and computes, them all."""
    lead = () if layer is None else (None,)   # whole stacks [L, E, ..]

    def spec(key, w):
        s = (P(*lead, EP, TP, None) if key == "we_down"
             else P(*lead, EP, None, TP))
        # a QuantWeight's scale [.., E, out] follows the output columns
        return s if not isinstance(w, QuantWeight) else QuantWeight(
            q=s, scale=P(*s[:-2], s[-1]))

    def local(x, experts, *route):
        first = jax.lax.axis_index(EP) * experts["we_down"].shape[-3]
        return jax.lax.psum(
            _expert_mix(x, experts, layer, *route, first=first), (EP, TP))

    specs = {key: spec(key, w) for key, w in experts.items()}
    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(), specs) + (P(),) * len(route),
        out_specs=P(), check_vma=False)(x, experts, *route)


def _group_limited_topk(cfg, biased: jnp.ndarray) -> jnp.ndarray:
    """The k experts a token chooses from its choice scores ``biased`` [N,
    E]: ``n_group`` groups of consecutive experts, a group's score the sum
    of its two highest, the best ``topk_group`` groups kept, the k highest
    among them: [N, k] in ``lax.top_k``'s order (descending score, the
    lower index first among equals), to the bit what ``lax.top_k`` would
    choose, without its sort: on a TPU a ``top_k`` is a full key-and-index
    sort of the last axis, whatever k. A group's two highest are its
    maximum and, where that stands once, the maximum of what lies under
    it; the k choices are k maxima, each masked out once taken
    (``argmax`` takes the first of equals). The ``topk_group`` of
    ``n_group`` scores stay a ``top_k``: a sort of 8 keys costs nothing."""
    n, e = biased.shape
    g = cfg.n_group
    if g > 1:
        x = biased.reshape(n, g, e // g)
        best = jnp.max(x, axis=-1, keepdims=True)
        under = jnp.max(jnp.where(x < best, x, -jnp.inf), axis=-1)
        # counted in float32: an integer sum over the lanes is 4 us a layer
        twice = jnp.sum(x == best, axis=-1, dtype=jnp.float32) > 1
        group = best[..., 0] + jnp.where(twice, best[..., 0], under)  # [N, g]
        _, keep = jax.lax.top_k(group, cfg.topk_group)
        kept = jnp.any(keep[:, :, None] == jnp.arange(g)[None, None, :],
                       axis=1)                                     # [N, g]
        biased = jnp.where(jnp.repeat(kept, e // g, axis=1), biased,
                           -jnp.inf)
    chosen, at = [], jnp.arange(e)
    for _ in range(cfg.num_experts_per_tok):
        chosen.append(jnp.argmax(biased, axis=-1))
        biased = jnp.where(at == chosen[-1][:, None], -jnp.inf, biased)
    return jnp.stack(chosen, axis=-1)


def _sigmoid_route(cfg, x: jnp.ndarray, lp: dict):
    """DeepSeek-V3's ``noaux_tc`` router on ``x`` [N, d]: scores ``s =
    sigmoid(x Wr)`` over all experts in float32; the choice is made on ``s
    + bias`` (``_group_limited_topk``: by maxima, not by a sort); the
    weights are the chosen ``s`` (without the bias) over their sum, times
    ``routed_scaling_factor``. The choices stand in ``lax.top_k``'s order,
    and the float32 sum adds them in that order. Returns (weights [N, k]
    float32, experts [N, k])."""
    scores = jax.nn.sigmoid(jnp.dot(
        x, lp["router"], preferred_element_type=jnp.float32))
    top_i = _group_limited_topk(
        cfg, scores + lp["router_bias"].astype(jnp.float32))
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.norm_topk_prob:
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    return top_s * cfg.routed_scaling_factor, top_i


def _latent_route(cfg, x: jnp.ndarray, lp: dict, carried: jnp.ndarray):
    """ZAYA1's router on ``x`` [N, d]: a latent ``s = x Wd + gamma *
    carried`` [N, R] float32, ``carried`` being the layer before's latent
    of the same token (zeros in the first layer); probabilities ``p =
    softmax(gelu(gelu(rms(s) W1) W2) W3)`` over all experts in float32; the
    choice is made on ``p + bias`` (a balancing bias, as ``noaux_tc``'s),
    the weights are the chosen ``p`` as they are. The three small matmuls
    run at the highest precision: with one choice a token, a flipped
    choice is a whole expert's difference. Returns (weights [N, k]
    float32, experts [N, k], the latent to hand on)."""
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    s = jnp.dot(x, lp["router_down"], preferred_element_type=f32)
    s = s + lp["router_gamma"].astype(f32) * carried
    z = rms_norm(s, lp["router_norm"], cfg.rms_norm_eps)
    for w in (lp["router_w1"], lp["router_w2"]):
        z = jax.nn.gelu(jnp.dot(z, w.astype(f32), precision=hi),
                        approximate=False)
    probs = jax.nn.softmax(
        jnp.dot(z, lp["router"].astype(f32), precision=hi), axis=-1)
    top_i = jax.lax.top_k(probs + lp["router_bias"].astype(f32),
                          cfg.num_experts_per_tok)[1]
    return jnp.take_along_axis(probs, top_i, axis=-1), top_i, s


def _moe_mlp(cfg, x: jnp.ndarray, lp: dict,
             valid: jnp.ndarray | None = None, layer: int | None = None,
             route: tuple | None = None
             ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Routed mixture MLP on flattened tokens ``x`` [N, d] -> [N, d],
    dropless, with static shapes.

    Routing follows HF Qwen3MoeSparseMoeBlock: router logits in the
    model's dtype, softmax in float32 over ALL experts, top-k, optional
    renormalisation of the k probabilities, times
    ``routed_scaling_factor`` where the configuration has one (Laguna's
    ``moe_routed_scaling_factor``). The N*k (token, expert)
    choices are sorted by expert, the three expert projections run as
    grouped matmuls over the sorted rows (``_expert_mix``: whatever the
    imbalance, no choice is dropped and no expert multiplies a row that
    did not choose it), and each token sums its k results with the routing
    weights in float32. One block serves decode, prefill and the trainer;
    at a decode step's shapes on one TPU the kernels take the sorted rows
    from ``x`` and sum them back themselves (``_expert_rows``: the shapes
    choose, ``grouped_matmul.in_kernel``).

    ``valid`` [N] (padding, decode rows without a request): an invalid
    token routes nowhere and returns zero.

    ``layer``: ``lp``'s experts are whole stacks, and that layer of them
    is meant (``_unrolled_layer``).

    ``route``: (weights [N, k] float32, experts [N, k]) where the caller
    has routed already: a router with a latent carried from layer to layer
    (``_latent_route``) is the layer loop's to thread.

    Also returns the step's load, int32 [3]: (token, expert) pairs routed,
    experts with at least one row, rows of the busiest expert."""
    n, d = x.shape
    k = cfg.num_experts_per_tok
    first, e = cache_spec.experts_held(cfg)
    with jax.named_scope("moe_route"):
        if route is not None:
            top_p, top_i = route
        elif cfg.scoring_func == "sigmoid":
            top_p, top_i = _sigmoid_route(cfg, x, lp)
        else:
            probs = jax.nn.softmax(mm(x, lp["router"]).astype(jnp.float32),
                                   axis=-1)
            top_p, top_i = jax.lax.top_k(probs, k)                # [N, k]
            if cfg.norm_topk_prob:
                top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
            if cfg.routed_scaling_factor != 1.0:
                top_p = top_p * cfg.routed_scaling_factor
        if e != cfg.num_experts:
            # this chip's share: a choice that falls on an expert held
            # elsewhere is left out, as expert ``e`` (none) with weight 0
            here = (top_i >= first) & (top_i < first + e)
            top_p = jnp.where(here, top_p, 0.0)
            top_i = jnp.where(here, top_i - first, e)
        choice = top_i.reshape(n * k)
        if valid is not None:
            valid = valid.astype(bool)
            top_p = jnp.where(valid[:, None], top_p, 0.0)
            # expert ``e`` is none: it sorts last and counts nowhere
            choice = jnp.where(jnp.repeat(valid, k), choice, e)
        mesh = _context_mesh()
        by_table = mesh is None and grouped_matmul.in_kernel(
            n, d, x.dtype.itemsize, n * k, e)
        if by_table:
            # ONE sort: the kernels read a sorted row's weight beside its
            # token, and no choice looks its row up
            _, order, weight = jax.lax.sort(
                (choice, jnp.arange(n * k), top_p.reshape(n * k)), num_keys=1)
        else:
            order = jnp.argsort(choice, stable=True)  # sorted row -> choice
            place = jnp.argsort(order)                # choice -> sorted row
        sizes = jnp.sum(jax.nn.one_hot(choice, e, dtype=jnp.int32), axis=0)
        load = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                          jnp.max(sizes)])
    with jax.named_scope("moe_experts"):
        experts = {key: lp[key] for key in EXPERT_KEYS if key in lp}
        token_of = order // k
        if by_table:
            out = _expert_rows(x, experts, layer, token_of, weight, sizes)
        elif mesh is None:
            out = _expert_mix(x, experts, layer, token_of, place, choice,
                              top_p, sizes)
        elif e != cfg.num_experts:
            raise NotImplementedError(
                "a share of the experts (experts_held) on a mesh: the ep "
                "axis holds them all")
        else:
            out = _expert_mix_sharded(mesh, x, experts, layer, token_of,
                                      place, choice, top_p, sizes)
    if "ws_gate" in lp:
        with jax.named_scope("moe_shared"):
            gate = jax.nn.silu(mm(x, lp["ws_gate"]).astype(jnp.float32))
            out = out + mm(gate.astype(x.dtype) * mm(x, lp["ws_up"]),
                           lp["ws_down"]).astype(jnp.float32)
    elif "ws_up" in lp:
        # the ungated shared expert: ``relu(x W_up)^2 W_down``
        with jax.named_scope("moe_shared"):
            up = jnp.maximum(mm(x, lp["ws_up"]).astype(jnp.float32), 0.0)
            out = out + mm(jnp.square(up).astype(x.dtype),
                           lp["ws_down"]).astype(jnp.float32)
    with jax.named_scope("glue"):
        return out.astype(x.dtype), load


def head_input(cfg, x):
    """What the output matmul reads of the normed ``x``: under muP
    (``dim_model_base`` > 0) ``x / (hidden_size / dim_model_base)``."""
    if not cfg.dim_model_base:
        return x
    return (x.astype(jnp.float32)
            * (cfg.dim_model_base / cfg.hidden_size)).astype(x.dtype)


def _head(cfg, params, x, logits_for=None):
    """Final norm and the output matmul; ``logits_for`` [B] unembeds one
    position a row of ``x`` [B, T, d]."""
    with jax.named_scope("head"):
        x = head_input(cfg, norm(params, "final_norm", x, cfg.rms_norm_eps))
        head = (params["embed"].T if cfg.tie_word_embeddings
                else params["lm_head"])
        if logits_for is not None:
            # unembed only one position per row: prefill needs just the
            # last real token's logits, and [B, T, V] f32 for a long chunk
            # is the dominant HBM transient (e.g. 4k x 152k f32 = 2.5 GB
            # per prompt)
            x = jnp.take_along_axis(x, logits_for[:, None, None], axis=1)[:, 0]
            return unembed(x, head, "bd,dv->bv")
        eq = "btd,dv->btv" if x.ndim == 3 else "sd,dv->sv"
        return unembed(x, head, eq)


def _scatter_token_kv(pool, write_page, write_off, upd):
    """Scatter one token's KV per slot into ``pool`` [Hkv, N, ps, D];
    ``upd`` is [S, Hkv, D]. Written as a ROW scatter in the flattened
    [Hkv·N·ps, D] view: the update window is then the minor-most dim alone,
    so XLA's layout assignment keeps the pool in standard layout — the
    4-D form's split window (Hkv major + D minor) made layout assignment
    pick a permuted physical layout, and the attention kernel's
    standard-layout operand constraint then forced a full-pool copy every
    decode iteration."""
    hkv, n, ps, d = pool.shape
    s = write_page.shape[0]
    flat = pool.reshape(hkv * n * ps, d)
    head_off = jnp.arange(hkv, dtype=jnp.int32)[:, None] * (n * ps)
    idx = (head_off + (write_page * ps + write_off)[None, :]).reshape(-1)
    flat = flat.at[idx].set(
        upd.transpose(1, 0, 2).reshape(hkv * s, d).astype(pool.dtype))
    return flat.reshape(hkv, n, ps, d)


def _scatter_pages_kv(pool, page_ids, upd):
    """Scatter whole pages into ``pool`` [Hkv, N, ps, D]; ``upd`` is
    [Hkv, n_pg, ps, D]. Same flat-row trick as ``_scatter_token_kv``
    ([Hkv·N, ps·D] rows) to keep the pool in standard layout."""
    hkv, n, ps, d = pool.shape
    npg = page_ids.shape[0]
    flat = pool.reshape(hkv * n, ps * d)
    idx = (jnp.arange(hkv, dtype=jnp.int32)[:, None] * n
           + page_ids[None, :].astype(jnp.int32)).reshape(-1)
    flat = flat.at[idx].set(upd.reshape(hkv * npg, ps * d).astype(pool.dtype))
    return flat.reshape(hkv, n, ps, d)


def _slab_ids(a, page_ids):
    """Slabs of ``a``'s ``[H * N, ps, w]`` view that hold the pages
    ``page_ids`` [B, n] of every head, head-major: [H * B * n]. The view's
    last two dimensions are the pool's own tiles, so a gather or a scatter
    over its slabs leaves the pool's layout as it is."""
    h, n = a.shape[:2]
    return (jnp.arange(h, dtype=jnp.int32)[:, None] * n
            + page_ids.reshape(-1)[None, :].astype(jnp.int32)).reshape(-1)


def _gather_slabs_kv(pool, page_ids):
    """``_gather_kv`` by slabs (``_slab_ids``): 2,560 slabs of 16 KB for a
    prefix of 256 pages, where token rows of the flat view were 164k
    gathers of 256 B (1.4 ms less of a chunk's 56 ms on the chip)."""
    b, n_pg = page_ids.shape

    def one(a):
        h, n, ps, w = a.shape
        got = a.reshape(h * n, ps, w)[_slab_ids(a, page_ids)]
        return got.reshape(h, b, n_pg * ps, w).transpose(1, 2, 0, 3)

    return one(pool[0]), one(pool[1])


def _scatter_slabs(a, page_ids, rows):
    """``a`` [H, N, ps, w] with the pages ``page_ids`` [B, n] holding
    ``rows`` [B, n * ps, H, w], by slabs (``_slab_ids``)."""
    h, n, ps, w = a.shape
    b, n_pg = page_ids.shape
    pages = rows.reshape(b * n_pg, ps, h, w).transpose(2, 0, 1, 3)
    return a.reshape(h * n, ps, w).at[_slab_ids(a, page_ids)].set(
        pages.reshape(-1, ps, w).astype(a.dtype)).reshape(a.shape)
