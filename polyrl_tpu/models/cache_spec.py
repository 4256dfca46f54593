"""The one place that says, per layer, which mixer and which MLP a model
runs and what a sequence keeps for that layer between steps.

A mixer is ``gqa`` (grouped-query softmax attention with RoPE), ``kda``
(Kimi Delta Attention: a recurrent float32 state a head plus the tails of
three short convolutions), ``mla`` (multi-head latent attention: one
normed latent row and one shared rope key a token) or ``cca`` (compressed
convolutional attention: grouped-query attention over query and key
latents that two short causal convolutions mix, half the value heads
shifted by one token); and the six kinds of the SambaY family
(``mb_per_layer`` > 0, below). An MLP is ``dense``
(SwiGLU) or ``moe`` (routed experts, ``decoder._moe_mlp``). Every preset
from before the hybrid family is the uniform pattern: ``gqa`` in every
layer, with the same MLP in every layer. A published ``layer_types`` list
(``full_attention`` / ``sliding_attention``: Laguna) makes ``gqa`` where
the list says full and ``gqa_window`` where it says sliding: the same
rope'd softmax attention over the last ``sliding_window`` keys, kept in a
RING as ``swa``'s are, with a head count and a rope of its kind's own
(``gqa_heads``, ``gqa_rope``). The DeepSeek-V3 family
(``kv_lora_rank`` without a ``layer_group_size``) is ``mla`` in every
layer behind leading dense layers. ``cca_time0`` > 0 is ``cca`` in every
layer (ZAYA1's decoder). A published ``mixer_types`` list
(``minicpm4`` / ``lightning-attn``: MiniCPM-SALA) makes ``sparse`` (softmax
attention over the blocks of keys a token's queries choose, a K/V head at
a time, through pooled keys) and ``lightning`` (linear attention: a float32
state a head under a constant decay). A published ``hybrid_override_pattern``
(``nemotron_h``: one character a layer, ``PATTERN_KINDS``) makes layers of
ONE sublayer: ``M`` is a ``mamba2`` mixer (Mamba-2's SSD: a float32 state
a head under a scalar decay, B and C shared by groups of heads, the tail of
one short convolution) and no MLP, ``*`` a ``gqa`` mixer WITHOUT positions
(``attn_no_rope``) and no MLP, ``E`` a routed MLP and no mixer
(``LayerPlan.mixer`` or ``.mlp`` is None; ``hybrid.py`` runs such a layer
with its one norm and one residual, and the tree holds one ``norm`` a
layer); another character (the family's dense ``-``) is refused by name.

The SambaY family (``mb_per_layer`` > 0: a decoder, a cross-decoder and
differential attention without positions) has six kinds that follow from
``mb_per_layer``, ``sliding_window`` and the layer's index ``i``, with ``h``
half the layers: below ``h``, ``ssm`` (a Mamba-1 selective scan) where ``i %
mb_per_layer == 0`` and ``swa`` (attention over the last
``sliding_window`` keys) otherwise; at ``h``, ``ssm_mem``: the same scan,
whose output ``m`` before its gate is handed down the layers of the same
step; at ``h + 1``, ``diff``: full attention that WRITES the one paged K/V
pair the model has; above, ``gmu`` (a gated memory unit on ``m``, which
keeps nothing) where ``i % mb_per_layer == 0`` and ``cross`` (attention
with its own queries over the ``diff`` layer's pages, which keeps nothing
and READS another layer's pool) otherwise.

What a sequence keeps is said by the mixer's record (``models/mixers``:
``Mixer.cache``, which ``layer_cache`` looks up; each module's docstring
has its arrays) in this module's types: PAGED (so many values a token, in
pages that the engine's allocator hands out: a K/V pair of ``[Hkv, N,
page, D]`` for ``gqa`` and ``diff``, one latent pool ``[1, N, page, row]``
for ``mla``, ``row`` being ``rank + rope`` rounded up to whole lanes), or a
SLOT (fixed-size arrays indexed by the engine's slot, for ``kda``,
``lightning``, the scans and ``mamba2``: its float32 state ``[G, N, (H / G)
P]``, a group's heads side by side on the lanes under the state size on the
sublanes, 2 MiB a layer at the published sizes, with the ``[K-1, channels]``
tail), or BOTH in one layer (``PagedAndSlot``, for
``cca`` and ``sparse``: the latter's slot is the table of pages its last
decode step attended, int32). A ``sparse`` layer's pages carry a
POOLED-KEY STORE beside the K/V pair (``Paged.pooled``): one float32 row
every ``pooled`` tokens a K/V head, ``[N, page_size / pooled * heads, width]``, a third array of the
layer's pool that the SAME page numbers index, so the allocator, the
ledger, growth, yield and the page table know one kind of page. A ``swa``
layer keeps a RING: a K/V pair of the last ``window`` tokens in pages
that belong to the SLOT (``window / page_size`` pages a slot in a pool of
the layer's own, at a place fixed when the pool is made: token ``t`` lies
at ``t % window``, and a row's length is ``min(t + 1, window)``):
attention without positions does not care for the order of its keys, so
the paged write and attention kernels serve it as they are, nothing is
ever freed, and the allocator and the ledger never see it: a sequence's
window costs the same whatever its length. A ``cross`` layer keeps
NOTHING and names the layer whose pages it reads (``Reads``;
``pool_index`` hands it the producer's pool), so a page's bytes are ONE
layer's for the whole model. What a layer hands to later layers of the
same step (``ssm_mem``'s ``m``, as a router's carried latent) is cached
nowhere.

A layer of the plan is a layer of WEIGHTS. What a token keeps for it may
be several passes' worth: a looped model (``ut_steps`` > 1) runs the plan
that many times a token, and pass ``t``'s layer ``l`` attends what pass
``t``'s layer ``l`` kept. ``passes`` is the one place that says so: a
PAGED layer's pools are then ``passes`` runs of the engine's
``num_pages`` pages (``Paged.passes``), a token's bytes and a page's
bytes ``passes`` times the one-pass figure, and the pass's index becomes
a page offset (``pass_offset``: ``t * num_pages``, which
``hybrid.paged_decode`` and ``hybrid.prefill`` add to the page numbers
they hand a layer; the kernels find their pages through the table as
ever). The engine hands out and books ``num_pages`` LOGICAL pages and
never learns that a model loops.

``CBEngine`` asks two questions of a model's layers (ARCHITECTURE.md,
"Cache specification"). Does a sequence keep anything outside pages
(``is_stateful``)? A state in a slot has no page boundary to snapshot at,
so the engine then turns off every feature that re-enters a sequence
anywhere but at its last token; that holds for ``cca``'s tails as for a
``kda`` state, so a ``cca`` model has no prefix cache, no shared prompt in
a group and no salvage, and a yielded row prefills anew from token 0,
which recomputes the tails with the pages. And which features that act on pages have
a kernel for every mixer of the plan (``without_kernel``)? What acts on
pages alone (prefix cache, a group's shared prompt, salvage, the ledger,
page growth and yield) runs on any paged pool; the grouped two-phase
decode kernel, speculation's multi-token verify and the spill tier's page
copies are written for a K/V pair without tails: ``without_kernel`` names
``cca`` for all three (its one-token decode runs the ``gqa`` kernels,
``ops.paged_attention``'s write and attention, on its K/V pair).

Readers: ``decoder.make_paged_pools`` and ``CBEngine._make_pools`` (the
arrays; the page ledger takes its bytes a page from the paged ones),
``CBEngine`` (the two questions), ``models/hybrid.py`` (the layer loop)
and ``models/mixers`` (the dimensions);
``benchmark/lib/costs_hybrid.py``, ``costs_latent.py``, ``costs_cca.py``
and ``costs_sambay.py`` repeat the arithmetic on their own."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    # "gqa" | "kda" | "mla" | "cca" | "ssm" | "swa" | "ssm_mem" | "diff" |
    # "gmu" | "cross" | "gqa_window" | "sparse" | "lightning" | "mamba2";
    # None: the layer has no mixer (``hybrid_override_pattern``)
    mixer: str | None
    mlp: str | None   # "dense" | "moe"; None: the layer has no MLP
    published: int    # the layer's index in the published model


@dataclasses.dataclass(frozen=True)
class Paged:
    """``arrays`` pools of ``[heads, passes * pages, page_size, width]`` a
    layer: a token keeps ``passes`` rows in each, one a pass of a looped
    model (``passes``), at the same place in ``passes`` pages that lie
    ``pages`` apart (``pass_offset``). ``pooled`` > 0: one more array,
    ``[pages, page_size / pooled * heads, width]`` in ``POOLED_DTYPE``,
    a row every ``pooled`` tokens a head (a ``sparse`` layer's pooled keys;
    a page's rows of every head are one tile of the chip's at 2 heads and
    4 rows a page)."""
    arrays: int
    heads: int
    width: int
    passes: int = 1
    pooled: int = 0

    def values_per_token(self) -> int:
        return self.arrays * self.heads * self.width * self.passes

    def bytes_per_token(self, itemsize: int) -> int:
        extra = (self.heads * self.width
                 * jnp.dtype(POOLED_DTYPE).itemsize // self.pooled
                 if self.pooled else 0)
        return self.values_per_token() * itemsize + extra


@dataclasses.dataclass(frozen=True)
class Slot:
    """Arrays of ``(slots, *shape)`` a layer: (name, shape, dtype)."""
    arrays: tuple

    def bytes_per_slot(self) -> int:
        total = 0
        for _name, shape, dtype in self.arrays:
            n = 1
            for s in shape:
                n *= s
            total += n * jnp.dtype(dtype).itemsize
        return total


@dataclasses.dataclass(frozen=True)
class PagedAndSlot:
    """A layer that keeps both: pages a token and arrays a slot."""
    paged: Paged
    slot: Slot


@dataclasses.dataclass(frozen=True)
class Ring:
    """A K/V pair of the last ``window`` tokens a slot, ``[heads, 1 + slots
    * window / page_size, page_size, width]`` each: pages that belong to
    the slot (slot ``i``'s are ``1 + i * n .. 1 + i * n + n - 1``, page 0
    the null page)."""
    heads: int
    width: int
    window: int
    dtype: object

    def bytes_per_slot(self) -> int:
        return (2 * self.heads * self.width * self.window
                * jnp.dtype(self.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class Reads:
    """A layer that keeps nothing and reads the pages of layer ``layer``
    (its place in the plan)."""
    layer: int


def paged_part(c) -> Paged | None:
    return c if isinstance(c, Paged) else getattr(c, "paged", None)


def slot_part(c) -> Slot | Ring | None:
    return c if isinstance(c, (Slot, Ring)) else getattr(c, "slot", None)


# the type the recurrent state is kept in (Ling's config: float32). A
# module constant and no option: the benchmark's control of ``correct``
# computes the reference with a bfloat16 state, not the program.
STATE_DTYPE = jnp.float32
# the type a ``sparse`` layer's pooled keys are kept in: the mean of
# ``sparse_kernel_size`` keys, which the selection's scores are taken
# against; float32 rows of ``[heads * rows a page, width]`` are whole
# (8, 128) tiles at the published sizes, where bfloat16 rows would be
# padded to the same bytes
POOLED_DTYPE = jnp.float32


def layer_plan(cfg) -> tuple[LayerPlan, ...]:
    """The model's layers in order. ``layer_group_size`` > 0 is the hybrid
    family: published layer ``i`` is ``mla`` where ``(i + 1) %
    layer_group_size == 0`` and ``kda`` otherwise. ``kv_lora_rank`` > 0
    without it is latent attention in every layer; neither is ``gqa`` in
    every layer; ``mb_per_layer`` > 0 is the SambaY family's six kinds
    (module docstring); ``layer_types`` names ``gqa`` and ``gqa_window``
    layer by published layer (``LAYER_TYPES``). ``first_k_dense_replace`` leading published layers keep
    the dense MLP. ``kept_layers`` names the published layers that run
    here (a depth cut), all of them by default."""
    if cfg.hybrid_override_pattern:
        return _pattern_plan(cfg)
    kept = cfg.kept_layers or tuple(range(cfg.num_layers))
    if len(kept) != cfg.num_layers:
        raise ValueError(f"kept_layers {kept} names {len(kept)} layers, "
                         f"num_layers is {cfg.num_layers}")
    if cfg.mb_per_layer and cfg.kept_layers:
        raise ValueError("a depth cut of a model whose upper layers read "
                         "what layers below them keep")
    half = cfg.num_layers // 2
    plan = []
    for i in kept:
        if cfg.mb_per_layer:
            scan = i % cfg.mb_per_layer == 0
            if i < half:
                mixer = "ssm" if scan else "swa"
            elif i < half + 2:
                mixer = "ssm_mem" if i == half else "diff"
            else:
                mixer = "gmu" if scan else "cross"
        elif cfg.layer_group_size:
            mixer = "mla" if (i + 1) % cfg.layer_group_size == 0 else "kda"
        elif cfg.cca_time0:
            mixer = "cca"
        elif cfg.layer_types:
            mixer = LAYER_TYPES[cfg.layer_types[i]]
        elif cfg.mixer_types:
            mixer = MIXER_TYPES[cfg.mixer_types[i]]
        else:
            mixer = "mla" if cfg.kv_lora_rank else "gqa"
        sparse = bool(cfg.num_experts) and i >= cfg.first_k_dense_replace
        plan.append(LayerPlan(mixer, "moe" if sparse else "dense", i))
    return tuple(plan)


# a character of a published ``hybrid_override_pattern`` -> the layer's ONE
# sublayer, (mixer, mlp)
PATTERN_KINDS = {"M": ("mamba2", None), "*": ("gqa", None), "E": (None, "moe")}


def _pattern_plan(cfg) -> tuple[LayerPlan, ...]:
    """The layers a published ``hybrid_override_pattern`` names, one
    sublayer each (``PATTERN_KINDS``), every one of them kept."""
    pattern = cfg.hybrid_override_pattern
    if len(pattern) != cfg.num_layers or (
            cfg.kept_layers
            and tuple(cfg.kept_layers) != tuple(range(len(pattern)))):
        raise ValueError(
            f"hybrid_override_pattern names {len(pattern)} layers, "
            f"num_layers is {cfg.num_layers} (no depth cut of a pattern "
            "without a period)")
    unknown = sorted(set(pattern) - set(PATTERN_KINDS))
    if unknown:
        raise NotImplementedError(
            f"hybrid_override_pattern characters {unknown}: a layer of "
            f"that kind is not written here ({sorted(PATTERN_KINDS)} are)")
    return tuple(LayerPlan(*PATTERN_KINDS[ch], i)
                 for i, ch in enumerate(pattern))


def one_sublayer(cfg) -> bool:
    """Every layer is ONE norm, one sublayer and one residual (a published
    ``hybrid_override_pattern``): the tree holds ``norm`` [L, d] in place
    of ``attn_norm`` and ``mlp_norm``."""
    return bool(cfg.hybrid_override_pattern)


# a published ``layer_types`` entry -> the mixer that runs it
LAYER_TYPES = {"full_attention": "gqa", "sliding_attention": "gqa_window"}
# a published ``mixer_types`` entry -> the mixer that runs it
MIXER_TYPES = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def is_uniform(cfg) -> bool:
    """Every layer alike and ``gqa``, run once a token between two norms a
    layer: the stacked-scan decoder."""
    return (not cfg.layer_group_size and not cfg.kv_lora_rank
            and not cfg.cca_time0 and not cfg.mb_per_layer
            and not cfg.layer_types and not cfg.mixer_types
            and not cfg.hybrid_override_pattern
            and not (cfg.num_experts and cfg.first_k_dense_replace)
            and cfg.ut_steps == 1 and not cfg.sandwich_norm)


def passes(cfg) -> int:
    """Times a token runs the plan (a looped model's ``ut_steps``; 1 for
    every other), and so the passes' worth of keys a token keeps in a paged
    layer's pages (module docstring): ``layer_cache`` sets ``Paged.passes``
    from it, and ``paged_bytes_per_token`` and ``make_pools`` follow."""
    return cfg.ut_steps


def pass_offset(cfg, pool, t):
    """What pass ``t`` adds to a logical page's number to find its own
    page in ``pool`` (an array of ``make_pools``): the pool is ``passes``
    runs of the engine's ``num_pages`` pages, pass ``t``'s run the
    ``t``-th, so logical page 0 is pass ``t``'s null page there."""
    return t * (pool.shape[1] // passes(cfg))


def published_depth(cfg) -> int:
    """Layers of the published model, of which ``num_layers`` run here: a
    per-layer list's length where the configuration has one."""
    per_layer = cfg.mixer_types or cfg.layer_types
    return len(per_layer) if per_layer else cfg.num_layers


def experts_held(cfg) -> tuple[int, int]:
    """(first, count) of the routed experts a layer holds here, of the
    ``num_experts`` it routes over: all of them unless the configuration
    names a share."""
    return cfg.experts_held or (0, cfg.num_experts)


def kda_dims(cfg) -> tuple[int, int, int]:
    """(heads, key size, value size) of a KDA layer."""
    return cfg.num_heads, cfg.head_dim_, cfg.head_dim_


def latent_width(cfg) -> int:
    """Values a token's latent row holds: the normed latent and the
    shared rope key."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


LANES = 128


def latent_row(cfg) -> int:
    """Columns a latent row takes in its pool: ``latent_width`` rounded up
    to whole lanes, the rest zero. The chip lays a minor dimension out in
    tiles of 128 whatever its logical size, and a kernel's DMA can slice
    a pool only at tile boundaries, so the pad is stated, not hidden."""
    return -(-latent_width(cfg) // LANES) * LANES


def cca_dims(cfg) -> tuple[int, int, int]:
    """(query heads, key/value heads, head size) of a CCA layer: the query
    latent is ``Hq * D`` wide, the key latent ``Hkv * D``, a value half
    ``Hkv * D / 2``."""
    return cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_


def ssm_dims(cfg) -> tuple[int, int, int, int]:
    """(inner width, state size, convolution taps, rank of dt) of a Mamba
    layer: ``expand * hidden``, and a sixteenth of the hidden size where
    the configuration names no rank."""
    return (cfg.ssm_expand * cfg.hidden_size, cfg.ssm_state_size,
            cfg.ssm_conv_kernel, cfg.ssm_dt_rank or cfg.hidden_size // 16)


def diff_dims(cfg) -> tuple[int, int, int]:
    """(differential heads, K/V pairs, a pair's width) of a differential
    attention layer: adjacent query heads and adjacent K/V heads paired,
    a pair's two heads kept side by side."""
    return cfg.num_heads // 2, cfg.num_kv_heads // 2, 2 * cfg.head_dim_


def gqa_heads(cfg, p: LayerPlan) -> int:
    """Query heads of a ``gqa`` or ``gqa_window`` layer: the published
    ``num_attention_heads_per_layer`` entry, the model's count without."""
    per_layer = cfg.num_heads_per_layer
    return per_layer[p.published] if per_layer else cfg.num_heads


def mamba2_dims(cfg) -> tuple[int, int, int, int, int]:
    """(heads H, head size P, groups G, state size N, convolution taps K)
    of a Mamba-2 layer; its inner width is ``H * P`` and its convolution
    runs over ``H * P + 2 * G * N`` channels (x | B | C)."""
    return (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.ssm_state_size, cfg.ssm_conv_kernel)


def gqa_rope(cfg, p: LayerPlan):
    """The rope of a ``gqa`` or ``gqa_window`` layer
    (``decoder.RopeParameters``): the published ``rope_parameters`` block
    of its layer type, the model's own rope without; None for attention
    WITHOUT positions (``attn_no_rope``)."""
    if cfg.attn_no_rope:
        return None
    return cfg.rope_of(cfg.layer_types[p.published] if cfg.layer_types
                       else None)


def layer_cache(cfg, plan: LayerPlan, dtype=None
                ) -> Paged | Slot | PagedAndSlot | Ring | Reads | None:
    """What a sequence keeps for a layer: its mixer's record says
    (``models/mixers``; imported here, not above: the records are built
    from this module's types)."""
    from polyrl_tpu.models.mixers import MIXERS

    if plan.mixer is None:
        return None
    c = MIXERS[plan.mixer].cache(cfg, plan, dtype or cfg.dtype)
    if passes(cfg) == 1:
        return c
    if not isinstance(c, Paged):
        raise NotImplementedError(
            f"a {plan.mixer} layer run {passes(cfg)} times a token: only "
            "what lies in pages alone is kept a pass")
    return dataclasses.replace(c, passes=passes(cfg))


def cache_spec(cfg, dtype=None) -> tuple:
    return tuple(layer_cache(cfg, p, dtype) for p in layer_plan(cfg))


def is_stateful(cfg) -> bool:
    """Some layer keeps a state that is not paged."""
    return any(slot_part(c) is not None for c in cache_spec(cfg))


# features of the engine that act on pages through a kernel (or a copy)
# written for one kind of paged cache, and the mixers that have it
# (``cca`` keeps a K/V pair too, and has none of the three: each re-enters
# a sequence where its tails are not; the SambaY family's kinds have none
# either: its one K/V pair holds two heads side by side under queries that
# are half zero, and a scan's state and a ring have no page to share,
# verify over or spill). The kernels are the stacked-scan decoder's: a
# model of several kinds of layer has none of the three, ``gqa`` layers or
# not)
FEATURE_KERNELS = {
    "decode_group_share": ("gqa",),   # ops.paged_attention's grouped kernel
    "spec_tokens": ("gqa",),          # the multi-token verify forward
    "kv_spill": ("gqa",),             # rollout/kvspill.py copies a K/V pair
}


def without_kernel(cfg, feature: str) -> tuple[str, ...]:
    """The mixers of this model's layers for which the engine's
    ``feature`` has no kernel: empty where it may run."""
    have = FEATURE_KERNELS[feature] if is_uniform(cfg) else ()
    return tuple(sorted({p.mixer for p in layer_plan(cfg) if p.mixer}
                        - set(have)))


def paged_bytes_per_token(cfg, dtype=None) -> int:
    item = jnp.dtype(dtype or cfg.dtype).itemsize
    return sum(paged_part(c).bytes_per_token(item)
               for c in cache_spec(cfg, dtype) if paged_part(c) is not None)


def slot_bytes(cfg, dtype=None) -> int:
    """Bytes one slot's state takes, all layers."""
    return sum(slot_part(c).bytes_per_slot()
               for c in cache_spec(cfg, dtype) if slot_part(c) is not None)


def pool_index(cfg) -> tuple[tuple[int | None, int | None], ...]:
    """For each layer: (its place among the layers that keep pages, its
    place among the layers that keep a slot), None where it keeps none:
    the indices into ``make_pools``' two tuples. A layer that reads
    another layer's pages (``Reads``) is handed that layer's place."""
    spec = cache_spec(cfg)
    out, n_paged, n_slot = [], 0, 0
    for c in spec:
        has_p, has_s = paged_part(c) is not None, slot_part(c) is not None
        out.append((n_paged if has_p else None, n_slot if has_s else None))
        n_paged, n_slot = n_paged + has_p, n_slot + has_s
    return tuple((out[c.layer][0], None) if isinstance(c, Reads) else o
                 for o, c in zip(out, spec))


def make_pools(cfg, num_pages: int, page_size: int, slots: int = 0,
               dtype=None) -> tuple:
    """The arrays. For the uniform ``gqa`` pattern ``(k, v)``, each a
    per-layer tuple of ``[Hkv, num_pages, page_size, D]`` (page 0 is the
    null page). For any other pattern ``(paged, state)``: ``paged`` a tuple
    with one entry for each layer that keeps pages, in order (a ``[1,
    num_pages, page_size, width]`` latent pool for ``mla``, a ``(k, v)``
    pair of ``[Hkv, num_pages, page_size, D]`` for ``cca``, and for
    ``sparse`` with the pooled keys ``[num_pages, Hkv * page_size / stride,
    D]`` float32 as its third), ``state`` a
    tuple with one tuple of ``[slots, *shape]`` arrays for each layer that
    keeps a slot, in order (``(state [slots, H, Dk, Dv] float32, conv
    [slots, K-1, channels])`` for ``kda``; the three tails for ``cca``;
    a ``sparse`` layer's ``picked [slots, Hkv, W + 1]`` int32;
    ``(state [slots, N, inner] float32, conv [slots, K-1, inner])`` for
    ``ssm``; a ``swa`` layer's ring, a ``(k, v)`` pair of ``[pairs, 1 +
    slots * window / page_size, page_size, width]`` whose pages are the
    slot's (``Ring``);
    none for a model that is ``mla`` in every layer). A ``cca`` layer has
    an entry in both (``pool_index``). The
    engine hands ``slots = max_slots + 1``: the last row is the sink that padding rows of an admission wave write to."""
    dtype = dtype or cfg.dtype
    spec = cache_spec(cfg, dtype)
    if is_uniform(cfg):
        shape = (spec[0].heads, num_pages, page_size, spec[0].width)
        return (tuple(jnp.zeros(shape, dtype) for _ in spec),
                tuple(jnp.zeros(shape, dtype) for _ in spec))
    paged, state = [], []
    for c in spec:
        pages, slot = paged_part(c), slot_part(c)
        if pages is not None:
            pool = [jnp.zeros((pages.heads, pages.passes * num_pages,
                               page_size, pages.width), dtype)
                    for _ in range(pages.arrays)]
            if pages.pooled:
                if page_size % pages.pooled:
                    raise ValueError(f"a pooled row every {pages.pooled} "
                                     f"tokens in pages of {page_size}")
                pool.append(jnp.zeros(
                    (num_pages, pages.heads * (page_size // pages.pooled),
                     pages.width), POOLED_DTYPE))
            paged.append(pool[0] if len(pool) == 1 else tuple(pool))
        if isinstance(slot, Ring):
            if slot.window % page_size:
                raise ValueError(f"a window of {slot.window} keys in pages "
                                 f"of {page_size}")
            ring = (slot.heads, 1 + slots * (slot.window // page_size),
                    page_size, slot.width)
            state.append((jnp.zeros(ring, slot.dtype),
                          jnp.zeros(ring, slot.dtype)))
        elif slot is not None:
            state.append(tuple(jnp.zeros((slots, *shape), dt)
                               for _name, shape, dt in slot.arrays))
    return tuple(paged), tuple(state)
