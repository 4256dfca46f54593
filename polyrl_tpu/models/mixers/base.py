"""What a kind of mixer is to the rest of the tree: ONE record (``Mixer``)
that answers, for a mixer's name, everything ``models/hybrid.py``,
``cache_spec.layer_cache`` and ``CBEngine`` ask about it, and what its two
forms are handed and hand back (``Chunk``, ``Step``, ``Kept``). The helpers
that more than one family uses are here too."""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.models import scopes

L2_EPS = 1e-6


class Kept(NamedTuple):
    """What a form hands back beside its output. A sequence form: what
    the chunk's tokens keep in the layer's pages (for the caller to
    scatter) and the batch's slot rows after the last valid token; a
    one-token form: the layer's pool and its whole slot arrays with the
    token in. ``hands``: what later layers of the same call read, by name
    (``Chunk.hands``)."""
    pages: object = None
    slot: object = None
    hands: dict = {}


@dataclasses.dataclass(frozen=True)
class Chunk:
    """What a sequence form is handed beside its weights and the normed
    input ``[B, T, d]``."""
    positions: jax.Array          # [B, T]
    valid: jax.Array              # [B, T], padding on the right
    # the slot's rows at the chunk's start, as ``Mixer.read_slot`` gave
    # them (zeros before a sequence's first token; a ring: None for none)
    state: object
    # (what the layer's pages hold of the tokens before the chunk [B, Tp,
    # ..], how many of them are real [B]); None for none
    prefix: object
    # what layers before this one of the same call handed on, each under a
    # name of its own: ``latent`` (the router's, [B, T, R] float32 or None;
    # ``hybrid._mlp`` reads and renews it), ``m`` (the ``ssm_mem`` layer's
    # scan output before its gate), ``kv`` (the ``diff`` layer's keys,
    # values and their positions, those before the chunk among them)
    hands: dict


class Load:
    """The decode step's load vector while the layers count into it: one
    int32 entry a name of ``names`` (``hybrid.load_names``). An entry moves
    where its layer's form says so, among that layer's operations (the
    vector is an output of the step, and the order of a program's
    operations is part of its text)."""

    def __init__(self, names: tuple):
        self.names = names
        with jax.named_scope("glue"):
            self.vector = jnp.zeros((len(names),), jnp.int32)

    def add(self, name: str, amount) -> None:
        """Called between a form's scopes, never inside one: the count is
        the step's bookkeeping (``glue``), not the layer's work."""
        with jax.named_scope("glue"):
            self.vector = self.vector.at[self.names.index(name)].add(amount)


@dataclasses.dataclass(frozen=True)
class Step:
    """What a one-token form is handed beside its weights and the normed
    input ``[S, d]``: the step's rows, and (from ``pages`` down) the
    layer's own."""
    positions: jax.Array          # [S]
    seq_lens: jax.Array           # [S]: tokens a row has cached
    live: jax.Array               # [S]: the row has a request
    page_table: jax.Array         # [S, n]
    page_size: int
    # where a live row's token goes in a paged pool (the null page for
    # the rest), and the keys a row attends over, its own among them
    write_page: jax.Array
    write_off: jax.Array
    attn_lens: jax.Array
    n_live: jax.Array             # scalars: live rows, the sum of attn_lens
    rows_read: jax.Array
    load: Load
    # what ``Mixer.per_step`` of the plan's kinds worked out once for the
    # step, by the mixer's name
    per: dict
    pages: object = None          # the layer's pool (a ``Reads`` layer: the
    slot: object = None           # producer's), its slot arrays, whole
    stack: dict | None = None     # the stack its weights are rows of, and
    index: int = 0                # its row: a kernel reads a layer in place
    hands: dict = dataclasses.field(default_factory=dict)   # as ``Chunk``'s


class SlotRows(NamedTuple):
    """Where a prefill chunk's rows lie: for ``read_slot``/``write_slot``."""
    slots: jax.Array              # [B]
    prefix_len: jax.Array         # a scalar: tokens before the chunk
    lens: jax.Array               # [B]: the chunk's real tokens
    fresh: jax.Array              # prefix_len == 0


def read_rows(cfg, arrays, at: SlotRows):
    """The slots' rows of a layer's slot arrays at a chunk's start: zeros
    for a slot's first chunk, whatever the last request left there."""
    return tuple(jnp.where(at.fresh, jnp.zeros((), a.dtype), a[at.slots])
                 for a in arrays)


def write_rows(cfg, arrays, at: SlotRows, new, was):
    return tuple(a.at[at.slots].set(a1.astype(a.dtype))
                 for a, a1 in zip(arrays, new))


@dataclasses.dataclass(frozen=True)
class Mixer:
    """One kind of mixer. ``cfg`` is a ``decoder.ModelConfig``, ``p`` the
    layer's ``cache_spec.LayerPlan``, ``lp`` its weights (a row of the
    stack), ``h_in`` the normed input."""
    name: str
    # (cfg, p, dtype) -> what a sequence keeps for the layer
    cache: Callable
    # the stack of ``params["layers"]`` its weights lie in: kinds of one
    # shape share a stack, and its ``init``
    stack: str = ""
    # (cfg, layers of the stack, draw) -> {stack: weights, and what the
    # family keeps beside the stacks}, drawn in this order
    # (``hybrid.init_params``)
    init: Callable | None = None
    # of its matrices [L, in, out]: those sharded (tp, fsdp), the rest
    # being (fsdp, tp), and those whose columns stay whole (fsdp, None)
    row_parallel: tuple = ()
    replicated: tuple = ()
    # (cfg, p, lp, h_in [B, T, d], Chunk) -> (out [B, T, d], Kept)
    sequence: Callable | None = None
    # (cfg, p, lp, h_in [S, d], Step) -> (out [S, d], Kept)
    step: Callable | None = None
    # (cfg, Step) -> what its layers share of one step (``Step.per``)
    per_step: Callable | None = None
    # the scopes its pages are gathered and written under in prefill, and
    # its slot read and written back under: leaves that
    # ``models/scopes.py`` declares, as every scope its forms open is
    pages_scope: str = ""
    slot_scope: str = ""
    # a prefill chunk moves its K/V pair's pages as slabs of the pool's
    # ``[H N, ps, w]`` view (``blocks._gather_slabs_kv``, ``_scatter_slabs``)
    # and not as rows of its ``[H N, ps w]`` view (``hybrid._gather_kv``,
    # ``_scatter_kv``): a pool of eight or ten heads by rows XLA lays out
    # anew, 1.7-2.7 GB of temporaries beside a 900 MB pool that the chip's
    # compiler refused. The CCA model's programs are the rows' and are kept
    # to the byte (an accepted benchmark cell's): one form for all is for
    # the PR that can measure that cell (ROADMAP D9 (3))
    pages_by_slabs: bool = False
    # a kind whose pages hold more than what ``hybrid._scatter_chunk``
    # writes: (cfg, pool, prefix_page_ids, page_ids, what its sequence form
    # kept, tokens before the chunk) -> pool
    scatter: Callable | None = None
    # its slot around a prefill chunk: (cfg, arrays, SlotRows) -> rows;
    # (cfg, arrays, SlotRows, new rows, rows read) -> arrays
    read_slot: Callable = read_rows
    write_slot: Callable = write_rows
    # (cfg, arrays, slot) -> what ``CBEngine.recurrent_state`` reads of its
    # slot, on the host (float32; a ``sparse`` layer's table int32)
    held: Callable | None = None
    # the entries of the step's load vector its one-token form moves, by
    # their ``server_info`` names
    counts: tuple = ()
    # its entries stand in EVERY routed model's vector, zero where the plan
    # has no such layer: the accepted cells' vectors were laid out so
    # before entries followed the plan, and a vector's width is part of
    # its program's bytes (ROADMAP D6)
    counts_in_routed: bool = False
    # (its share counter in ``engine_profile.CUMULATIVE_KEYS``, (cfg, rows)
    # -> whether a decode step of ``rows`` rows takes its kernel)
    kernel: tuple | None = None

    def __post_init__(self):
        for name in (self.pages_scope, self.slot_scope):
            if name:
                scopes.declared(name)


def yarn_inv_freq(theta: float, r: int, s) -> np.ndarray:
    """The ``r / 2`` frequencies of a rope over ``r`` columns, float64:
    ``theta ** (-2i / r)``, under YaRN (``s``: a ``decoder.RopeScaling``,
    DeepSeek-V3's reading) divided by ``factor`` from the dimension up at
    which ``original_max_position_embeddings`` positions make ``beta_slow``
    turns (rounded up), kept below the one at which they make
    ``beta_fast`` (rounded down), blended linearly between."""
    inv = 1.0 / (theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    if s is None:
        return inv
    if s.rope_type != "yarn":
        raise NotImplementedError(
            f"rope scaling {s.rope_type!r} in a model of several kinds of "
            "layer (yarn only)")

    def dim_of(turns: float) -> float:
        return (r * math.log(s.original_max_position_embeddings
                             / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(s.beta_fast)), 0)
    high = min(math.ceil(dim_of(s.beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inv / s.factor * ramp + inv * (1 - ramp)


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_amplitude(s) -> float:
    """What YaRN multiplies cos and sin by: 1 without it; the published
    ``attention_factor`` where the configuration has one; else
    ``mscale``'s factor over ``mscale_all_dim``'s (1 where they are
    equal)."""
    if s is None or s.rope_type != "yarn":
        return 1.0
    if s.attention_factor:
        return s.attention_factor
    return (yarn_mscale(s.factor, s.mscale)
            / yarn_mscale(s.factor, s.mscale_all_dim))


def rope_partial(x, positions, inv_freq, amplitude: float = 1.0):
    """Rope on the first ``2 * len(inv_freq)`` columns of each head of
    ``x`` [..., T, H, D] float32 at ``positions`` [..., T] (rotate-half
    within them: columns ``i`` and ``i + len(inv_freq)`` are a pair), the
    rest as they are; cos and sin times ``amplitude`` (YaRN's)."""
    half = len(inv_freq)
    ang = positions.astype(jnp.float32)[..., None, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], axis=-1)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def tail_after(full, n_valid, k: int):
    """Rows ``n_valid .. n_valid + k - 1`` of each ``[tail | chunk]``
    sequence ``full`` [B, k + T, C]: the convolution tail after a chunk's
    last valid position (``n_valid`` [B])."""
    return jax.vmap(lambda f, s: jax.lax.dynamic_slice_in_dim(
        f, s, k, 0))(full, n_valid)


def set_rows(whole, rows):
    """``whole`` with its leading rows replaced by ``rows``."""
    if whole.shape[0] == rows.shape[0]:
        return rows
    return jax.lax.dynamic_update_slice_in_dim(whole, rows, 0, 0)


def shift_tail(tail, window, live):
    """A convolution tail ``tail`` [slots, K-1, C] after a decode step:
    the live rows' ``window`` [S, K, C] (their tail and the new token)
    less its oldest row, the rest as they were."""
    return set_rows(tail, jnp.where(
        live[:, None, None], window[:, 1:].astype(tail.dtype),
        tail[:window.shape[0]]))


# float32 bytes the scores of one block of keys may take against all the
# queries of a call, and the fewest keys a block holds
_SCORE_BYTES = 128 << 20
_MIN_KEY_BLOCK = 128


def key_block(cfg, b: int, t: int, heads: int = 0) -> int:
    """Keys a block of ``mla_expanded``, ``diff_attention`` or
    ``gqa_attention`` holds for ``b`` rows of ``t`` queries at ``heads``
    heads (the model's without): what keeps the [B, H, T, block] float32
    scores within ``_SCORE_BYTES``, in whole multiples of
    ``_MIN_KEY_BLOCK`` (at 128 heads and a 512-token chunk: 512 keys; at
    32 heads: 2048)."""
    fit = _SCORE_BYTES // (4 * b * (heads or cfg.num_heads) * t)
    return max(_MIN_KEY_BLOCK, fit // _MIN_KEY_BLOCK * _MIN_KEY_BLOCK)
