"""The two products of a decode step's latent attention with ``wkv_b``, read
from the stacked parameter where it lies: ``absorb`` folds a layer's key
half into the queries (``q_nope`` [S, H, nope] -> [S, H, rank]),
``unabsorb`` applies its value half to the attention's output over the
latent rows ([S, H, rank] -> [S, H, v] float32). What they compute is
``hybrid.mla_absorb``'s and ``hybrid.mla_unabsorb``'s einsum, which stay
the oracle.

Why a kernel: ``wkv_b`` is stored as published, ``[L, rank, H * (nope +
v)]``, head ``h``'s key columns at ``h * (nope + v)`` and its value columns
``nope`` further on. A product batched over heads wants the heads major, so
XLA writes layer ``l``'s slice out anew (33.5 MB at 128 heads) and a copy
of it with the heads in front, every layer of every step. Here a grid
step's weights are ``[rank, nope]`` (or ``[rank, v]``) windows of the STACK,
one a head, picked by the block's index map at the static layer: whole lane
tiles of the published layout, so only the half a product multiplies is
read, once, and nothing of a layer's weights is written.

A head's product is one MXU matmul with float32 accumulation: ``q_h
[S, nope] x wk_h [rank, nope]^T`` and ``o_h [S, rank] x wv_h [rank, v]``;
``absorb`` rounds to the queries' dtype (what the oracle does before it
lays the rope part beside). The activations arrive as they are, ``[S, H,
n]``, a grid step's block ``[S, heads, n]`` and a head's rows gathered in
VMEM (``x_ref[:, h, :]``); the products leave as ``[S, H * n]``, a head a
window of whole lane tiles, which is what ``wo`` multiplies.

``accepts`` says which shapes the kernels take: ``nope``, ``v`` and
``rank`` whole lane tiles with ``nope == v`` (so that both halves start at
whole blocks), a count of heads a step that ``_heads_per_block`` finds from
the static shapes. ``in_kernel`` adds the backend. Everything else takes
the einsum. Neither notes anything in ``ops/dispatch.py``: what says that
the kernels ran is the engine's ``mla_proj_kernel_steps`` and their own
events, ``mla_absorb`` and ``mla_unabsorb``, in a device trace.

What bounds them, alone at ``dots.vlm1``'s shapes (65 rows, 128 heads, a
stack of five, both products of all five layers in one program on one v5e;
``tools/bench_mla_proj.py``; my chip runs, PR 42; the least is the stack
read once, 0.205 ms at 819 GB/s): the einsum's program 1.06-1.19 ms; these
kernels 0.286 ms at 8 heads a grid step and 0.291 at 16 (72 and 70% of the
bandwidth: 0.126 + 0.160; bit-equal to the einsum). With the activations
handed over flat, ``[S, H * n]``, the kernels alone read 0.246 (83%: 0.123
each; 0.252 at 16 heads, 0.282 at 4), but XLA then lays ``o_latent`` out
anew between the attention kernel and ``unabsorb`` (9.4 MB a layer through
a padded copy, 0.19 ms a step in the cell's program), so the gather in
VMEM, 0.04 ms a step dearer, is ahead: the cell's step fell 17.62 ->
17.41 ms. The same trade for ``absorb``'s OUTPUT does not pay (stored head
by head, ``out_ref[:, h, :]``, the kernel reads 0.234 for 0.123; the copy
and slice it saves are 0.065 ms a step). A form that streams BOTH halves
of a step's heads as one ``[rank, heads * (nope + v)]`` block reads twice
the bytes in twice the time (0.455). At Ling's shapes (129 rows, 32 heads,
a stack of ONE, where the einsum needs no slice) the kernels take 0.023 ms
and their program 0.052 against the einsum's 0.036: 0.016 ms of a 20 ms
step the other way. Neither the block size nor the flat form is worth a
knob.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# a grid step's windows of the weights, all heads of the step together
_BLOCK_BYTES = 2 * 2**20
# of v5e's 16 MiB of scoped VMEM: the weights' and the activations' blocks
# twice (the pipeline's two buffers) and a head's float32 product
_VMEM_BYTES = 12 * 2**20


def _heads_per_block(h: int, rows: int, rank: int, n: int,
                     itemsize: int) -> int | None:
    """Heads a grid step multiplies: the most that divide ``h``, are whole
    sublane tiles of the activations' ``[S, heads, n]`` blocks (or all of
    ``h``), whose weight windows fit ``_BLOCK_BYTES`` and whose blocks, in
    and out, twice over, fit ``_VMEM_BYTES`` (the wider activation counted
    at float32); None where no count does."""
    for hb in range(h, 0, -1):
        weights = hb * rank * n * itemsize
        acts = hb * rows * (n * itemsize + rank * 4)
        if (h % hb == 0 and (hb == h or hb % _SUBLANES == 0)
                and weights <= _BLOCK_BYTES
                and 2 * (weights + acts) + rows * rank * 4 <= _VMEM_BYTES):
            return hb
    return None


def accepts(cfg, rows: int) -> bool:
    """Whether the kernels take this configuration's ``wkv_b`` at ``rows``
    rows a step. The backend is ``in_kernel``'s to ask."""
    r, nope, v = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    return bool(r and nope == v and nope % _LANES == 0 and r % _LANES == 0
                and _heads_per_block(cfg.num_heads, rows, r, nope,
                                     jnp.dtype(cfg.dtype).itemsize))


def in_kernel(cfg, rows: int) -> bool:
    """Whether ``hybrid.paged_decode``'s MLA layers run the kernels here."""
    return jax.default_backend() == "tpu" and accepts(cfg, rows)


def _kernel(x_ref, *refs, hb: int, dims):
    # x [S, hb, n_in]; hb windows of the weights; out [S, hb * n_out]
    out_ref = refs[hb]
    n_out = out_ref.shape[1] // hb
    for h in range(hb):
        out_ref[:, h * n_out:(h + 1) * n_out] = jax.lax.dot_general(
            x_ref[:, h, :], refs[h][...], (dims, ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT).astype(out_ref.dtype)


def _call(name: str, x, stack, layer: int, half: int, dims, n_out: int,
          out_dtype, hb: int | None, interpret: bool):
    """``x`` [S, H, n_in] against the ``half``-th ``[rank, n]`` window of
    every head of ``stack[layer]``, contracted over ``dims`` -> [S, H,
    n_out]."""
    s, h, _n_in = x.shape
    _layers, rank, cols = stack.shape
    n = cols // h // 2
    hb = hb or _heads_per_block(h, s, rank, n, stack.dtype.itemsize)

    def window(i):
        return pl.BlockSpec((None, rank, n),
                            lambda j: (layer, 0, 2 * (j * hb + i) + half))

    out = pl.pallas_call(
        functools.partial(_kernel, hb=hb, dims=dims),
        out_shape=jax.ShapeDtypeStruct((s, h * n_out), out_dtype),
        grid=(h // hb,),
        in_specs=[pl.BlockSpec((s, hb, x.shape[2]), lambda j: (0, j, 0)),
                  *[window(i) for i in range(hb)]],
        out_specs=pl.BlockSpec((s, hb * n_out), lambda j: (0, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES + 2 * 2**20),
        name=name, interpret=interpret,
    )(x, *[stack] * hb)
    return out.reshape(s, h, n_out)


@functools.partial(jax.jit, static_argnames=("layer", "hb", "interpret"))
def absorb(q_nope, wkv_b, layer: int, hb: int | None = None,
           interpret: bool = False):
    """``q_nope`` [S, H, nope] through the key half of ``wkv_b[layer]``
    (``wkv_b`` the stack [L, rank, H * (nope + v)]) -> [S, H, rank] in
    ``q_nope``'s dtype: ``q_h [S, nope] x wk_h [rank, nope]^T`` a head.
    ``hb`` is ``_heads_per_block``'s answer unless a test or
    ``tools/bench_mla_proj.py`` hands it another."""
    return _call("mla_absorb", q_nope, wkv_b, layer, 0, ((1,), (1,)),
                 wkv_b.shape[1], q_nope.dtype, hb, interpret)


@functools.partial(jax.jit, static_argnames=("layer", "hb", "interpret"))
def unabsorb(o_latent, wkv_b, layer: int, hb: int | None = None,
             interpret: bool = False):
    """``o_latent`` [S, H, rank] through the value half of
    ``wkv_b[layer]`` -> [S, H, v] float32: ``o_h [S, rank] x wv_h [rank,
    v]`` a head."""
    h = o_latent.shape[1]
    return _call("mla_unabsorb", o_latent.astype(wkv_b.dtype), wkv_b, layer,
                 1, ((1,), (0,)), wkv_b.shape[2] // h // 2, jnp.float32, hb,
                 interpret)
