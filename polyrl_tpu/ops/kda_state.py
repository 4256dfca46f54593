"""One position of the KDA recurrence over the decode step's rows, in one
pass over the state and in place: what ``hybrid.kda_recurrent_step``
computes, for the leading ``S`` rows of a state stack ``[slots, H, Dk,
Dv]`` float32.

``kda_state_update`` is the dispatcher: on a TPU, for a float32 state
whose ``Dk`` and ``Dv`` are whole lane tiles, the kernel below; elsewhere
(and for every other shape) the oracle on ``state[:S]``, the rows without
a request kept by a ``where``, written back. It notes nothing in
``ops/dispatch.py``: what says that the kernel ran is the engine's
``kda_kernel_steps`` and the kernel's own event, ``kda_state``, in a
device trace.

The kernel: a grid step brings a block of one row's heads into VMEM
(``_heads_per_block``: the whole row where it fits 2 MiB, which Ling's 32
heads of 128 x 128 do), and a head at a time decays it along ``Dk``,
reduces ``pred = dec^T k`` over the sublanes, forms ``u = beta (v -
pred)``, adds ``k u^T``, reduces ``o = new^T q`` and stores the head. The
state is the call's input AND output (``input_output_aliases``): one HBM
read and one HBM write of each visited row, rows past ``S`` never touched,
no copy where the caller donates the stack. Everything float32 on the VPU,
the products and their order the oracle's; only the sums over ``Dk`` run
in another order (sixteen vregs added, then the eight sublanes folded).

``k``, ``q`` and ``exp(g)`` multiply along ``Dk``, the state's sublane
axis, so each must spread over ``Dv`` by a lane broadcast. They reach the
kernel as they are, ``[S, H, Dk]`` (as ``[S, H, Dk, 1]`` each would pad to
the size of the state in HBM); a grid step takes the exponential of its
``[heads, Dk]`` block of ``g`` and transposes the three blocks once, and a
head's column is a lane slice of the result. ``v`` and ``beta`` lie along
lanes; ``beta`` comes spread over ``Dv``. A row without a request is
handed ``g = 0`` and ``beta = 0`` and keeps its state: ``x * 1 + k * 0``.

What bounds it, kernel alone at Ling's shapes (six stacks of 129 x 32 x
128 x 128 in one donated program on one v5e; my chip runs, PR 40; the
least is 3.96 ms at 819 GB/s): the oracle's two XLA passes 7.17 ms; this
kernel 5.02 (4.96 with the columns laid out by XLA beforehand, which cost
the program 0.19 ms more than it saved the kernel), and the SAME for a
kernel that only copies its block (4.95), for XLA's own in-place ``state *
c`` (4.91), under a hand-written ring of three to eight blocks in flight
(4.89-4.95) and at 16 or 8 heads a grid step (4.97, 5.04). So a read and
a write of the same bytes run at 80% of the chip's bandwidth whatever
issues them, the arithmetic (3,385 bundles a row: three lane broadcasts
and seven VPU operations a state vreg) hides behind the transfers, and
neither block size nor buffers are worth a knob.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# a grid step's block of state, each way: a whole row's heads at Ling's
# sizes. PR 36's finding on this chip holds here: under a MiB a step the
# DMAs' queue and a grid step's own cost show beside the transfer
_BLOCK_BYTES = 2 * 2**20
# state in and out, double-buffered, is four blocks; the rest is the
# small operands, their transposes and what a head's sixteen vregs spill
_VMEM_LIMIT = 6 * _BLOCK_BYTES


def _heads_per_block(h: int, dk: int, dv: int) -> int | None:
    """Heads of one row a grid step updates: the most that divide ``h``,
    fit ``_BLOCK_BYTES`` and are whole sublane tiles of the ``[H, Dv]``
    rows beside the state (or all of ``h``); None where no count does."""
    fits = _BLOCK_BYTES // (dk * dv * 4)
    for hb in range(min(h, fits), 0, -1):
        if h % hb == 0 and (hb == h or hb % 8 == 0):
            return hb
    return None


def accepts(state_shape, dtype) -> bool:
    """Whether the kernel takes a state stack of this shape and dtype:
    float32, ``Dk`` and ``Dv`` whole lane tiles, a block of heads that
    fits. The backend is the dispatcher's to ask."""
    _slots, h, dk, dv = state_shape
    return (dtype == jnp.float32 and dk % _LANES == 0 and dv % _LANES == 0
            and _heads_per_block(h, dk, dv) is not None)


def in_kernel(state_shape, dtype) -> bool:
    """Whether ``kda_state_update`` runs the kernel for this state here."""
    return jax.default_backend() == "tpu" and accepts(state_shape, dtype)


def _kernel(k_ref, q_ref, g_ref,   # [1, hb, Dk] each
            v_ref,       # [1, hb, Dv]
            beta_ref,    # [1, hb, Dv]: beta spread over the lanes
            state_ref,   # [1, hb, Dk, Dv]
            new_ref,     # the same block of the same array
            o_ref,       # [1, hb, Dv]
            ):
    # [Dk, hb]: a head's column spreads over Dv by a lane broadcast
    k_cols, q_cols = k_ref[0].T, q_ref[0].T
    decay_cols = jnp.exp(g_ref[0]).T
    for h in range(k_cols.shape[1]):
        k = k_cols[:, h:h + 1]                            # [Dk, 1]
        dec = state_ref[0, h] * decay_cols[:, h:h + 1]    # [Dk, Dv]
        pred = jnp.sum(dec * k, axis=0, keepdims=True)    # [1, Dv]
        u = beta_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - pred)
        new = dec + k * u
        new_ref[0, h] = new
        o_ref[0, h:h + 1, :] = jnp.sum(new * q_cols[:, h:h + 1], axis=0,
                                       keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "hb"))
def kda_state_pallas(state, q, k, v, g, beta, interpret: bool = False,
                     hb: int | None = None):
    """The kernel: ``state`` [slots, H, Dk, Dv] float32 with its leading
    ``S`` rows updated in place, and ``o`` [S, H, Dv]; ``q k g`` [S, H,
    Dk], ``v`` [S, H, Dv], ``beta`` [S, H], all float32. ``hb`` is
    ``_heads_per_block``'s answer for the shapes unless a test or
    ``tools/bench_kda_state.py`` hands it another."""
    s, h, dk = k.shape
    dv = v.shape[-1]
    hb = hb or _heads_per_block(h, dk, dv)
    cols = pl.BlockSpec((1, hb, dk), lambda i, j: (i, j, 0))
    rows = pl.BlockSpec((1, hb, dv), lambda i, j: (i, j, 0))
    block = pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0))
    new, o = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((s, h, dv), jnp.float32)),
        grid=(s, h // hb),
        in_specs=[cols, cols, cols, rows, rows, block],
        out_specs=(block, rows),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="kda_state", interpret=interpret,
    )(k, q, g, v, jnp.broadcast_to(beta[..., None], v.shape), state)
    return new, o


def kda_state_update(state, q, k, v, g, beta, live):
    """``state`` [slots, H, Dk, Dv] with its leading ``S`` rows advanced
    one position where ``live`` [S] says so and kept where not, and ``o``
    [S, H, Dv] float32 (a kept row's is not for use): the kernel where
    ``in_kernel`` says so (interpreted off a TPU: tests alone get there),
    else ``hybrid.kda_recurrent_step`` on those rows, a ``where`` and the
    write-back."""
    s = k.shape[0]
    if in_kernel(state.shape, state.dtype):
        return kda_state_pallas(
            state, q, k, v, jnp.where(live[:, None, None], g, 0.0),
            jnp.where(live[:, None], beta, 0.0),
            interpret=jax.default_backend() != "tpu")
    from polyrl_tpu.models.mixers.kda import kda_recurrent_step

    old = state[:s]
    new, o = kda_recurrent_step(old.astype(jnp.float32), q, k, v, g, beta)
    new = jnp.where(live[:, None, None, None], new.astype(state.dtype), old)
    if s != state.shape[0]:
        new = jax.lax.dynamic_update_slice_in_dim(state, new, 0, 0)
    return new, o
