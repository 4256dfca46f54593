"""One position of the lightning (linear-attention) recurrence over the
decode step's rows, in one pass over the state and in place: what
``mixers.lightning.lightning_recurrent_step`` computes, for the leading
``S`` rows of a state stack ``[slots, H, Dk, Dv]`` float32::

    S <- lambda_h S + k^T v        o = q S

``ops/kda_state.py``'s neighbour, and its kernel's shape: a grid step
brings one row's heads into VMEM (``kda_state._heads_per_block``: the whole
row at 32 heads of 128 x 128, 2 MiB), and a head at a time scales it along
``Dk`` by the head's decay, adds ``k v^T``, reduces ``o = new^T q`` over
the sublanes and stores the head. The state is the call's input AND output
(``input_output_aliases``): one HBM read and one HBM write of each visited
row, rows past ``S`` never touched. The recurrence has no delta term and no
``beta``, and its decay is one number a head and not a vector over ``Dk``;
it reaches the kernel spread over ``Dk`` (``[S, H, Dk]``, 1.5 MB a layer at
96 rows) so that the three operands that multiply along the state's
sublane axis are transposed alike. A row without a request is handed a
decay of 1 and ``k = 0`` and keeps its state: ``x * 1 + 0 * v``. KDA's
kernel and its lowered program are left as they were (Ling's cell).

``lightning_state_update`` is the dispatcher: on a TPU, for a float32 state
whose ``Dk`` and ``Dv`` are whole lane tiles, the kernel; elsewhere the
oracle on ``state[:S]``, the rows without a request kept by a ``where``,
written back. What says that the kernel ran is the engine's
``lightning_kernel_steps`` and the kernel's own event, ``lightning_state``,
in a device trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from polyrl_tpu.ops.kda_state import _VMEM_LIMIT, _heads_per_block, accepts


def in_kernel(state_shape, dtype) -> bool:
    """Whether ``lightning_state_update`` runs the kernel for this state
    here."""
    return jax.default_backend() == "tpu" and accepts(state_shape, dtype)


def _kernel(k_ref, q_ref, decay_ref,   # [1, hb, Dk] each
            v_ref,       # [1, hb, Dv]
            state_ref,   # [1, hb, Dk, Dv]
            new_ref,     # the same block of the same array
            o_ref,       # [1, hb, Dv]
            ):
    # [Dk, hb]: a head's column spreads over Dv by a lane broadcast
    k_cols, q_cols, decay_cols = k_ref[0].T, q_ref[0].T, decay_ref[0].T
    for h in range(k_cols.shape[1]):
        new = (state_ref[0, h] * decay_cols[:, h:h + 1]
               + k_cols[:, h:h + 1] * v_ref[0, h:h + 1, :])     # [Dk, Dv]
        new_ref[0, h] = new
        o_ref[0, h:h + 1, :] = jnp.sum(new * q_cols[:, h:h + 1], axis=0,
                                       keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "hb"))
def lightning_state_pallas(state, q, k, v, decay, interpret: bool = False,
                           hb: int | None = None):
    """The kernel: ``state`` [slots, H, Dk, Dv] float32 with its leading
    ``S`` rows updated in place, and ``o`` [S, H, Dv]; ``q k`` [S, H, Dk],
    ``v`` [S, H, Dv], ``decay`` [S, H] (lambda, 1 for a row that keeps its
    state), all float32."""
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
        in_specs=[cols, cols, cols, rows, block],
        out_specs=(block, rows),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="lightning_state", interpret=interpret,
    )(k, q, jnp.broadcast_to(decay[..., None], k.shape), v, state)
    return new, o


def lightning_state_update(state, q, k, v, decay, live):
    """``state`` [slots, H, Dk, Dv] with its leading ``S`` rows advanced
    one position where ``live`` [S] says so and kept where not, and ``o``
    [S, H, Dv] float32 (a kept row's is not for use); ``decay`` [H]: a
    head's lambda. The kernel where ``in_kernel`` says so, else
    ``lightning_recurrent_step`` on those rows, a ``where`` and the
    write-back."""
    s = k.shape[0]
    if in_kernel(state.shape, state.dtype):
        return lightning_state_pallas(
            state, q, jnp.where(live[:, None, None], k, 0.0), v,
            jnp.where(live[:, None], decay[None, :], 1.0))
    from polyrl_tpu.models.mixers.lightning import lightning_recurrent_step

    old = state[:s]
    new, o = lightning_recurrent_step(old.astype(jnp.float32), q, k, v, decay)
    new = jnp.where(live[:, None, None, None], new.astype(state.dtype), old)
    if s != state.shape[0]:
        new = jax.lax.dynamic_update_slice_in_dim(state, new, 0, 0)
    return new, o
