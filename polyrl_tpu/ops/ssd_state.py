"""One position of the Mamba-2 (SSD) recurrence over the decode step's
rows, in one pass over the state and in place: what
``mixers.mamba2.ssd_recurrent_step`` computes, for the leading ``S`` rows
of a state stack ``[slots, G, N, W]`` float32 (G groups of ``R = H / G``
heads that share B and C, ``N`` the state size, ``W = R * P`` a group's
heads of size P side by side)::

    S[g, n, (r, p)] <- a[(g, r)] S[g, n, (r, p)] + B[g, n] (dt x)[(g, r), p]
    y[(g, r), p] = sum_n S[g, n, (r, p)] C[g, n]

The fourth state kernel beside ``ops/kda_state.py``, ``ssm_state.py`` and
``lightning_state.py``, and why it is one of its own: lightning's kernel
multiplies its decay along the state's SUBLANE axis (its key axis, over
which ``o`` is reduced), one column a head spread over the lanes. Here the
axis that is reduced is N (B and C are the key and the query), so N lies on
the sublanes; the decay then varies along the LANES (a scalar a head, a
group's heads side by side), and lightning's kernel has no operand that
does: serving this through it means another kernel body, and
MiniCPM-SALA's lowered program with it. Laid out the other way (``[H, P,
N]``, N on the lanes, as ISSUE 58 first wrote it) every head's ``y`` is a
reduction over lanes, 512 cross-lane reductions a row and a transpose of
their columns; this layout reduces over sublanes (vregs added on the VPU,
the eight sublanes folded once a lane tile), broadcasts B and C along the
lanes once a GROUP and not once a head, and takes ``dt x`` and the decay as
rows that broadcast over sublanes for nothing. ``mixers/mamba2.py::held``
hands the state out as the published ``[H, P, N]``.

The kernel: a grid step brings one row's groups into VMEM
(``_groups_per_block``: the whole row, 8 groups of 128 x 512 = 2 MiB, at
the published sizes), and a group and a lane tile of 128 at a time scales
it by the decay's row, adds ``B`` (a column, spread over the lanes) times
``dt x`` (a row), stores it, and reduces ``y = sum_n new * C`` over the
sublanes. The state is the call's input AND output
(``input_output_aliases``): one HBM read and one HBM write of each visited
row, rows past ``S`` never touched. A row without a request is handed a
decay of 1 and ``dt x = 0`` and keeps its state: ``x * 1 + B * 0``.

``ssd_state_update`` is the dispatcher: on a TPU, for a float32 state whose
``N`` is whole sublane tiles and whose ``W`` is whole lane tiles, the
kernel; elsewhere the oracle on ``state[:S]``, the rows without a request
kept by a ``where``, written back. What says that the kernel ran is the
engine's ``ssd_kernel_steps`` and the kernel's own event, ``ssd_state``, in
a device trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ``_groups_per_block``: groups of one row a grid step updates, by KDA's
# rule for its heads (the most that divide G, fit its 2 MiB block and are
# whole sublane tiles of the ``[G, W]`` rows beside the state, or all of G)
from polyrl_tpu.ops.kda_state import (_LANES, _VMEM_LIMIT,
                                      _heads_per_block as _groups_per_block)

_SUBLANES = 8


def accepts(state_shape, dtype) -> bool:
    """Whether the kernel takes a state stack of this shape and dtype:
    float32, ``N`` whole sublane tiles, ``W`` whole lane tiles, a block of
    groups that fits. The backend is the dispatcher's to ask."""
    _slots, g, n, w = state_shape
    return (dtype == jnp.float32 and n % _SUBLANES == 0 and w % _LANES == 0
            and _groups_per_block(g, n, w) is not None)


def in_kernel(state_shape, dtype) -> bool:
    """Whether ``ssd_state_update`` runs the kernel for this state here."""
    return jax.default_backend() == "tpu" and accepts(state_shape, dtype)


def _kernel(b_ref, c_ref,        # [1, gb, N] each
            x_ref, a_ref,        # [1, gb, W] each: dt x, the decay
            state_ref,           # [1, gb, N, W]
            new_ref,             # the same block of the same array
            y_ref,               # [1, gb, W]
            ):
    # [N, gb]: a group's column spreads over W by a lane broadcast
    b_cols, c_cols = b_ref[0].T, c_ref[0].T
    gb, w = x_ref.shape[1:]
    for g in range(gb):
        b_col, c_col = b_cols[:, g:g + 1], c_cols[:, g:g + 1]
        for lo in range(0, w, _LANES):
            at = slice(lo, lo + _LANES)
            new = (state_ref[0, g, :, at] * a_ref[0, g:g + 1, at]
                   + b_col * x_ref[0, g:g + 1, at])              # [N, 128]
            new_ref[0, g, :, at] = new
            y_ref[0, g:g + 1, at] = jnp.sum(new * c_col, axis=0,
                                            keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "gb"))
def ssd_state_pallas(state, x, a, b, c, interpret: bool = False,
                     gb: int | None = None):
    """The kernel: ``state`` [slots, G, N, W] float32 with its leading
    ``S`` rows updated in place, and ``y`` [S, G, W]; ``x`` (dt x) and
    ``a`` (the decay, 1 for a row that keeps its state) [S, G, W], ``b``
    and ``c`` [S, G, N], all float32."""
    s, g, w = x.shape
    n = b.shape[-1]
    gb = gb or _groups_per_block(g, n, w)
    cols = pl.BlockSpec((1, gb, n), lambda i, j: (i, j, 0))
    rows = pl.BlockSpec((1, gb, w), lambda i, j: (i, j, 0))
    block = pl.BlockSpec((1, gb, n, w), lambda i, j: (i, j, 0, 0))
    new, y = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((s, g, w), jnp.float32)),
        grid=(s, g // gb),
        in_specs=[cols, cols, rows, rows, block],
        out_specs=(block, rows),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ssd_state", interpret=interpret,
    )(b, c, x, a, state)
    return new, y


def ssd_recurrent_step(state, x, a, b, c):
    """The oracle, one position for rows ``[S, ...]``: ``state`` [S, G, N,
    W], ``x`` (dt x) and ``a`` [S, G, W], ``b`` and ``c`` [S, G, N]; returns
    (new state, y [S, G, W]); everything float32."""
    new = state * a[:, :, None, :] + b[..., None] * x[:, :, None, :]
    return new, jnp.sum(new * c[..., None], axis=2)


def ssd_state_update(state, x, a, b, c, live):
    """``state`` [slots, G, N, W] with its leading ``S`` rows advanced one
    position where ``live`` [S] says so and kept where not, and ``y`` [S,
    G, W] float32 (a kept row's is not for use). The kernel where
    ``in_kernel`` says so, else ``ssd_recurrent_step`` on those rows, a
    ``where`` and the write-back."""
    s = x.shape[0]
    if in_kernel(state.shape, state.dtype):
        keep = live[:, None, None]
        return ssd_state_pallas(state, jnp.where(keep, x, 0.0),
                                jnp.where(keep, a, 1.0), b, c)
    old = state[:s]
    new, y = ssd_recurrent_step(old.astype(jnp.float32), x, a, b, c)
    new = jnp.where(live[:, None, None, None], new.astype(state.dtype), old)
    if s != state.shape[0]:
        new = jax.lax.dynamic_update_slice_in_dim(state, new, 0, 0)
    return new, y
