"""One position of a Mamba-1 selective scan over the decode step's rows,
in one pass over the state and in place: what ``mixers.ssm.ssm_step``
computes, for the leading ``S`` rows of a layer's states ``[slots, N, I]``
float32 (``N`` the state size on the sublanes, ``I`` the inner width on
the lanes).

``ssm_state_update`` is the dispatcher: on a TPU, for a float32 state
whose ``I`` is whole lane tiles and ``N`` whole sublane tiles, the kernel
below; elsewhere (and for every other shape) ``mixers.ssm.ssm_step`` on
``state[:S]``, the rows without a request kept by a ``where``, written
back. It notes nothing in ``ops/dispatch.py`` (as ``ops/kda_state.py``
notes nothing): what says that the kernel ran is its own event,
``ssm_state``, in a device trace.

Why a kernel where XLA's own fusion already read a state once and wrote
it once: the in-place update is then the program's by construction (the
alias), not the compiler's choice a version at a time, as
``ops/kda_state.py`` made it for KDA, and the step is a little faster
for it: ``busy_ms_per_step`` 51.73 -> 51.61 and ``engine_tok_s`` 2474.2
-> 2480.3 (+0.24%) in the benchmark's SambaY cell (one traced run each,
other seeds; two traced runs of one tree read 2435.06 and 2435.09; my
chip runs, PR 43). What it does NOT do is own a layer's HBM traffic
inside a decode step, which is what it was written for: alone on the chip
(``tools/bench_ssm_state.py``: nine layers of 129 x 16 x 5120 in one
donated program) its events are 1.158 ms, 80.2% of what the states' bytes
need at 819 GB/s (the rate a read and a write of the same bytes reach on
this chip: ``ops/kda_state.py``), against 1.257 ms for the whole program
of XLA's fusion; but in the decode program XLA's memory-space assignment
brings the call's state operand into VMEM ahead of it, as it did for its
own fusion (``copy-done f32[129,16,5120]``, under no scope, beside other
layers' work), so the kernel's events there are VMEM to VMEM, 0.036 ms a
layer, and no span of the trace holds the layer's 84 MB of HBM traffic:
the benchmark has no ``ssm_core_ms`` and no share of a roofline for it (a
share over the scope's time read 184% with the fusion and 286% with the
kernel; PERF.md sections 3 and 7).

The kernel: a grid step brings ``_ROWS`` rows' states into VMEM and, a
row and ``_LANE_CHUNK`` lanes at a time (sixteen vregs of state, so the
chain stays in registers), forms ``new = exp(dt a) * state + (dt c) b``
with ``dt`` and ``dt c`` spread over the sublanes and ``b`` over the
lanes, stores it, and reduces ``m = sum_n new * c_out`` over the
sublanes. The state is the call's input AND output
(``input_output_aliases``): one HBM read and one HBM write of each
visited row, rows past ``S`` never touched, no copy where the caller
donates the array. ``b`` and ``c_out`` multiply along ``N``, the state's
sublane axis, so they come as ``[S, N, 1]`` (padded to a lane tile in
HBM: 16 KB a row beside the state's 320 KB) and spread by a lane
broadcast. A row without a request is handed ``dt = 0`` and keeps its
state: ``s * 1 + 0 * b``. The skip term ``D c`` is the caller's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# rows a grid step: a sublane tile of the ``[S, I]`` operands beside the
# state, and 2.6 MB of state each way at I 5120 (under a MiB a step the
# DMAs' queue and a grid step's own cost show: ops/kda_state.py)
_ROWS = 8
# lanes of one row the arithmetic takes at a time
_LANE_CHUNK = 1024


def accepts(state_shape, dtype) -> bool:
    """Whether the kernel takes states of this shape and dtype: float32,
    ``I`` whole lane tiles, ``N`` whole sublane tiles. The backend is the
    dispatcher's to ask."""
    _slots, n, inner = state_shape
    return (dtype == jnp.float32 and inner % _LANES == 0
            and n % _SUBLANES == 0)


def in_kernel(state_shape, dtype) -> bool:
    """Whether ``ssm_state_update`` runs the kernel for this state here."""
    return jax.default_backend() == "tpu" and accepts(state_shape, dtype)


def _lane_chunk(inner: int) -> int:
    chunk = _LANE_CHUNK
    while inner % chunk:
        chunk //= 2
    return chunk


def _kernel(dt_ref, x_ref,        # [R, I] each: dt, dt * c
            b_ref, c_ref,         # [R, N, 1] each
            a_ref,                # [N, I]
            state_ref,            # [R, N, I]
            new_ref,              # the same block of the same array
            m_ref,                # [R, I]
            *, s: int):
    rows, n, inner = state_ref.shape
    chunk = _lane_chunk(inner)
    # the last block may reach past the step's ``s`` rows: a slot there
    # (the stack may hold more than the step visits) keeps its state
    whole = pl.program_id(0) < s // rows
    for r in range(rows):
        b = jnp.broadcast_to(b_ref[r], (n, chunk))
        c = jnp.broadcast_to(c_ref[r], (n, chunk))
        for j in range(0, inner, chunk):
            at = slice(j, j + chunk)
            old = state_ref[r, :, at]
            new = (jnp.exp(dt_ref[r:r + 1, at] * a_ref[:, at]) * old
                   + x_ref[r:r + 1, at] * b)
            if s % rows and r >= s % rows:
                new = jnp.where(whole, new, old)
            new_ref[r, :, at] = new
            m_ref[r:r + 1, at] = jnp.sum(new * c, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_state_pallas(state, a, dt, x, bm, cm, interpret: bool = False):
    """The kernel: ``state`` [slots, N, I] float32 with its leading ``S``
    rows updated in place, and ``m`` [S, I] without the skip term; ``a``
    [N, I], ``dt`` and ``x = dt * c`` [S, I], ``bm cm`` [S, N], all
    float32."""
    s, inner = dt.shape
    n = a.shape[0]
    rows = pl.BlockSpec((_ROWS, inner), lambda i: (i, 0))
    cols = pl.BlockSpec((_ROWS, n, 1), lambda i: (i, 0, 0))
    block = pl.BlockSpec((_ROWS, n, inner), lambda i: (i, 0, 0))
    block_bytes = _ROWS * n * inner * 4
    new, m = pl.pallas_call(
        functools.partial(_kernel, s=s),
        out_shape=(jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((s, inner), jnp.float32)),
        grid=(pl.cdiv(s, _ROWS),),
        in_specs=[rows, rows, cols, cols,
                  pl.BlockSpec((n, inner), lambda i: (0, 0)), block],
        out_specs=(block, rows),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # state in and out, double-buffered, is four blocks; the rest
            # is ``a``, the row operands and ``m``
            vmem_limit_bytes=6 * block_bytes + 4 * 2**20),
        name="ssm_state", interpret=interpret,
    )(dt, x, bm[:, :, None], cm[:, :, None], a, state)
    return new, m


def ssm_state_update(lp, state, c, dt, bm, cm, live):
    """``state`` [slots, N, I] with its leading ``S`` rows advanced one
    position where ``live`` [S] says so and kept where not, and the scan's
    output ``m`` [S, I] float32 (a kept row's is not for use): the kernel
    where ``in_kernel`` says so, else ``mixers.ssm.ssm_step`` on those rows, a
    ``where`` and the write-back. ``lp``: the layer's ``a_log`` [N, I] and
    ``d_skip`` [I]; ``c dt`` [S, I], ``bm cm`` [S, N], float32."""
    s = c.shape[0]
    if in_kernel(state.shape, state.dtype):
        dt = jnp.where(live[:, None], dt, 0.0)
        new, m = ssm_state_pallas(state, -jnp.exp(lp["a_log"]), dt, dt * c,
                                  bm, cm)
        return new, m + lp["d_skip"] * c
    from polyrl_tpu.models.mixers.ssm import ssm_step

    old = state[:s]
    new, m = ssm_step(lp, old, c, dt, bm, cm)
    new = jnp.where(live[:, None, None], new, old)
    if s != state.shape[0]:
        new = jax.lax.dynamic_update_slice_in_dim(state, new, 0, 0)
    return new, m
