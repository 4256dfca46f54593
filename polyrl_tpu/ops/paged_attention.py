"""Paged decode attention: one query token per sequence over a paged KV pool.

TPU-native replacement for the reference's SGLang paged-KV CUDA decode
kernels (SURVEY.md §2.2 native-census row 1). The KV cache is a pool of
fixed-size pages shared by all running sequences; each sequence owns an
ordered page list (its row of ``page_table``). This is what makes
continuous batching work: sequences of wildly different lengths share one
static-shaped pool, so ONE compiled decode step serves every mix of
requests — no shape buckets, no recompilation as requests come and go.

Two implementations with identical semantics:

- ``paged_attention_ref`` — jnp gather + dense softmax. XLA-compilable
  everywhere; the correctness oracle and the CPU-test path.
- ``paged_attention_pallas`` — Pallas TPU kernel. Grid (seq, kv_head,
  page); the page table is a scalar-prefetch operand, so each grid step's
  BlockSpec index_map DMAs exactly the page it needs from HBM into VMEM
  (automatic double-buffering from the pipeline emitter). Online softmax
  accumulates in VMEM scratch across the page axis; invalid pages are
  skipped with ``pl.when`` (their index_map points at the reserved null
  page 0, whose DMA cost is the price of a uniform grid).

Shared-prefix GROUPED decode (``grouped_paged_attention*``): GRPO's
G-samples-per-prompt traffic means G slots share one physical prompt-KV
prefix (page-table indirection since the group-shared prefill layer).
The per-slot kernel above still streams those prefix pages from HBM once
PER SLOT — a G× redundant read of the dominant KV segment of a decode
step that is bandwidth-bound. The grouped variant is two-phase:

- **Phase 1 (prefix)**: grid (group, prefix_page) — each shared prefix
  page is DMA'd ONCE per group and attends against the group's G·rep
  stacked decode queries (a [G·rep, page] MXU matmul instead of G rep-row
  gemvs — arithmetic intensity ×G). Emits per-slot partial flash stats
  (m, l, unnormalized acc).
- **Phase 2 (suffix)**: the per-slot kernel shape, over each slot's OWN
  pages past the prefix (prompt tail + generated KV), with the online
  softmax INITIALIZED from phase 1's stats — the standard flash (m, l,
  acc) log-sum-exp merge falls out of the rescale the kernel already
  does per page. Ungrouped slots init with (m=-inf, l=0, acc=0) and
  phase 2 degenerates to exactly the ungrouped kernel's math.

``grouped_paged_attention_ref`` is the jnp oracle for the same two-phase
split (used by CPU tests and as the engine's CPU path); the result is
mathematically the plain full-table attention, so it is pinned against
``paged_attention_ref`` on the reconstructed per-slot tables.

Layout notes (why these shapes):
- pools are [num_pages, page_size, Hkv, D]: page_size×D are the tiled
  (sublane×lane) dims of each DMA; Hkv is a grid axis so one kernel
  instance streams a [page_size, D] tile — MXU-shaped for the q·kᵀ matmul.
- q is pre-reshaped to [S, Hkv, rep, D] (rep = GQA group size): the kernel
  computes a [rep, page_size] logits tile per page — contraction over D
  lands on the MXU without any in-kernel head regrouping.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from polyrl_tpu.ops import dispatch

NEG_INF = float(np.finfo(np.float32).min)


def paged_attention_ref(
    q: jnp.ndarray,        # [S, Hq, D]
    k_pool: jnp.ndarray,   # [Hkv, N_pages, page_size, D]
    v_pool: jnp.ndarray,   # [Hkv, N_pages, page_size, D]
    page_table: jnp.ndarray,  # [S, P] int32 page ids (0 = null page ok)
    seq_lens: jnp.ndarray,    # [S] int32 valid tokens per sequence
    scale: float | None = None,
) -> jnp.ndarray:
    """Gather-based oracle. Returns [S, Hq, D] in q.dtype."""
    s, hq, d = q.shape
    hkv, n_pages, ps, _ = k_pool.shape
    p = page_table.shape[1]
    rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    k = k_pool[:, page_table].reshape(hkv, s, p * ps, d)  # [Hkv, S, T, D]
    v = v_pool[:, page_table].reshape(hkv, s, p * ps, d)
    qr = q.reshape(s, hkv, rep, d).astype(jnp.float32)

    logits = jnp.einsum("shrd,hstd->shrt", qr, k.astype(jnp.float32)) * scale
    pos = jnp.arange(p * ps)[None, :]  # [1, T]
    valid = pos < jnp.maximum(seq_lens, 1)[:, None]  # clamp: empty rows stay finite
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("shrt,hstd->shrd", probs, v.astype(jnp.float32))
    return out.reshape(s, hq, d).astype(q.dtype)


def _paged_attn_kernel(page_tbl_ref, seq_lens_ref,  # scalar prefetch
                       q_ref,      # [1, Hkv, rep, D]
                       k_ref,      # [Hkv, 1, page_size, D]
                       v_ref,      # [Hkv, 1, page_size, D]
                       out_ref,    # [1, Hkv, rep, D]
                       m_ref, l_ref, acc_ref,  # VMEM [Hkv, rep_pad, 128|D]
                       *, page_size: int, scale: float):
    """One (slot, page) program computing ALL kv-head groups at once:
    Mosaic requires the last two block dims be (8,128)-tileable or full, so
    the kv-head axis must ride whole inside the block (blocking it to 1 is
    rejected on real TPUs — only interpret mode accepted it)."""
    import jax.experimental.pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)
    seq_len = seq_lens_ref[s]
    n_pages = (jnp.maximum(seq_len, 1) + page_size - 1) // page_size

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(p < n_pages)
    def _work():
        q = q_ref[0].astype(jnp.float32)   # [Hkv, rep, D]
        # pool is head-major: the page block arrives as [Hkv, 1, page, D]
        k = k_ref[:, 0].astype(jnp.float32)  # [Hkv, page_size, D]
        v = v_ref[:, 0].astype(jnp.float32)  # [Hkv, page_size, D]
        rep = q.shape[1]

        logits = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [Hkv, rep, page_size]
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 2)
        logits = jnp.where(pos < jnp.maximum(seq_len, 1), logits, NEG_INF)

        m_prev = m_ref[:, :rep, :1]                    # [Hkv, rep, 1]
        l_prev = l_ref[:, :rep, :1]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(logits - m_new)                # [Hkv, rep, page_size]
        l_new = alpha * l_prev + jnp.sum(probs, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            probs, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # [Hkv, rep, D]
        acc_ref[:, :rep, :] = acc_ref[:, :rep, :] * alpha + pv
        m_ref[:, :rep, :1] = m_new
        l_ref[:, :rep, :1] = l_new

    @pl.when(p == n_pages - 1)
    def _finish():
        rep = out_ref.shape[2]
        out_ref[0] = (
            acc_ref[:, :rep, :] / jnp.maximum(l_ref[:, :rep, :1], 1e-30)
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_pallas(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, hq, d = q.shape
    hkv, n_pool, page_size, _ = k_pool.shape
    p = page_table.shape[1]
    rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    rep_pad = max(rep, 8)  # f32 sublane tile

    qr = q.reshape(s, hkv, rep, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, p),
        in_specs=[
            pl.BlockSpec((1, hkv, rep, d), lambda si, pi, pt, sl: (si, 0, 0, 0)),
            pl.BlockSpec((hkv, 1, page_size, d),
                         lambda si, pi, pt, sl: (0, pt[si, pi], 0, 0)),
            pl.BlockSpec((hkv, 1, page_size, d),
                         lambda si, pi, pt, sl: (0, pt[si, pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, rep, d),
                               lambda si, pi, pt, sl: (si, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, rep_pad, 128), jnp.float32),  # m (col 0 used)
            pltpu.VMEM((hkv, rep_pad, 128), jnp.float32),  # l
            pltpu.VMEM((hkv, rep_pad, d), jnp.float32),    # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=page_size, scale=scale),
        out_shape=jax.ShapeDtypeStruct((s, hkv, rep, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_attention",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32), qr, k_pool, v_pool)
    return out.reshape(s, hq, d)


def paged_attention_lib(q, k_pool, v_pool, page_table, seq_lens, scale=None):
    """The tuned multi-page kernel from jax.experimental.pallas.ops.tpu:
    processes ``pages_per_compute_block`` pages per grid step with
    double-buffered page DMAs, so HBM bandwidth is actually saturated (our
    one-page-per-step kernel bottoms out near 90 GB/s on real chips — fine
    as a readable oracle, 8-9x off as the production path). The pool layout
    [Hkv, N, page, D] is exactly the kernel's native layout; the kernel
    applies no softmax scale, so q is pre-scaled here."""
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention as _pa)

    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    p = page_table.shape[1]
    ppcb = min(8, p)
    while p % ppcb:
        ppcb -= 1
    # the library owns the pallas_call (no ``name=`` to give): the scope
    # is this kernel's stable name on a device trace
    with jax.named_scope("paged_attention_lib"):
        return _pa(
            (q * scale).astype(q.dtype), k_pool, v_pool,
            jnp.maximum(seq_lens.astype(jnp.int32), 1),
            page_table.astype(jnp.int32),
            pages_per_compute_block=ppcb)


# -- shared-prefix grouped decode attention ---------------------------------


def _group_slot_maps(group_slots, group_prefix_lens, s: int, page_size: int):
    """Invert the group table into per-slot maps (jit-safe, static shapes).

    group_slots [NG, G] int32 (-1 = empty seat) → for each of the ``s``
    attention rows: the group row it sits in (-1 = ungrouped), its seat
    column, and the number of leading page-table columns phase 1 already
    covered (0 for ungrouped rows). Scatter uses mode="drop" so the -1
    seats (routed out of bounds) cannot clamp-corrupt the last slot.
    """
    ng, gmax = group_slots.shape
    flat = group_slots.reshape(-1)
    gidx = jnp.repeat(jnp.arange(ng, dtype=jnp.int32), gmax)
    gcol = jnp.tile(jnp.arange(gmax, dtype=jnp.int32), ng)
    tgt = jnp.where(flat >= 0, flat, s)  # s = out of bounds → dropped
    slot_grp = jnp.full((s,), -1, jnp.int32).at[tgt].set(gidx, mode="drop")
    slot_col = jnp.zeros((s,), jnp.int32).at[tgt].set(gcol, mode="drop")
    pre_tok = group_prefix_lens[jnp.clip(slot_grp, 0, ng - 1)]
    slot_npre = jnp.where(slot_grp >= 0, pre_tok // page_size, 0)
    return slot_grp, slot_col, slot_npre.astype(jnp.int32)


def grouped_paged_attention_ref(
    q: jnp.ndarray,               # [S, Hq, D]
    k_pool: jnp.ndarray,          # [Hkv, N_pages, page_size, D]
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,      # [S, P] int32 — FULL per-slot page rows
    seq_lens: jnp.ndarray,        # [S] int32 — attended tokens per slot
    group_slots: jnp.ndarray,     # [NG, G] int32 slot ids, -1 = empty seat
    group_prefix_pages: jnp.ndarray,  # [NG, P_pre] int32 shared prefix pages
    group_prefix_lens: jnp.ndarray,   # [NG] int32 prefix TOKENS (page-mult.)
    scale: float | None = None,
) -> jnp.ndarray:
    """Two-phase oracle: explicit prefix/suffix split + LSE merge in jnp.

    Contract (what the engine guarantees): for every seated slot ``s`` of
    group ``g``, ``page_table[s, :n_pre] == group_prefix_pages[g, :n_pre]``
    (the PR-8 page-table indirection) and ``seq_lens[s] > prefix_len`` —
    so the merged result equals plain full-table attention up to float
    reduction order. Unseated slots take the phase-2-only path and match
    ``paged_attention_ref`` exactly.
    """
    s, hq, d = q.shape
    hkv, _n, ps, _ = k_pool.shape
    p = page_table.shape[1]
    ng, _g = group_slots.shape
    p_pre = group_prefix_pages.shape[1]
    rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    slot_grp, _slot_col, slot_npre = _group_slot_maps(
        group_slots, group_prefix_lens, s, ps)
    pre_tok = (slot_npre * ps)[:, None]                   # [S, 1]
    qr = q.reshape(s, hkv, rep, d).astype(jnp.float32)

    # phase 1: every slot against ITS group's shared prefix (ungrouped
    # slots fully masked → explicit zero/neg-inf stats below)
    gi = jnp.clip(slot_grp, 0, ng - 1)
    kp = k_pool[:, group_prefix_pages].reshape(hkv, ng, p_pre * ps, d)
    vp = v_pool[:, group_prefix_pages].reshape(hkv, ng, p_pre * ps, d)
    kp_s, vp_s = kp[:, gi], vp[:, gi]                     # [Hkv, S, T1, D]
    logits1 = jnp.einsum("shrd,hstd->shrt", qr,
                         kp_s.astype(jnp.float32)) * scale
    pos1 = jnp.arange(p_pre * ps)[None, :]
    valid1 = pos1 < pre_tok                               # [S, T1]
    logits1 = jnp.where(valid1[:, None, None, :], logits1, NEG_INF)
    m1 = jnp.max(logits1, axis=-1)                        # [S, Hkv, rep]
    e1 = jnp.exp(logits1 - m1[..., None])
    e1 = jnp.where(valid1[:, None, None, :], e1, 0.0)
    l1 = jnp.sum(e1, axis=-1)
    acc1 = jnp.einsum("shrt,hstd->shrd", e1, vp_s.astype(jnp.float32))
    grouped = (slot_grp >= 0)[:, None, None]
    m1 = jnp.where(grouped, m1, NEG_INF)
    l1 = jnp.where(grouped, l1, 0.0)
    acc1 = jnp.where(grouped[..., None], acc1, 0.0)

    # phase 2: each slot's own pages PAST the prefix
    k2 = k_pool[:, page_table].reshape(hkv, s, p * ps, d)
    v2 = v_pool[:, page_table].reshape(hkv, s, p * ps, d)
    logits2 = jnp.einsum("shrd,hstd->shrt", qr,
                         k2.astype(jnp.float32)) * scale
    pos2 = jnp.arange(p * ps)[None, :]
    valid2 = ((pos2 >= pre_tok)
              & (pos2 < jnp.maximum(seq_lens, 1)[:, None]))
    logits2 = jnp.where(valid2[:, None, None, :], logits2, NEG_INF)
    m2 = jnp.max(logits2, axis=-1)
    e2 = jnp.exp(logits2 - m2[..., None])
    e2 = jnp.where(valid2[:, None, None, :], e2, 0.0)
    l2 = jnp.sum(e2, axis=-1)
    acc2 = jnp.einsum("shrt,hstd->shrd", e2, v2.astype(jnp.float32))

    # LSE merge (NEG_INF is finite, so the alphas stay NaN-free: an empty
    # side contributes l=0 and its alpha multiplies nothing)
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = a1 * l1 + a2 * l2
    acc = a1[..., None] * acc1 + a2[..., None] * acc2
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(s, hq, d).astype(q.dtype)


def _grouped_prefix_kernel(pre_pages_ref, pre_lens_ref,  # scalar prefetch
                           q_ref,      # [1, Hkv, GR, D] (group's stacked q)
                           k_ref,      # [Hkv, 1, page_size, D]
                           v_ref,
                           acc_out_ref,  # [1, Hkv, GR, D] f32 unnormalized
                           m_out_ref,    # [1, Hkv, GR, 128] f32 (col 0)
                           l_out_ref,
                           m_ref, l_ref, acc_ref,  # VMEM scratch
                           *, page_size: int, scale: float):
    """Phase 1: one (group, prefix_page) program. The page block is DMA'd
    once and attends against ALL G·rep stacked queries of the group — the
    HBM stream the per-slot kernel pays G times happens once, and the
    q·kᵀ contraction is a [GR, page] MXU matmul. Outputs are the group's
    flash stats; normalization happens in phase 2's merge. Empty seats /
    GR padding compute garbage rows that no slot ever gathers."""
    import jax.experimental.pallas as pl

    g = pl.program_id(0)
    p = pl.program_id(1)
    pre_len = pre_lens_ref[g]
    n_pages = (pre_len + page_size - 1) // page_size  # 0 for pad group rows

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(p < n_pages)
    def _work():
        q = q_ref[0].astype(jnp.float32)     # [Hkv, GR, D]
        k = k_ref[:, 0].astype(jnp.float32)  # [Hkv, page_size, D]
        v = v_ref[:, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [Hkv, GR, page]
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 2)
        logits = jnp.where(pos < pre_len, logits, NEG_INF)

        m_prev = m_ref[:, :, :1]
        l_prev = l_ref[:, :, :1]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(logits - m_new)
        l_new = alpha * l_prev + jnp.sum(probs, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            probs, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # [Hkv, GR, D]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:, :, :1] = m_new
        l_ref[:, :, :1] = l_new

    @pl.when((p == n_pages - 1) & (n_pages > 0))
    def _finish():
        acc_out_ref[0] = acc_ref[:]
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = l_ref[:]


def _grouped_suffix_kernel(pt_ref, lens_ref, npre_ref,  # scalar prefetch
                           q_ref,     # [1, Hkv, rep, D]
                           m1_ref,    # [1, Hkv, rep_pad, 128] phase-1 m
                           l1_ref,
                           acc1_ref,  # [1, Hkv, rep_pad, D]
                           k_ref, v_ref,
                           out_ref,
                           m_ref, l_ref, acc_ref,  # VMEM scratch
                           *, page_size: int, scale: float):
    """Phase 2: the per-slot kernel over the slot's pages PAST its phase-1
    prefix (page column ``npre + p``), with the online-softmax state
    INITIALIZED from phase 1's (m, l, acc) — the rescale every page
    iteration already performs IS the flash log-sum-exp merge. Ungrouped
    slots arrive with (NEG_INF, 0, 0) and reduce to the plain kernel."""
    import jax.experimental.pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)
    seq_len = lens_ref[s]
    npre = npre_ref[s]
    n_tot = (jnp.maximum(seq_len, 1) + page_size - 1) // page_size
    n_sfx = jnp.maximum(n_tot - npre, 1)  # active slots always own >= 1

    @pl.when(p == 0)
    def _init():
        m_ref[:] = m1_ref[0]
        l_ref[:] = l1_ref[0]
        acc_ref[:] = acc1_ref[0]

    @pl.when(p < n_sfx)
    def _work():
        q = q_ref[0].astype(jnp.float32)     # [Hkv, rep, D]
        k = k_ref[:, 0].astype(jnp.float32)  # [Hkv, page_size, D]
        v = v_ref[:, 0].astype(jnp.float32)
        rep = q.shape[1]
        logits = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        pos = (npre + p) * page_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 2)
        logits = jnp.where(pos < jnp.maximum(seq_len, 1), logits, NEG_INF)

        m_prev = m_ref[:, :rep, :1]
        l_prev = l_ref[:, :rep, :1]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(logits - m_new)
        l_new = alpha * l_prev + jnp.sum(probs, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            probs, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[:, :rep, :] = acc_ref[:, :rep, :] * alpha + pv
        m_ref[:, :rep, :1] = m_new
        l_ref[:, :rep, :1] = l_new

    @pl.when(p == n_sfx - 1)
    def _finish():
        rep = out_ref.shape[2]
        out_ref[0] = (
            acc_ref[:, :rep, :] / jnp.maximum(l_ref[:, :rep, :1], 1e-30)
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def grouped_paged_attention_pallas(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    group_slots: jnp.ndarray,
    group_prefix_pages: jnp.ndarray,
    group_prefix_lens: jnp.ndarray,
    scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Two pallas_calls + a small XLA gather between them.

    Phase 1 produces per-GROUP stats [NG, Hkv, G·rep, D]; the inter-phase
    gather re-keys them per SLOT ([S, Hkv, rep, D] — a few MB) so phase
    2's BlockSpec stays a plain per-slot index map and no in-kernel
    dynamic slicing (Mosaic sublane-offset restrictions) is needed.
    Ungrouped slots substitute (NEG_INF, 0, 0) in that gather.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, hq, d = q.shape
    hkv, _n_pool, page_size, _ = k_pool.shape
    p = page_table.shape[1]
    ng, gmax = group_slots.shape
    p_pre = group_prefix_pages.shape[1]
    rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    rep_pad = max(rep, 8)
    gr = gmax * rep
    gr_pad = max(8, -(-gr // 8) * 8)

    qr = q.reshape(s, hkv, rep, d)
    slot_grp, slot_col, slot_npre = _group_slot_maps(
        group_slots, group_prefix_lens, s, page_size)

    # ---- phase 1: one stream of the shared prefix per group ----
    flat = jnp.clip(group_slots.reshape(-1), 0, s - 1)
    qg = qr[flat].reshape(ng, gmax, hkv, rep, d)
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(ng, hkv, gr, d)
    if gr_pad != gr:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gr_pad - gr), (0, 0)))
    grid1 = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ng, p_pre),
        in_specs=[
            pl.BlockSpec((1, hkv, gr_pad, d),
                         lambda gi, pi, pp, plen: (gi, 0, 0, 0)),
            pl.BlockSpec((hkv, 1, page_size, d),
                         lambda gi, pi, pp, plen: (0, pp[gi, pi], 0, 0)),
            pl.BlockSpec((hkv, 1, page_size, d),
                         lambda gi, pi, pp, plen: (0, pp[gi, pi], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, gr_pad, d),
                         lambda gi, pi, pp, plen: (gi, 0, 0, 0)),
            pl.BlockSpec((1, hkv, gr_pad, 128),
                         lambda gi, pi, pp, plen: (gi, 0, 0, 0)),
            pl.BlockSpec((1, hkv, gr_pad, 128),
                         lambda gi, pi, pp, plen: (gi, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, gr_pad, 128), jnp.float32),  # m (col 0)
            pltpu.VMEM((hkv, gr_pad, 128), jnp.float32),  # l
            pltpu.VMEM((hkv, gr_pad, d), jnp.float32),    # acc
        ],
    )
    acc1, m1, l1 = pl.pallas_call(
        functools.partial(_grouped_prefix_kernel, page_size=page_size,
                          scale=scale),
        out_shape=[jax.ShapeDtypeStruct((ng, hkv, gr_pad, d), jnp.float32),
                   jax.ShapeDtypeStruct((ng, hkv, gr_pad, 128), jnp.float32),
                   jax.ShapeDtypeStruct((ng, hkv, gr_pad, 128), jnp.float32)],
        grid_spec=grid1,
        interpret=interpret,
        name="grouped_prefix",
    )(group_prefix_pages.astype(jnp.int32),
      group_prefix_lens.astype(jnp.int32), qg, k_pool, v_pool)

    # ---- inter-phase gather: group stats → per-slot init blocks ----
    gi = jnp.clip(slot_grp, 0, ng - 1)
    rows = (slot_col * rep)[:, None] + jnp.arange(rep)[None]   # [S, rep]
    ridx = rows[:, None, :, None]                              # [S,1,rep,1]

    def per_slot(a, fill, width):
        g = jnp.take_along_axis(
            a[gi], jnp.broadcast_to(ridx, (s, hkv, rep, width)), axis=2)
        g = jnp.where((slot_grp >= 0)[:, None, None, None], g, fill)
        if rep_pad != rep:
            g = jnp.pad(g, ((0, 0), (0, 0), (0, rep_pad - rep), (0, 0)),
                        constant_values=fill)
        return g

    m1s = per_slot(m1, NEG_INF, 128)
    l1s = per_slot(l1, 0.0, 128)
    acc1s = per_slot(acc1, 0.0, d)

    # ---- phase 2: per-slot suffix pages, merged via the init state ----
    grid2 = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, p),
        in_specs=[
            pl.BlockSpec((1, hkv, rep, d),
                         lambda si, pi, pt, sl, npre: (si, 0, 0, 0)),
            pl.BlockSpec((1, hkv, rep_pad, 128),
                         lambda si, pi, pt, sl, npre: (si, 0, 0, 0)),
            pl.BlockSpec((1, hkv, rep_pad, 128),
                         lambda si, pi, pt, sl, npre: (si, 0, 0, 0)),
            pl.BlockSpec((1, hkv, rep_pad, d),
                         lambda si, pi, pt, sl, npre: (si, 0, 0, 0)),
            pl.BlockSpec(
                (hkv, 1, page_size, d),
                lambda si, pi, pt, sl, npre:
                (0, pt[si, jnp.minimum(npre[si] + pi, pt.shape[1] - 1)],
                 0, 0)),
            pl.BlockSpec(
                (hkv, 1, page_size, d),
                lambda si, pi, pt, sl, npre:
                (0, pt[si, jnp.minimum(npre[si] + pi, pt.shape[1] - 1)],
                 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, rep, d),
                               lambda si, pi, pt, sl, npre: (si, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, rep_pad, 128), jnp.float32),
            pltpu.VMEM((hkv, rep_pad, 128), jnp.float32),
            pltpu.VMEM((hkv, rep_pad, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_suffix_kernel, page_size=page_size,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((s, hkv, rep, d), q.dtype),
        grid_spec=grid2,
        interpret=interpret,
        name="grouped_suffix",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32), slot_npre,
      qr, m1s, l1s, acc1s, k_pool, v_pool)
    return out.reshape(s, hq, d)


def grouped_paged_attention(q, k_pool, v_pool, page_table, seq_lens,
                            group_slots, group_prefix_pages,
                            group_prefix_lens, scale=None):
    """Dispatch: two-phase Pallas kernels on TPU, two-phase jnp oracle
    elsewhere. Override with POLYRL_GROUPED_ATTN=ref|pallas. On TPU a
    lowering failure of the Pallas kernels raises."""
    impl = os.environ.get("POLYRL_GROUPED_ATTN", "")
    on_tpu = jax.default_backend() == "tpu"
    if impl == "pallas" or (impl != "ref" and on_tpu):
        dispatch.note("grouped", "pallas")
        return grouped_paged_attention_pallas(
            q, k_pool, v_pool, page_table, seq_lens, group_slots,
            group_prefix_pages, group_prefix_lens, scale,
            interpret=not on_tpu)
    dispatch.note("grouped", "ref")
    return grouped_paged_attention_ref(
        q, k_pool, v_pool, page_table, seq_lens, group_slots,
        group_prefix_pages, group_prefix_lens, scale)


def make_tp_grouped_paged_attention(mesh):
    """Tensor-parallel wrapper for the grouped kernel: q and both pools
    shard over tp on the head dim exactly like ``make_tp_paged_attention``
    (the grouped pallas calls are custom calls GSPMD cannot partition);
    the group tables are control metadata and stay replicated."""
    from jax.sharding import PartitionSpec as P

    from polyrl_tpu.parallel.mesh import TP

    def inner(q, k_pool, v_pool, page_table, seq_lens, group_slots,
              group_prefix_pages, group_prefix_lens):
        return grouped_paged_attention(
            q, k_pool, v_pool, page_table, seq_lens, group_slots,
            group_prefix_pages, group_prefix_lens)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, TP, None), P(TP, None, None, None),
                  P(TP, None, None, None), P(), P(), P(), P(), P()),
        out_specs=P(None, TP, None), check_vma=False)


_KV_WRITE_CHUNK = 64  # slots per program: bounds the VMEM window buffers


def _kv_write_kernel(page_ref, off_ref, owner_ref,  # scalar prefetch
                     kpool_ref, vpool_ref, kupd_ref, vupd_ref,
                     kout_ref, vout_ref, kbuf, vbuf, sems,
                     *, rows: int, n_slots: int):
    """One program per chunk of slots. A DMA cannot address one token row
    of a pool in HBM: the (page_size, D) dims are tiled, and Mosaic
    rejects a slice of 1 along page_size ("must be aligned to tiling").
    So each slot's row is written by read-modify-write of the aligned
    ``rows``-row window that holds it: all windows of the chunk are DMA'd
    to VMEM together, each slot's [Hkv, D] update is merged into its row,
    and all windows are DMA'd back together.

    Slots whose rows fall in the SAME window (inactive slots on null page
    0; the m consecutive positions of one slot in a speculative verify)
    share one buffer — ``owner_ref[i]`` is the first slot of the chunk
    with slot i's window — so their merges accumulate instead of racing.
    Merges run in slot order: for identical (page, off) the last wins."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk, hkv, _, d = kbuf.shape
    base = pl.program_id(0) * chunk
    n = jnp.minimum(chunk, n_slots - base)

    def window_copies(j, load: bool):
        i = base + j
        pg = page_ref[i]
        r0 = pl.multiple_of(off_ref[i] // rows * rows, rows)
        copies = []
        for src, dst, buf, sem in ((kpool_ref, kout_ref, kbuf, sems.at[0]),
                                   (vpool_ref, vout_ref, vbuf, sems.at[1])):
            if load:
                copies.append(pltpu.make_async_copy(
                    src.at[:, pg, pl.ds(r0, rows), :], buf.at[j], sem))
            else:
                copies.append(pltpu.make_async_copy(
                    buf.at[j], dst.at[:, pg, pl.ds(r0, rows), :], sem))
        return copies

    def for_each_window(fn):
        def body(j, carry):
            @pl.when(owner_ref[base + j] == base + j)
            def _():
                fn(j)
            return carry

        jax.lax.fori_loop(0, n, body, 0)

    def move_windows(load: bool):
        for_each_window(
            lambda j: [c.start() for c in window_copies(j, load)])
        for_each_window(
            lambda j: [c.wait() for c in window_copies(j, load)])

    move_windows(load=True)

    def merge(j, carry):
        i = base + j
        o = owner_ref[i] - base
        hit = jax.lax.broadcasted_iota(
            jnp.int32, (rows, d), 0) == off_ref[i] % rows
        for h in range(hkv):
            for buf, upd in ((kbuf, kupd_ref), (vbuf, vupd_ref)):
                cur = buf[o, h].astype(jnp.float32)      # [rows, D]
                buf[o, h] = jnp.where(hit, upd[i, pl.ds(h, 1), :],
                                      cur).astype(buf.dtype)
        return carry

    jax.lax.fori_loop(0, n, merge, 0)
    move_windows(load=False)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write_pallas(k_pool, v_pool, write_page, write_off, k_upd,
                          v_upd, interpret: bool = False):
    """Write one token's K/V per slot into the paged pools, in place.

    The XLA alternative (row scatter over [Hkv*N*ps, D], one row per
    slot*head) lowers to a serialized per-row loop on TPU. Here one
    Pallas program moves every slot's aligned row window HBM->VMEM with
    all DMAs in flight at once, merges the new rows, and moves the
    windows back (see ``_kv_write_kernel``). K and V are fused into one
    call. ``input_output_aliases`` keeps the pools in place (no copy);
    inactive slots are pre-routed to null page 0 by the caller."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = write_page.shape[0]
    hkv, _n, page_size, d = k_pool.shape
    # the DMA-able window: one packed sublane tile of the pool dtype
    # (8 rows of 32 bits), or the whole page when the page is smaller
    rows = min(page_size, 8 * (4 // k_pool.dtype.itemsize))
    if page_size % rows:
        raise ValueError(f"page_size {page_size} must be a multiple of "
                         f"{rows} for {k_pool.dtype} pools")
    chunk = min(s, _KV_WRITE_CHUNK)
    page = write_page.astype(jnp.int32)
    off = write_off.astype(jnp.int32)
    slot = jnp.arange(s, dtype=jnp.int32)
    key = (page * (page_size // rows) + off // rows) * (-(-s // chunk)) \
        + slot // chunk
    owner = jnp.argmax(key[:, None] == key[None, :], axis=1).astype(jnp.int32)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(-(-s // chunk),),
        in_specs=[hbm, hbm, vmem, vmem],
        out_specs=[hbm, hbm],
        scratch_shapes=[pltpu.VMEM((chunk, hkv, rows, d), k_pool.dtype),
                        pltpu.VMEM((chunk, hkv, rows, d), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        functools.partial(_kv_write_kernel, rows=rows, n_slots=s),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        grid_spec=grid_spec,
        # operand indices count the scalar-prefetch args: 0=page 1=off
        # 2=owner 3=k_pool 4=v_pool (aliased onto outputs 0/1) 5/6=updates
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
        name="paged_kv_write",
        # chunks run in order: a window shared across two chunks must be
        # written back by the first before the second reads it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # updates ride as f32 (rounded to the pool dtype first): one slot's
        # [Hkv, D] block is then whole (8, 128) tiles and a head's row is a
        # plain sublane slice, which packed 16-bit rows are not
    )(page, off, owner, k_pool, v_pool,
      k_upd.astype(k_pool.dtype).astype(jnp.float32),
      v_upd.astype(v_pool.dtype).astype(jnp.float32))


def paged_kv_write(k_pool, v_pool, write_page, write_off, k_upd, v_upd):
    """Dispatch: Pallas write kernel on TPU, XLA row scatter elsewhere.
    Override with POLYRL_KV_WRITE=scatter|pallas. On TPU a lowering
    failure of the Pallas kernel raises: the scatter it used to reroute
    to is the serialized per-row loop the kernel exists to replace."""
    impl = os.environ.get("POLYRL_KV_WRITE", "")
    on_tpu = jax.default_backend() == "tpu"
    if impl == "pallas" or (impl != "scatter" and on_tpu):
        dispatch.note("kv_write", "pallas")
        return paged_kv_write_pallas(
            k_pool, v_pool, write_page, write_off, k_upd, v_upd,
            interpret=not on_tpu)
    from polyrl_tpu.models.decoder import _scatter_token_kv

    dispatch.note("kv_write", "scatter")
    return (_scatter_token_kv(k_pool, write_page, write_off, k_upd),
            _scatter_token_kv(v_pool, write_page, write_off, v_upd))


def make_tp_paged_kv_write(mesh):
    """Tensor-parallel wrapper for the paged K/V write: pools and updates
    shard over tp on the KV-head dim (same split as the attention wrapper;
    GSPMD cannot partition the Pallas custom call, and an unsharded write
    would all-gather both pools per layer per step)."""
    from jax.sharding import PartitionSpec as P

    from polyrl_tpu.parallel.mesh import TP

    def inner(k_pool, v_pool, page, off, k_upd, v_upd):
        return paged_kv_write(k_pool, v_pool, page, off, k_upd, v_upd)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(TP, None, None, None), P(TP, None, None, None),
                  P(), P(), P(None, TP, None), P(None, TP, None)),
        out_specs=(P(TP, None, None, None), P(TP, None, None, None)),
        check_vma=False)


def make_tp_paged_attention(mesh):
    """Tensor-parallel wrapper: paged attention sharded over the tp axis on
    the HEAD dim (q [S, Hq, D] and both pools [Hkv, N, ps, D] split by tp;
    GQA query groups stay aligned with their shared KV head because both
    counts divide by tp). Needed because the Pallas kernel is a custom
    call — GSPMD cannot partition it, so without the shard_map a tp-sharded
    pool would be all-gathered per layer per step."""
    from jax.sharding import PartitionSpec as P

    from polyrl_tpu.parallel.mesh import TP

    def inner(q, k_pool, v_pool, page_table, seq_lens):
        return paged_attention(q, k_pool, v_pool, page_table, seq_lens)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, TP, None), P(TP, None, None, None),
                  P(TP, None, None, None), P(), P()),
        out_specs=P(None, TP, None), check_vma=False)


def paged_attention(q, k_pool, v_pool, page_table, seq_lens, scale=None):
    """Dispatch: the tuned library Pallas kernel on TPU, gather oracle
    elsewhere (interpret-mode for our custom kernel is exercised in tests;
    the oracle is faster for CPU test runs). Override with
    POLYRL_PAGED_ATTN=ref|pallas|lib."""
    impl = os.environ.get("POLYRL_PAGED_ATTN", "")
    on_tpu = jax.default_backend() == "tpu"
    if impl == "pallas":
        dispatch.note("paged_attention", "pallas")
        return paged_attention_pallas(
            q, k_pool, v_pool, page_table, seq_lens, scale,
            interpret=not on_tpu)
    if impl == "lib" or (impl != "ref" and on_tpu):
        dispatch.note("paged_attention", "lib")
        return paged_attention_lib(q, k_pool, v_pool, page_table, seq_lens, scale)
    dispatch.note("paged_attention", "ref")
    return paged_attention_ref(q, k_pool, v_pool, page_table, seq_lens, scale)
