"""The GQA decode kernel alone, on the chip, at a benchmark cell's shapes:
``paged_attention_pallas`` over K and V pools, a page table and the context
lengths that the cell's decode step hands it, timed by the device's own
clock (a ``jax.profiler`` trace of the calls) and checked against the
gather-based oracle on four rows: a variant further from it than
``TOLERANCE``, or one that fails to build or run, is not timed, and the
call then exits 1 after the other variants.

    chiprun -- python tools/bench_paged_attention.py \
        [--cell zaya1-8b.rollout-wide-cca] [--window 512] \
        [--into-answer 264,400] [--plans "16,4,3;16,1,2"] \
        [--also-tree .parent] [--parts whole,merged2,dead] [--sparse]

``--cell`` takes the heads from the cell's configuration and the rows, the
table's width and the prompt lengths from its traffic file; ``--window``
cuts every context to a ring of that many keys (Phi's window layers).
``--into-answer`` is a LIST of how many tokens every row has generated: the
tool gives every row the same number, and which rows have just crossed a
block's edge moves a reading by a third, so a variant is judged at two
offsets at least. ``--sparse`` lays the call out as a block-sparse layer
of the cell's configuration hands it over (``mixers/sparse.py``): every
(request, K/V head) a row of ONE head over the pools seen as one head's,
its table the pages that head chose (``topk`` of the request's own, drawn
without order, its newest last) and its length the whole chosen pages and
the part-filled newest one.
``--plans`` times the kernel under other (pages a block, sub-blocks of a
last block, buffers) than ``paged_attention._block_plan`` returns for the
shapes. ``--also-tree`` times other checkouts' kernels beside this one
(parent against change in one call; several, comma-separated). ``--parts``
times every variant again on the same K/V bytes laid out otherwise, which
takes a row's fixed cost apart: ``whole`` (every length rounded up to whole
blocks: no part-filled last block), ``merged<n>`` (every ``n`` rows'
contexts as one row: the same bytes in an ``n``-th of the rows), ``dead``
(a row without a request after every live one: a grid step's own cost).
Prints one JSON line a variant and layout; fails without a TPU."""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench_latent_attention import kernel_ms, load_module  # noqa: E402

KERNEL = "paged_attention"
HBM_BYTES_S = 819e9     # one TPU v5e chip (Google Cloud, "TPU v5e")
LANES = 128
# bfloat16 outputs of order 1 against a float32 oracle: sound variants read
# 0.002 at every cell's shapes (my chip runs, PR 44)
TOLERANCE = 0.01


def cell_shapes(cell: str) -> dict:
    """Query heads, K/V heads, head size, page size, table width and the
    prompt lengths of a cell of ``BENCHMARK.json``. Heads narrower than a
    lane tile lie side by side in the pool, as ``mixers.diff.paired_queries``
    has them (Phi: 20 K/V heads of 64 are 10 of 128, under 40 query rows)."""
    from benchmark.lib import traffic

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    work = next(w for w in bench["workloads"] if w["name"] == cell)
    file = next(c["file"] for c in bench["configs"]
                if c["name"] == work["config"])
    with open(os.path.join(ROOT, file)) as f:
        cfg = json.load(f)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // hq
    side_by_side = max(1, LANES // d)
    mix = traffic.load_mix(work["traffic"])
    engine = mix["engine"]
    return {"hq": hq, "hkv": hkv // side_by_side, "d": d * side_by_side,
            "sparse": cfg.get("sparse_config"),
            "page": engine["page_size"],
            "width": engine["max_seq_len"] // engine["page_size"],
            "prompts": traffic.size_set(mix["prompt_tokens"],
                                        int(mix["offered_requests"]))}


def lay_out(lengths: list[int], part: str, block_tokens: int) -> list[int]:
    """The same contexts as another set of rows (``--parts``)."""
    if part == "real":
        return list(lengths)
    if part == "whole":
        return [-(-t // block_tokens) * block_tokens for t in lengths]
    if part == "dead":
        return [t for length in lengths for t in (length, 0)]
    if part.startswith("merged"):
        n = int(part[len("merged"):])
        return [sum(lengths[k:k + n]) for k in range(0, len(lengths), n)]
    raise SystemExit(f"--parts: no layout {part!r}")


def inputs(lengths: list[int], shapes: dict, width: int, seed: int):
    """q, pools, table, lens: one more row than requests (the engine's
    spare slot, dead), every live row's pages its own, drawn at random
    from a pool a ninth larger than what the rows hold."""
    page, hkv, d = shapes["page"], shapes["hkv"], shapes["d"]
    rng = np.random.default_rng(seed)
    s = len(lengths) + 1
    pages = [-(-t // page) for t in lengths]
    n_pages = 1 + int(sum(pages) * 1.12)
    order = rng.permutation(np.arange(1, n_pages))
    table = np.zeros((s, width), np.int32)
    at = 0
    for r, n in enumerate(pages):
        table[r, :n] = order[at:at + n]
        at += n
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed % (1 << 31)), 3)
    k_pool = jax.random.normal(kk, (hkv, n_pages, page, d), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (hkv, n_pages, page, d), jnp.bfloat16)
    q = jax.random.normal(kq, (s, shapes["hq"], d), jnp.bfloat16)
    lens = jnp.asarray(lengths + [0], jnp.int32)
    return q, k_pool, v_pool, jnp.asarray(table), lens


def sparse_width(shapes: dict) -> int:
    """The most pages a (request, K/V head) attends
    (``mixers.sparse.table_width``)."""
    cfg = shapes["sparse"]
    return max(cfg["topk"], -(-cfg["dense_len"] // shapes["page"]))


def sparse_inputs(contexts: list[int], shapes: dict, seed: int):
    """What a block-sparse layer's decode step hands the kernel
    (``mixers.sparse.selected_table`` and ``step``): q ``[S * Hkv, Hq / Hkv,
    D]``, the pools as ``[1, Hkv * N, page, D]``, a table ``[S * Hkv, W]``
    of the pages a (request, head) chose (head ``g``'s numbers offset by
    ``g * N``; the newest page last, the others drawn from the request's
    own without order) and the keys they hold. One more request than
    ``contexts`` (the engine's spare slot, dead)."""
    cfg, page = shapes["sparse"], shapes["page"]
    hkv, d = shapes["hkv"], shapes["d"]
    if cfg["block_size"] != page:
        raise SystemExit("--sparse: a sparse layer's page is its block")
    rng = np.random.default_rng(seed)
    width = sparse_width(shapes)
    own = [-(-t // page) for t in contexts]
    n_pages = 1 + int(sum(own) * 1.12)
    order = rng.permutation(np.arange(1, n_pages))
    table = np.zeros(((len(contexts) + 1) * hkv, width), np.int32)
    lens = np.zeros(len(table), np.int32)
    at = 0
    for r, (t, n) in enumerate(zip(contexts, own)):
        mine = order[at:at + n]
        at += n
        count = n if t <= cfg["dense_len"] else min(n, cfg["topk"])
        for g in range(hkv):
            older = rng.permutation(mine[:-1])[:count - 1]
            table[r * hkv + g, :count] = g * n_pages + np.append(older,
                                                                 mine[-1])
            lens[r * hkv + g] = (count - 1) * page + t - (n - 1) * page
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed % (1 << 31)), 3)
    k_pool = jax.random.normal(kk, (1, hkv * n_pages, page, d), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (1, hkv * n_pages, page, d), jnp.bfloat16)
    q = jax.random.normal(kq, (len(table), shapes["hq"] // hkv, d),
                          jnp.bfloat16)
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lens)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="zaya1-8b.rollout-wide-cca")
    ap.add_argument("--into-answer", default="264,400",
                    help="tokens generated so far, a list (64 warm + the "
                         "traced part's middle, and a third of a block on)")
    ap.add_argument("--window", type=int, default=0,
                    help="a ring of this many keys a row (Phi's window "
                         "layers: 512) instead of the whole context")
    ap.add_argument("--sparse", action="store_true",
                    help="the call of a block-sparse layer of the cell's "
                         "configuration: a row a (request, K/V head)")
    ap.add_argument("--plans", default="")
    ap.add_argument("--also-tree", default="")
    ap.add_argument("--parts", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "bench_paged_attention"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 1

    from polyrl_tpu.ops import paged_attention as here

    shapes = cell_shapes(args.cell)
    if args.sparse and (not shapes["sparse"] or args.parts or args.window):
        raise SystemExit("--sparse: a cell with a sparse_config, and neither "
                         "--parts nor --window")
    width = shapes["width"]
    if args.window:
        width = args.window // shapes["page"]
    if args.sparse:
        width = sparse_width(shapes)
    rule = here._block_plan(1 if args.sparse else shapes["hkv"],
                            shapes["page"], shapes["d"], 2, width)
    variants = [("change", here, None)]
    variants += [(f"change {p}", here, tuple(int(x) for x in p.split(",")))
                 for p in args.plans.split(";") if p]
    variants += [(tree, load_module(tree, "paged_attention"), None)
                 for tree in args.also_tree.split(",") if tree]
    os.makedirs(args.out, exist_ok=True)
    failed = 0
    for into in (int(t) for t in args.into_answer.split(",")):
        real = [t + into for t in shapes["prompts"]]
        if args.window:
            real = [min(t, args.window) for t in real]
        parts = ["sparse"] if args.sparse else (
            ["real"] + [p for p in args.parts.split(",") if p])
        for part in parts:
            if args.sparse:
                q, k_pool, v_pool, table, lens = sparse_inputs(
                    real, shapes, args.seed)
            else:
                lengths = lay_out(real, part, rule[0] * shapes["page"])
                wide = max(width, -(-max(lengths) // shapes["page"]))
                q, k_pool, v_pool, table, lens = inputs(lengths, shapes,
                                                        wide, args.seed)
            failed += time_variants(args, variants, rule, part, into,
                                    q, k_pool, v_pool, table, lens)
    return 1 if failed else 0


def time_variants(args, variants, rule, part: str, into: int,
                  q, k_pool, v_pool, table, lens) -> int:
    """A JSON line a variant at one layout and offset; how many failed."""
    here = variants[0][1]
    hkv, _, _, d = k_pool.shape
    keys, rows = int(lens.sum()), len(lens)
    alive = np.flatnonzero(np.asarray(lens) > 0)
    live = len(alive)
    check = jnp.asarray([alive[0], alive[live // 2], alive[-1], rows - 1])
    want = here.paged_attention_ref(q[check], k_pool, v_pool, table[check],
                                    lens[check])
    want = jnp.where((lens[check] > 0)[:, None, None], want, 0)
    # the least the chip could take: every live key's K and V row once
    least_ms = 1e3 * 2 * hkv * d * 2 * keys / HBM_BYTES_S
    failed, first = 0, None
    for k, (name, mod, plan) in enumerate(variants):
        fn = mod.paged_attention_pallas
        if plan is not None:
            fn = functools.partial(fn, plan=plan)
        try:
            got = jax.block_until_ready(fn(q, k_pool, v_pool, table, lens))
            err = float(jnp.abs(got[check].astype(jnp.float32)
                                - want.astype(jnp.float32)).max())
            if not err <= TOLERANCE:
                raise ValueError(f"{err} from the oracle, over "
                                 f"{TOLERANCE}: not timed")
        except Exception as e:  # the others still run; the call fails
            failed += 1
            print(json.dumps({"variant": name, "layout": part,
                              "into_answer": into, "error": str(e)[:300]}),
                  flush=True)
            continue
        first = got if first is None else first
        trace_dir = os.path.join(args.out, f"trace_{part}_{into}_{k}")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.calls):
                got = fn(q, k_pool, v_pool, table, lens)
            jax.block_until_ready(got)
        ms = kernel_ms(trace_dir, KERNEL)
        med = statistics.median(ms)
        line = json.dumps({
            "variant": name, "layout": part, "into_answer": into,
            "plan": plan or (list(rule) if mod is here else None),
            "cell": args.cell, "window": args.window,
            "device": jax.devices()[0].device_kind,
            "heads": [q.shape[1], hkv],
            "rows": rows, "live_rows": live,
            "keys": keys, "table_width": table.shape[1],
            "kernel_ms_median": med, "kernel_ms_min": min(ms),
            "kernel_ms_max": max(ms), "events": len(ms),
            "roofline_share": 100 * least_ms / med,
            "us_a_row_beyond_bytes": 1e3 * (med - least_ms) / live,
            "max_abs_err_vs_oracle": err,
            # same bytes, same products, same order: a variant under the
            # first one's plan gives the first one's bits
            "bits_of_the_first": bool(jnp.array_equal(got, first))})
        print(line, flush=True)
        with open(os.path.join(args.out, "results.jsonl"), "a") as f:
            f.write(line + "\n")     # the call shows its last lines only
    return failed


if __name__ == "__main__":
    sys.exit(main())
