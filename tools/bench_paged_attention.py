"""The GQA decode kernel alone, on the chip, at a benchmark cell's shapes:
``paged_attention_pallas`` over K and V pools, a page table and the context
lengths that the cell's decode step hands it, timed by the device's own
clock (a ``jax.profiler`` trace of the calls) and checked against the
gather-based oracle on four rows: a variant further from it than
``TOLERANCE``, or one that fails to build or run, is not timed, and the
call then exits 1 after the other variants.

    chiprun -- python tools/bench_paged_attention.py \
        [--cell zaya1-8b.rollout-wide-cca] [--window 512] \
        [--plans "16,4,3;16,1,2"] [--also-tree .parent] \
        [--parts whole,merged2,dead]

``--cell`` takes the heads from the cell's configuration and the rows, the
table's width and the prompt lengths from its traffic file; ``--window``
cuts every context to a ring of that many keys (Phi's window layers).
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="zaya1-8b.rollout-wide-cca")
    ap.add_argument("--into-answer", type=int, default=264,
                    help="tokens generated so far (64 warm + the traced "
                         "part's middle)")
    ap.add_argument("--window", type=int, default=0,
                    help="a ring of this many keys a row (Phi's window "
                         "layers: 512) instead of the whole context")
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
    real = [t + args.into_answer for t in shapes["prompts"]]
    width = shapes["width"]
    if args.window:
        real = [min(t, args.window) for t in real]
        width = args.window // shapes["page"]
    rule = here._block_plan(shapes["hkv"], shapes["page"], shapes["d"], 2,
                            width)
    variants = [("change", here, None)]
    variants += [(f"change {p}", here, tuple(int(x) for x in p.split(",")))
                 for p in args.plans.split(";") if p]
    variants += [(tree, load_module(tree, "paged_attention"), None)
                 for tree in args.also_tree.split(",") if tree]
    os.makedirs(args.out, exist_ok=True)
    failed = 0
    for part in ["real"] + [p for p in args.parts.split(",") if p]:
        lengths = lay_out(real, part, rule[0] * shapes["page"])
        wide = max(width, -(-max(lengths) // shapes["page"]))
        q, k_pool, v_pool, table, lens = inputs(lengths, shapes, wide,
                                                args.seed)
        live = sum(t > 0 for t in lengths)
        check = jnp.asarray([0, len(lengths) // 2, len(lengths) - 1,
                             len(lengths)])
        want = here.paged_attention_ref(q[check], k_pool, v_pool,
                                        table[check], lens[check])
        want = jnp.where((lens[check] > 0)[:, None, None], want, 0)
        # the least the chip could take: every live key's K and V row once
        least_ms = 1e3 * (2 * shapes["hkv"] * shapes["d"] * 2 * sum(lengths)
                          / HBM_BYTES_S)
        for k, (name, mod, plan) in enumerate(variants):
            fn = mod.paged_attention_pallas
            if plan is not None:
                fn = functools.partial(fn, plan=plan)
            try:
                got = jax.block_until_ready(
                    fn(q, k_pool, v_pool, table, lens))
                err = float(jnp.abs(got[check].astype(jnp.float32)
                                    - want.astype(jnp.float32)).max())
                if not err <= TOLERANCE:
                    raise ValueError(f"{err} from the oracle, over "
                                     f"{TOLERANCE}: not timed")
            except Exception as e:  # the others still run; the call fails
                failed += 1
                print(json.dumps({"variant": name, "layout": part,
                                  "error": str(e)[:300]}), flush=True)
                continue
            trace_dir = os.path.join(args.out, f"trace_{part}_{k}")
            with jax.profiler.trace(trace_dir):
                for _ in range(args.calls):
                    got = fn(q, k_pool, v_pool, table, lens)
                jax.block_until_ready(got)
            ms = kernel_ms(trace_dir, KERNEL)
            med = statistics.median(ms)
            line = json.dumps({
                "variant": name, "layout": part,
                "plan": plan or (list(rule) if mod is here else None),
                "cell": args.cell, "window": args.window,
                "device": jax.devices()[0].device_kind,
                "heads": [shapes["hq"], shapes["hkv"]],
                "rows": len(lengths) + 1, "live_rows": live,
                "keys": sum(lengths), "table_width": wide,
                "kernel_ms_median": med, "kernel_ms_min": min(ms),
                "kernel_ms_max": max(ms), "events": len(ms),
                "roofline_share": 100 * least_ms / med,
                "us_a_row_beyond_bytes": 1e3 * (med - least_ms) / live,
                "max_abs_err_vs_oracle": err})
            print(line, flush=True)
            with open(os.path.join(args.out, "results.jsonl"), "a") as f:
                f.write(line + "\n")     # the call shows its last lines only
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
