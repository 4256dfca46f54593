"""The absorbed latent-attention decode kernel alone, on the chip, at a
benchmark cell's shapes: ``latent_paged_attention_pallas`` over the pool,
table and context lengths that the cell's decode step hands it, timed by
the device's own clock (a ``jax.profiler`` trace of the calls) and checked
against the gather-based oracle on a few rows.

    chiprun -- python tools/bench_latent_attention.py \
        [--heads 128] [--mix rollout-long-latent] \
        [--into-answer 564,1247,1930] \
        [--plans "32,2,2,256;32,2,2,1024"] [--also-tree .parent]

``--plans`` times the kernel under other (pages a block, sub-blocks a
block, buffers, keys a piece of a row's last block) than
``mla_attention._block_plan`` returns for the shapes: the sweep behind the
rule's constants. ``--also-tree`` times other checkouts' kernels beside
this one (parent against change in one call; several separated by
commas). ``--into-answer`` is a LIST of how many tokens every row has
generated. The tool gives every row the same number, and a row's last
block is what the variants differ in: at one offset the rows that have
just crossed a block's edge, and how far the others are into a piece, are
one draw of what a window walks through (a row's last block fills once
every 2,048 steps), and a plan that wins there may lose a third of a block
on (ROADMAP Queue 1 item 4 found the same of
``tools/bench_paged_attention.py``). So the default is three offsets a
third of a block apart, and a plan is judged on all three. Prints one JSON
line a variant and offset (time, share of the roof, keys the plan
multiplies over keys live); fails without a TPU."""

from __future__ import annotations

import argparse
import functools
import glob
import importlib.util
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

RANK, WIDTH, PAGE = 512, 640, 64
KERNEL = "latent_paged_attention"


def cell_lengths(mix_name: str, into_answer: int) -> list[int]:
    """The contexts a decode step of the cell's window sees: the mix's
    prompt lengths plus what has been generated."""
    from benchmark.lib import traffic

    mix = traffic.load_mix(mix_name)
    n = int(mix["offered_requests"])
    return [t + into_answer
            for t in traffic.size_set(mix["prompt_tokens"], n)]


def inputs(lengths: list[int], heads: int, table_width: int, seed: int):
    """q, pool, table, lens: one more row than requests (the engine's
    spare slot, dead), every live row's pages its own, drawn at random
    from a pool a ninth larger than what the rows hold."""
    rng = np.random.default_rng(seed)
    s = len(lengths) + 1
    pages = [-(-t // PAGE) for t in lengths]
    n_pages = 1 + int(sum(pages) * 1.12)
    order = rng.permutation(np.arange(1, n_pages))
    table = np.zeros((s, table_width), np.int32)
    at = 0
    for r, n in enumerate(pages):
        table[r, :n] = order[at:at + n]
        at += n
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    pool = jax.random.normal(k1, (1, n_pages, PAGE, WIDTH), jnp.bfloat16)
    pool = pool.at[..., RANK + 64:].set(0)
    q = jax.random.normal(k2, (s, heads, WIDTH), jnp.bfloat16)
    lens = jnp.asarray(lengths + [0], jnp.int32)
    return q, pool, jnp.asarray(table), lens


def load_module(tree: str, name: str = "mla_attention"):
    """``polyrl_tpu.ops.<name>`` of another checkout, under a name of its
    own (its imports resolve to this tree's package, which the kernel file
    shares only helpers with)."""
    path = os.path.join(tree, "polyrl_tpu", "ops", name + ".py")
    spec = importlib.util.spec_from_file_location(
        name + "_" + os.path.basename(os.path.abspath(tree)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_ms(trace_dir: str, kernel: str = KERNEL) -> list[float]:
    """Device durations of the kernel's events in the newest trace."""
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            out += [e.duration_ns / 1e6 for e in line.events
                    if e.name.lstrip("%").startswith(kernel)]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mix", default="rollout-long-latent")
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--into-answer", default="564,1247,1930",
                    help="tokens generated so far, a list: 64 warm + part "
                         "of the window, a third of a 2,048-key block apart")
    ap.add_argument("--table-width", type=int, default=320)
    ap.add_argument("--live", type=int, default=0,
                    help="only every (rows // live)-th request is live, the "
                         "others' rows are dead: a batch still filling")
    ap.add_argument("--plans", default="")
    ap.add_argument("--also-tree", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "bench_latent_attention"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 1

    from polyrl_tpu.ops import mla_attention as here

    scale = 192 ** -0.5
    rule = here._block_plan(args.heads, WIDTH, RANK, PAGE, 2, args.table_width)
    variants = [("change", here, None)]
    variants += [(f"change {p}", here, tuple(int(x) for x in p.split(",")))
                 for p in args.plans.split(";") if p]
    variants += [(tree, load_module(tree), None)
                 for tree in args.also_tree.split(",") if tree]
    os.makedirs(args.out, exist_ok=True)
    for into in (int(t) for t in args.into_answer.split(",")):
        lengths = cell_lengths(args.mix, into)
        if args.live:
            every = len(lengths) // args.live
            lengths = [t if k % every == 0 else 0
                       for k, t in enumerate(lengths)]
        q, pool, table, lens = inputs(lengths, args.heads, args.table_width,
                                      args.seed)
        check = jnp.asarray([0, len(lengths) // 2, len(lengths) - 1,
                             len(lengths)])
        want = here.latent_paged_attention_ref(q[check], pool, table[check],
                                               lens[check], RANK, scale)
        rows = sum(lengths)
        # the least the chip could take: the slower of the FLOP and byte roofs
        least_ms = 1e3 * max(2 * args.heads * (2 * RANK + 64) * rows / 197e12,
                             2 * (RANK + 64) * rows / 819e9)
        for k, (name, mod, plan) in enumerate(variants):
            fn = mod.latent_paged_attention_pallas
            if plan is not None:
                fn = functools.partial(fn, plan=plan)
            try:
                got = jax.block_until_ready(fn(q, pool, table, lens, RANK,
                                               scale))
            except Exception as e:  # a plan the compiler refuses: say so
                print(json.dumps({"variant": name, "error": str(e)[:300]}),
                      flush=True)
                continue
            err = float(jnp.abs(got[check].astype(jnp.float32) - want).max())
            trace_dir = os.path.join(args.out, f"trace{into}_{k}")
            with jax.profiler.trace(trace_dir):
                for _ in range(args.calls):
                    got = fn(q, pool, table, lens, RANK, scale)
                jax.block_until_ready(got)
            ms = kernel_ms(trace_dir)
            med = statistics.median(ms)
            line = {
                "variant": name, "into_answer": into,
                "plan": plan or (list(rule) if mod is here else None),
                "device": jax.devices()[0].device_kind, "heads": args.heads,
                "rows": len(lengths) + 1, "latent_rows": rows,
                "out": [str(got.dtype), list(got.shape)],
                "kernel_ms_median": med, "kernel_ms_min": min(ms),
                "kernel_ms_max": max(ms), "events": len(ms),
                "roofline_share": 100 * least_ms / med,
                "max_abs_err_vs_oracle": err}
            if mod is here:    # another tree's plan may mean another form
                line["keys_multiplied_over_live"] = here.keys_multiplied(
                    lengths, plan or rule, PAGE) / rows
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
