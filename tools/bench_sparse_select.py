"""A sparse layer's selection stage alone, on the chip, at the cell's
shapes: what ``mixers/sparse.py::step`` runs under ``sparse_select`` (the
pooled key a row's token completes written to the store,
``_complete_pooled``, then ``selected_table``) for as many layers as the
cell's share has sparse ones, in BOTH forms ``selected_table`` can take:
``kernel`` (``ops/sparse_select.py``: a row's own pooled pages) and
``jnp`` (the gather at the table's width, the oracle), each ONE donated
program whose queries are made by an operation of the same program, so
that XLA's memory-space assignment places the stores, the queries and the
tables as it does in the step (a tool that hands operands in as arguments
measures another kernel: ``PERF.md`` section 7), timed by the device's
own clock (a ``jax.profiler`` trace of the calls).

    chiprun -- python tools/bench_sparse_select.py \
        [--cell minicpm-sala.rollout-long-sparse-linear] [--layers 3] \
        [--forms kernel,jnp] [--decoded 4096]

The rows' lengths are the cell's: its prompts (``traffic.size_set`` of the
mix's ``prompt_tokens``: Pareto 8k-24k at the slots' midpoint quantiles)
plus 0 to ``--decoded`` tokens drawn evenly, the engine's spare row dead;
each row's pages are a run of a SHUFFLED pool. A line a form: the
program's device time a layer, the kernel's events' share of it, the
largest other operations by name, the pooled keys scored and their share
of 819 GB/s at 1,024 B each (``benchmark/lib/costs_sala.py::
select_bytes``'s count), and how many tables and counts part from the
first form's. Prints one JSON line a form; fails without a TPU."""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from types import SimpleNamespace

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench_grouped_matmul import device_ops  # noqa: E402

KERNEL = "sparse_select"
HBM_BYTES_S = 819e9     # one TPU v5e chip (Google Cloud, "TPU v5e")


def cell_rows(cell: str, decoded: int, seed: int):
    """(the model's configuration, a step's rows, the table's width, the
    pool's pages, the live rows' lengths) of a cell of ``BENCHMARK.json``."""
    from benchmark.lib import traffic
    from polyrl_tpu.models import decoder

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    work = next(w for w in bench["workloads"] if w["name"] == cell)
    mix = traffic.load_mix(work["traffic"])
    engine = mix["engine"]
    cfg = decoder.get_config(work["config"])
    slots = engine["max_slots"]
    page = engine["page_size"]
    width = engine["max_seq_len"] // page
    rng = np.random.default_rng(seed)
    lens = (np.asarray(traffic.size_set(mix["prompt_tokens"], slots))
            + rng.integers(0, decoded + 1, slots))
    n_pages = 1 + int(np.ceil((lens + decoded) / page).sum())
    return cfg, slots + 1, width, n_pages, lens


def stage(cfg, layers: int, form: str):
    """The stage over ``layers`` sparse layers as one program: (x [S, d'],
    wq [L, d', H * D], the layers' K pools and pooled stores, the page
    table, the lengths, who is live) -> (the stores, each layer's table,
    keys and count)."""
    from polyrl_tpu.models.mixers import sparse
    from polyrl_tpu.ops import sparse_select

    h, d = cfg.num_heads, cfg.head_dim_

    def run(x, wq, k_pools, stores, page_table, lens, live):
        ctx = SimpleNamespace(page_table=page_table, attn_lens=lens,
                              live=live)
        out = []
        for layer in range(layers):
            q = jnp.dot(x, wq[layer]).reshape(-1, h, d)
            q = (q * jax.lax.rsqrt(jnp.mean(jnp.square(
                q.astype(jnp.float32)), -1, keepdims=True) + 1e-6
            ).astype(q.dtype))
            store = sparse._complete_pooled(cfg, k_pools[layer],
                                            stores[layer], ctx)
            out.append((store, *sparse.selected_table(
                cfg, q, store, ctx, k_pools[layer].shape[1])))
        return out

    def traced(*args):
        # the tool steers the dispatcher while the form is traced
        was = sparse_select.in_kernel
        if form == "jnp":
            sparse_select.in_kernel = lambda *a: False
        try:
            return run(*args)
        finally:
            sparse_select.in_kernel = was

    return jax.jit(traced, donate_argnums=(3,))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell",
                    default="minicpm-sala.rollout-long-sparse-linear")
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--forms", default="kernel,jnp")
    ap.add_argument("--decoded", type=int, default=4096)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "bench_sparse_select"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 1

    from polyrl_tpu.models.mixers import sparse

    cfg, rows, width, n_pages, lens = cell_rows(args.cell, args.decoded,
                                                args.seed)
    stride, kernel, block, r = sparse.geometry(cfg)
    hkv, h, d = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim_
    rng = np.random.default_rng(args.seed)
    shuffled = rng.permutation(np.arange(1, n_pages))
    table = np.zeros((rows, width), np.int32)
    at = 0
    for row, n in enumerate(lens):
        m = -(-int(n) // block)
        table[row, :m] = shuffled[at:at + m]
        at += m
    lens_all = np.concatenate([lens, [0]]).astype(np.int32)
    live = np.arange(rows) < rows - 1
    keys = jax.random.split(jax.random.PRNGKey(args.seed % (1 << 31)),
                            2 + 2 * args.layers)
    x = jax.random.normal(keys[0], (rows, 256), jnp.bfloat16)
    wq = jax.random.normal(keys[1], (args.layers, 256, h * d), jnp.bfloat16)
    k_pools = [jax.random.normal(k, (hkv, n_pages, block, d), jnp.bfloat16)
               for k in keys[2:2 + args.layers]]

    def stores():
        return [jax.random.normal(k, (n_pages, r * hkv, d), jnp.float32)
                for k in keys[2 + args.layers:]]

    scored = int(np.maximum((lens - kernel) // stride + 1, 0).sum()) * hkv
    operands = (x, wq, k_pools)
    tail = (jnp.asarray(table), jnp.asarray(lens_all), jnp.asarray(live))
    os.makedirs(args.out, exist_ok=True)
    first, failed = None, 0
    for v, form in enumerate(f for f in args.forms.split(",") if f):
        fn = stage(cfg, args.layers, form)
        try:
            got = jax.block_until_ready(fn(*operands, stores(), *tail))
        except Exception as e:
            failed += 1
            print(json.dumps({"form": form, "error": str(e)[:400]}),
                  flush=True)
            continue
        tables = [np.asarray(a) for layer in got for a in layer[1:]]
        first = first or tables
        held = [layer[0] for layer in got]
        trace_dir = os.path.join(args.out, f"trace_{v}")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.calls):
                held = [layer[0] for layer in fn(*operands, held, *tail)]
            jax.block_until_ready(held)
        ops = device_ops(trace_dir)
        by_name = collections.Counter()
        for name, ms in ops:
            by_name[name.lstrip("%").split(" ")[0].split(".")[0]] += ms
        total = sum(ms for _n, ms in ops) / args.calls / args.layers
        ours = sum(ms for name, ms in ops
                   if name.lstrip("%").startswith(KERNEL)
                   ) / args.calls / args.layers
        line = json.dumps({
            "form": form, "cell": args.cell, "layers": args.layers,
            "device": jax.devices()[0].device_kind, "rows": rows,
            "table_width": width, "pool_pages": n_pages,
            "pages_a_row": [int(-(-lens.min() // block)),
                            int(-(-lens.max() // block))],
            "pages": int(np.ceil(lens / block).sum()),
            "stage_ms_a_layer": total, "kernel_ms_a_layer": ours,
            "around_the_kernel_ms": total - ours,
            "largest_ops_ms_a_layer": {
                n: ms / args.calls / args.layers
                for n, ms in by_name.most_common(8)},
            "pooled_scored": scored,
            "roofline_share": 100 * scored * d * 4 / HBM_BYTES_S
            / (total / 1e3) if total else None,
            "parts_from_first_form": [
                int((a != b).sum()) for a, b in zip(tables, first)]})
        print(line, flush=True)
        with open(os.path.join(args.out, "results.jsonl"), "a") as out:
            out.write(line + "\n")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
