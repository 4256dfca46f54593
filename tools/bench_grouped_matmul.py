"""The grouped matmul of the MoE expert projections alone, on the chip, at
a benchmark cell's shapes: ``grouped_matmul_pallas`` over one layer's
experts of the WHOLE stack (so the layer's offset into it is real), the
rows a decode step of the cell hands it, its two calls apart (gate+up with
SwiGLU, then down), timed by the device's own clock (a ``jax.profiler``
trace of the calls) and checked against ``_ragged`` on three tiles: a
variant further from it than ``TOLERANCE``, or one that fails to build or
run, is not timed, and the call then exits 1 after the other variants.

    chiprun -- python tools/bench_grouped_matmul.py \
        [--config dots.vlm1] [--tile 16] [--plans "256/128,1024"] \
        [--also-tree .parent]

``--config`` takes the widths, the experts held, the choices a token and
the sparse layers from the configuration's file and the rows from its
cell's traffic (every slot and the engine's spare row); each row's choices
are drawn evenly over the experts the router routes over, and those that
fall on an expert held elsewhere are left out, as the program leaves them.
``--rows`` and ``--tile`` take other rows a step and other rows a tile
than the cell's and ``row_tile``'s. ``--plans`` times the kernel under
other rows a slab than ``grouped_matmul._slab_plan`` returns for the
shapes (gate+up's, then after a ``/`` down's, which else keeps the
rule's). ``--also-tree`` times other checkouts' kernels
beside this one (parent against change in one call; several,
comma-separated). After the kernels, a layer's experts WHOLE in both forms
a decode step can take (``--forms tiled,rows``; ``blocks._expert_mix``: the
rows gathered into tiles and read back by XLA's operations around the two
kernels; ``blocks._expert_rows``: the kernels take their rows by table and
sum them back), each ONE program from the router's outputs (the choices
and their weights) on: the sort, the tables, the calls; a line a form with
the program's device time a call, its kernels' share of it, and how far
the two forms' results lie apart. Prints one JSON line a variant and call;
fails without a TPU."""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import re
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench_latent_attention import load_module  # noqa: E402

KERNEL = "grouped_matmul"
HBM_BYTES_S = 819e9     # one TPU v5e chip (Google Cloud, "TPU v5e")
# bfloat16 outputs against ``ragged_dot``'s, as a share of the largest:
# sound variants differ by a rounding of the last bit
TOLERANCE = 0.01


def cell_shapes(config: str) -> dict:
    """Widths, experts (held here; routed over), choices a token, sparse
    layers and a decode step's rows of a configuration of
    ``BENCHMARK.json`` under its first cell's traffic."""
    from benchmark.lib import traffic

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    file = next(c["file"] for c in bench["configs"] if c["name"] == config)
    work = next(w for w in bench["workloads"] if w["config"] == config)
    with open(os.path.join(ROOT, file)) as f:
        cfg = json.load(f)
    published = cfg.get("published") or {}
    return {
        "cell": work["name"],
        "hidden": cfg["hidden_size"], "inter": cfg["moe_intermediate_size"],
        "held": cfg["num_experts"],
        "routed": int(published.get("num_experts")
                      or published.get("n_routed_experts")
                      or cfg["num_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "stack": cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace",
                                                    0),
        "rows": traffic.load_mix(work["traffic"])["engine"]["max_slots"] + 1}


def step_choices(shapes: dict, rows: int, seed: int) -> np.ndarray:
    """A step's choices [rows, top_k]: every row but the spare one chooses
    ``top_k`` distinct experts evenly, the held ones are the first; a
    choice held elsewhere, and each of the spare row's, is expert ``held``
    (none), as ``blocks._moe_mlp`` hands them on."""
    rng = np.random.default_rng(seed)
    held = shapes["held"]
    choices = np.full((rows, shapes["top_k"]), held, np.int64)
    for row in range(rows - 1):
        chosen = rng.choice(shapes["routed"], shapes["top_k"], replace=False)
        choices[row] = np.where(chosen < held, chosen, held)
    return choices


def layer_forms(shapes: dict, layer: int) -> dict:
    """A layer's experts from the router's outputs on, a form a program:
    (tokens [N, d], the three stacks [L*E, ..] and their scales or None,
    choices [N, k], weights [N, k]) -> [N, d] float32, as
    ``blocks._moe_mlp`` runs each."""
    from polyrl_tpu.models import blocks

    held, k = shapes["held"], shapes["top_k"]

    def sizes_of(choice):
        return jnp.sum(jax.nn.one_hot(choice, held, dtype=jnp.int32), axis=0)

    def stacks(ws, scales):      # [L*E, ..] as the layers' [L, E, ..]
        from polyrl_tpu.models.quant import QuantWeight

        ws = [w.reshape(-1, held, *w.shape[1:]) for w in ws]
        if scales:
            ws = [QuantWeight(q=q, scale=s.reshape(-1, held, s.shape[-1]))
                  for q, s in zip(ws, scales)]
        return dict(zip(("we_gate", "we_up", "we_down"), ws))

    def tiled(x, ws, scales, choices, top_p):
        experts = stacks(ws, scales)
        choice = choices.reshape(-1)
        order = jnp.argsort(choice, stable=True)
        return blocks._expert_mix(x, experts, layer, order // k,
                                  jnp.argsort(order), choice, top_p,
                                  sizes_of(choice))

    def rows(x, ws, scales, choices, top_p):
        experts = stacks(ws, scales)
        choice = choices.reshape(-1)
        _, order, weight = jax.lax.sort(
            (choice, jnp.arange(choice.shape[0]), top_p.reshape(-1)),
            num_keys=1)
        return blocks._expert_rows(x, experts, layer, order // k, weight,
                                   sizes_of(choice))

    return {"tiled": jax.jit(tiled), "rows": jax.jit(rows)}


def three_tiles(gm, lay, used: int):
    """The first, the middle and the last used tile as a layout of their
    own, for ``_ragged``: (their rows of the tiled layout, that layout)."""
    tile = lay.tile
    picked = np.unique([0, used // 2, used - 1])
    groups = np.asarray(lay.tile_group)[picked]
    at = (picked[:, None] * tile + np.arange(tile)[None, :]).reshape(-1)
    padded = np.zeros(lay.padded_sizes.shape[0], np.int32)
    np.add.at(padded, groups, tile)
    n = at.shape[0]
    sub = gm.TiledLayout(jnp.asarray(groups, jnp.int32),
                         jnp.asarray([len(picked)], jnp.int32),
                         jnp.asarray(padded), jnp.arange(n, dtype=jnp.int32),
                         jnp.ones((n,), bool),
                         jnp.zeros_like(lay.padded_sizes))
    return jnp.asarray(at), sub


def device_ops(trace_dir: str) -> list[tuple[str, float]]:
    """(name, ms) of the events of the first chip's ``XLA Ops`` line in
    the newest trace under ``trace_dir``."""
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)    # owns its events
    return [(e.name, e.duration_ns / 1e6) for plane in data.planes
            if plane.name.startswith("/device:TPU:0")
            for line in plane.lines if line.name == "XLA Ops"
            for e in line.events]


def device_ms(trace_dir: str) -> tuple[float, float]:
    """(every operation's, the kernel's events') device time in the newest
    trace, ms: the device runs one operation at a time."""
    ops = device_ops(trace_dir)
    return (sum(ms for _name, ms in ops),
            sum(ms for name, ms in ops
                if name.lstrip("%").startswith(KERNEL)))


def kernel_events(trace_dir: str) -> list[tuple]:
    """(stacked weights, ms, [rows in VMEM, result in VMEM]) of the
    kernel's events in the newest trace. An event's name
    is its HLO instruction, layouts and all: ``S(1)`` in a layout is
    memory space 1, VMEM, where XLA's memory-space assignment may keep a
    custom call's operand or result."""
    out = []
    for name, ms in device_ops(trace_dir):
        hit = re.match(r"%?" + KERNEL + r"\S* = \w+\[\d+,\d+\](\S*) "
                       r"custom-call\((.*?)\), custom_call", name)
        if not hit:
            continue
        operands = hit.group(2).split(", ")
        stacks = [o for o in operands
                  if re.match(r"(bf16|s8)\[\d+,\d+,\d+\]", o)]
        rows = operands[operands.index(stacks[0]) - 1]
        out.append((len(stacks), ms,
                    ["S(1)" in rows.split(" %")[0], "S(1)" in hit.group(1)]))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="dots.vlm1")
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--tile", type=int, default=0)
    ap.add_argument("--int8", action="store_true",
                    help="int8 experts with a scale an output channel")
    ap.add_argument("--plans", default="")
    ap.add_argument("--also-tree", default="")
    ap.add_argument("--forms", default="tiled,rows",
                    help="a layer's experts whole, in these forms")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "bench_grouped_matmul"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 1

    from polyrl_tpu.ops import grouped_matmul as here

    shapes = cell_shapes(args.config)
    rows = args.rows or shapes["rows"]
    held, stack = shapes["held"], shapes["stack"]
    layer = stack // 2
    m = rows * shapes["top_k"]
    tile = args.tile or here.row_tile(m, held)
    choices = step_choices(shapes, rows, args.seed)
    sizes = np.bincount(choices.reshape(-1), minlength=held + 1)[:held]
    lay = here.tiled_layout(jnp.asarray(sizes, jnp.int32), m, tile)
    used = int(lay.tiles_used[0])
    lay = lay._replace(
        tile_group=lay.tile_group + layer * held,
        padded_sizes=jnp.pad(lay.padded_sizes,
                             (layer * held, (stack - 1 - layer) * held)))
    hit = int((sizes > 0).sum())

    variants = [("change", here, None)]
    for p in args.plans.split(","):     # "gate+up's[/down's]"
        if p:
            slabs = [int(q) for q in p.split("/")]
            variants.append((f"change {p}", here, (slabs + [None])[:2]))
    variants += [(tree, load_module(tree, "grouped_matmul"), None)
                 for tree in args.also_tree.split(",") if tree]
    os.makedirs(args.out, exist_ok=True)
    d, f = shapes["hidden"], shapes["inter"]
    kx, *kws = jax.random.split(jax.random.PRNGKey(args.seed % (1 << 31)), 4)
    rows_in = jax.random.normal(kx, (lay.src.shape[0], d), jnp.bfloat16)
    ws = [0.02 * jax.random.normal(kw, (stack * held, *shape), jnp.bfloat16)
          for kw, shape in zip(kws, [(d, f), (d, f), (f, d)])]
    scales = None
    if args.int8:
        ws = [jnp.clip(jnp.round(w * 2000), -127, 127).astype(jnp.int8)
              for w in ws]
        scales = [jnp.full((stack * held, w.shape[-1]), 5e-4, jnp.float32)
                  for w in ws]
    at, sub = three_tiles(here, lay, used)

    def calls(ws, scales):      # (weights, scales) of gate+up and of down
        return ((tuple(ws[:2]), scales and tuple(scales[:2])),
                ((ws[2],), scales and (scales[2],)))

    def layer_of(mod, slabs, rows_in, ws, scales):
        """The two calls as a layer's step has them: the rows made by an
        operation of the same program, ``hidden`` handed from the first
        call to the second (XLA's memory-space assignment then places
        them as it does in the step), the checked rows gathered out."""
        fns = [functools.partial(mod.grouped_matmul_pallas, tile=tile,
                                 **({"slab": tk} if tk else {}))
               for tk in slabs or (None, None)]
        gate_up, down = calls(ws, scales)
        x = jnp.where(lay.live[:, None], rows_in, 0)
        hidden = fns[0](x, gate_up[0], lay.tile_group, lay.tiles_used,
                        gate_up[1])
        ys = fns[1](hidden, down[0], lay.tile_group, lay.tiles_used, down[1])
        return hidden[at], ys[at]

    def apart(got, want) -> float:
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    gate_up, down = calls(ws, scales)
    want_hidden = here._ragged(jnp.where(lay.live[:, None], rows_in, 0)[at],
                               *gate_up, sub)
    failed = 0
    for v, (name, mod, slabs) in enumerate(variants):
        fn = jax.jit(functools.partial(layer_of, mod, slabs))
        try:
            hidden, ys = jax.block_until_ready(fn(rows_in, ws, scales))
            # the down call against ``_ragged`` of the rows IT was handed
            errs = [apart(hidden, want_hidden),
                    apart(ys, here._ragged(hidden, *down, sub))]
            if not max(errs) <= TOLERANCE:
                raise ValueError(f"{errs} of the largest from _ragged, over "
                                 f"{TOLERANCE}: not timed")
        except Exception as e:      # the others still run; the call fails
            failed += 1
            print(json.dumps({"variant": name, "error": str(e)[:300]}),
                  flush=True)
            continue
        trace_dir = os.path.join(args.out, f"trace_{v}")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.calls):
                got = fn(rows_in, ws, scales)
            jax.block_until_ready(got)
        rule = getattr(mod, "_slab_plan", None)
        events = kernel_events(trace_dir)
        for c, (call, n_w, (k, n)) in enumerate([("gate_up", 2, (d, f)),
                                                 ("down", 1, (f, d))]):
            ms = [e[1] for e in events if e[0] == n_w]
            in_vmem = next(e[2] for e in events if e[0] == n_w)
            med = statistics.median(ms)
            weight_bytes = hit * n_w * k * n * ws[0].dtype.itemsize
            line = json.dumps({
                "variant": name, "call": call, "config": args.config,
                "k_n": [k, n], "weights": [n_w, str(ws[0].dtype)],
                "slab": slabs and slabs[c] or (rule and rule(
                    k, n, ws[0].dtype.itemsize, n_w)),
                "device": jax.devices()[0].device_kind,
                "rows": rows, "tile": tile, "tiles_used": used,
                "experts_hit": hit, "layer_of": [layer, stack],
                "rows_and_out_in_vmem": in_vmem,
                "weight_mb": weight_bytes / 1e6,
                "kernel_ms_median": med, "kernel_ms_min": min(ms),
                "kernel_ms_max": max(ms), "events": len(ms),
                "gb_s": weight_bytes / med / 1e6,
                "roofline_share": 100 * weight_bytes / HBM_BYTES_S
                / (med / 1e3),
                "rel_err_vs_ragged": errs[c]})
            print(line, flush=True)
            with open(os.path.join(args.out, "results.jsonl"), "a") as out:
                out.write(line + "\n")   # the call shows its last lines only
    # a layer's experts whole, a form a program
    forms = layer_forms(shapes, layer)
    x = jax.random.normal(kx, (rows, d), jnp.bfloat16)
    top_p = jnp.where(jnp.asarray(choices) < held, jax.random.uniform(
        kx, choices.shape, jnp.float32), 0.0)
    operands = (x, ws, scales, jnp.asarray(choices, jnp.int32), top_p)
    results = {}
    for v, name in enumerate(f for f in args.forms.split(",") if f):
        try:
            results[name] = jax.block_until_ready(forms[name](*operands))
        except Exception as e:
            failed += 1
            print(json.dumps({"form": name, "error": str(e)[:300]}),
                  flush=True)
            continue
        trace_dir = os.path.join(args.out, f"trace_form_{v}")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.calls):
                got = forms[name](*operands)
            jax.block_until_ready(got)
        total, kernel = device_ms(trace_dir)
        weight_bytes = hit * 3 * d * f * ws[0].dtype.itemsize
        line = json.dumps({
            "form": name, "config": args.config, "rows": rows, "tile": tile,
            "device": jax.devices()[0].device_kind, "experts_hit": hit,
            "weights": str(ws[0].dtype), "layer_of": [layer, stack],
            "program_ms": total / args.calls,
            "kernels_ms": kernel / args.calls,
            "around_the_kernels_ms": (total - kernel) / args.calls,
            "roofline_share": 100 * weight_bytes / HBM_BYTES_S
            / (total / args.calls / 1e3),
            "rel_diff_to_tiled": apart(results[name], results["tiled"])
            if "tiled" in results else None})
        print(line, flush=True)
        with open(os.path.join(args.out, "results.jsonl"), "a") as out:
            out.write(line + "\n")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
