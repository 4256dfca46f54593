"""One chunk of chunked prefill alone, on the chip, at a benchmark cell's
shapes: ``decoder.prefill_suffix_into_pages`` for a model of several kinds
of layer, 512 tokens that continue a prefix of ``--prefix-pages`` pages in
the slot's state, with weights drawn on the device and the pools a cell's
size, timed by the device's own clock (a ``jax.profiler`` trace) and
reduced to device milliseconds a chunk by ``jax.named_scope`` and by
operation (``benchmark/lib/xspans.py``). The benchmark's traced window
holds decode only, so this is where a cell's ``setup_s`` is read apart.

    chiprun -- python tools/trace_prefill_chunk.py \
        [--preset phi-4-mini-flash-reasoning --pages 10987 --slots 129] \
        [--prefix-pages 64,256] [--chunks 3]

Prints one JSON line a prefix length; fails without a TPU."""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

def reduce(trace_dir: str, program: str, top: int) -> dict:
    """Device ms a run of ``jit_<program>`` in the newest trace under
    ``trace_dir``: whole programs, by the innermost leaf scope an
    operation lies under of those the program declares
    (``models/scopes.py``, as ``benchmark/lib/account.py`` partitions a
    decode step), and the ``top`` operations with their scope."""
    from benchmark.lib import account, tracered, xspans
    from polyrl_tpu.models.scopes import LEAF_SCOPES

    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    trace = xspans.load(path)
    progs = xspans.whole_programs(trace, f"jit_{program}")
    plane = trace["device"][sorted(trace["device"])[0]]
    by, ops = collections.Counter(), collections.Counter()
    for name, scope_path, start, dur in plane["ops"]:
        # (a ``lax.cond``'s own event spans its branch's operations, as
        # a loop's does; the instruction is named ``cond``)
        if (xspans._is_container(name)
                or tracered.op_key(name).split(" ")[0] == "cond"
                or not any(a <= start < b for a, b in progs)):
            continue
        at = account.innermost(scope_path, LEAF_SCOPES)
        by[at] += dur
        ops[f"{at}: {tracered.op_key(name)}"] += dur
    n = max(len(progs), 1)
    return {"programs": len(progs),
            "program_ms": sum(b - a for a, b in progs) / 1e6 / n,
            "by_scope_ms": {k: v / 1e6 / n for k, v in by.most_common()},
            "top_ops_ms": [(k, v / 1e6 / n) for k, v in ops.most_common(top)]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="phi-4-mini-flash-reasoning")
    ap.add_argument("--pages", type=int, default=10987)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--slots", type=int, default=129)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--prefix-pages", default="64")
    ap.add_argument("--chunks", type=int, default=3)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "trace_prefill_chunk"))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 1

    from polyrl_tpu.models import decoder

    cfg = decoder.get_config(args.preset)
    params = jax.jit(lambda k: decoder.init_params(k, cfg))(
        jax.random.PRNGKey(args.seed))
    pools = decoder.make_paged_pools(cfg, args.pages, args.page_size,
                                     slots=args.slots)
    ids = jax.random.randint(jax.random.PRNGKey(1), (args.chunk,), 0,
                             cfg.vocab_size, jnp.int32)
    own = args.chunk // args.page_size

    def prefill_chunk(params, paged, state, ids, n, at, pre_pages, pages,
                      slot):
        return decoder.prefill_suffix_into_pages(
            params, cfg, ids, n, at, (paged, state), pre_pages, pages, slot)

    os.makedirs(args.out, exist_ok=True)
    for n_pre in map(int, args.prefix_pages.split(",")):
        fn = jax.jit(prefill_chunk, donate_argnums=(1, 2))
        operands = (ids, jnp.int32(args.chunk),
                    jnp.int32(n_pre * args.page_size),
                    1 + jnp.arange(n_pre, dtype=jnp.int32),
                    1 + n_pre + jnp.arange(own, dtype=jnp.int32),
                    jnp.int32(1))
        pools, logits = jax.block_until_ready(
            fn(params, *pools, *operands))
        trace_dir = os.path.join(args.out, f"prefix{n_pre}")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.chunks):
                pools, logits = fn(params, *pools, *operands)
            jax.block_until_ready(logits)
        print(json.dumps({"preset": args.preset, "prefix_pages": n_pre,
                          "chunk": args.chunk,
                          "device": jax.devices()[0].device_kind,
                          **reduce(trace_dir, "prefill_chunk", args.top)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
