"""Whether two checkouts build the SAME programs for a preset: the SHA-256
of the StableHLO that the engine's fused decode step (8 steps, the token
drawn inside the head), its 512-token prefill chunk over 64 pages of
prefix and the program that draws the weights (``init``: the draw order
decides every leaf's key, and a routed cell's rate follows its weights)
lower to for a TPU, kernels included, from abstract arguments at the
preset's widths. No chip and no weights: a PR that edits code an
accepted benchmark cell shares can show here that the cell's programs are
the parent's to the byte.

    python tools/same_programs.py [--keep DIR] <other checkout> [preset ...]

``--keep DIR`` leaves the texts in ``DIR/other`` and ``DIR/here`` (``diff``
them where a digest differs: a difference is a change of behaviour until
the texts show otherwise): a program's StableHLO as digested (``.mlir``)
and as ``readable`` makes it (``.txt``), which is the one to ``diff``: a
kernel's body decoded from its base64 to MLIR without locations, so that a
line that moved in the kernel's own file does not show, and the private
functions numbered in the order they appear (PR 52's GQA steps differ from
their parent's in the kernels' memory colours alone, and read so).

Each checkout is copied to the same scratch path in turn (a kernel's
serialized body carries its source's path) and each preset lowered in a
process of its own, without caller frames in the locations (a line that
moved in a file is no other program). A process a preset, because a
kernel's serialized body carries the locations of its operations, and JAX
traces a jitted helper of ``jax.numpy`` (``//``, ``%``, ``where``,
``minimum``) once a process and keeps the location of that FIRST call: in
one process Ling's latent kernel named the line of ``paged_attention.py``
where qwen's step had first divided, and read different once that line had
moved (PR 44). Prints a line a program and exits 1 where two differ; a
preset that the other checkout does not have reads ``new``."""

from __future__ import annotations

import base64
import functools
import hashlib
import json
import math
import os
import re
import shutil
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRESETS = ("qwen2.5-7b", "qwen3-30b-a3b", "ling-3.0-flash-share4",
           "dots.vlm1-share16", "zaya1-8b-depth12",
           "phi-4-mini-flash-reasoning", "laguna-xs.2-share8", "ouro-2.6b",
           "minicpm-sala", "nemotron-3-nano-30b-a3b-share8")


def readable(text: str) -> str:
    """A lowered program's text for ``diff``: each ``tpu_custom_call``'s
    serialized Mosaic module decoded and printed without its locations
    behind the call's line (whose config keeps everything but the body),
    and the private functions' numbers (``@_where_188``: a counter of the
    process) replaced by their order of appearance."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True    # the ``stable_mosaic`` wrapper

    def decoded(m):
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            body = module.operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22<below>\\22' + m.group(2) + "\n" + body

    text = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]*)\\22(.*)', decoded,
                  text)
    order: dict[str, str] = {}
    return re.sub(r"@(\w+?)_(\d+)\b", lambda m: order.setdefault(
        m.group(0), f"@{m.group(1)}_#{len(order)}"), text)


def digests(presets, keep: str = "") -> dict:
    """{"<preset> <program>": sha256} from the checkout on ``sys.path``;
    the texts too, in the directory ``keep``."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.default_backend = lambda: "tpu"     # the dispatchers' question
    from polyrl_tpu.models import decoder

    rows, pages, width = 128, 2048, 192
    arg = jax.ShapeDtypeStruct
    out = {}
    for preset in presets:
        if preset not in decoder.PRESETS:
            continue    # a checkout from before the preset: ``new`` below
        cfg = decoder.get_config(preset)
        # pages of 64 tokens, or what a tiny preset's window is whole
        # pages of (a ring is, ``cache_spec.Ring``)
        page = math.gcd(64, cfg.sliding_window or 64)
        params = jax.eval_shape(
            lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
        pools = jax.eval_shape(lambda: decoder.make_paged_pools(
            cfg, pages, page, slots=rows + 1))

        def step(params, paged, state, rng, table, lens, last, active, temps):
            def body(carry, _):
                paged, state, rng, lens, last = carry
                rng, sub = jax.random.split(rng)
                head = functools.partial(decoder.head_and_sample, rng=sub,
                                         temps=temps)
                (tok, logp), pools, load = decoder.forward_paged_decode(
                    params, cfg, last, lens, (paged, state), table, lens,
                    active=active, head_fn=head)
                return (*pools, rng, lens + 1, tok), (tok, logp, load)
            return jax.lax.scan(body, (paged, state, rng, lens, last), None,
                                length=8)

        def chunk(params, paged, state, ids, n, at, pre_pages, own, slot):
            return decoder.prefill_suffix_into_pages(
                params, cfg, ids, n, at, (paged, state), pre_pages, own, slot)

        i32 = jnp.int32
        programs = {
            "step": (step, (arg((2,), jnp.uint32), arg((rows, width), i32),
                            arg((rows,), i32), arg((rows,), i32),
                            arg((rows,), jnp.bool_),
                            arg((rows,), jnp.float32))),
            "prefill": (chunk, (arg((512,), i32), arg((), i32), arg((), i32),
                                arg((64,), i32), arg((512 // page,), i32),
                                arg((), i32)))}
        texts = {name: jax.jit(fn, donate_argnums=(1, 2)).trace(
            params, *pools, *rest).lower(
                lowering_platforms=("tpu",)).as_text()
                 for name, (fn, rest) in programs.items()}
        texts["init"] = jax.jit(
            lambda key: decoder.init_params(key, cfg)).trace(
                arg((2,), jnp.uint32)).lower(
                    lowering_platforms=("tpu",)).as_text()
        for name, text in texts.items():
            out[f"{preset} {name}"] = hashlib.sha256(text.encode()).hexdigest()
            if keep:
                for kind, form in ((".mlir", text), (".txt", readable(text))):
                    with open(os.path.join(keep, f"{preset}.{name}{kind}"),
                              "w") as f:
                        f.write(form)
    return out


def main(argv) -> int:
    if argv and argv[0] == "--digests":
        sys.path.insert(0, os.getcwd())
        print(json.dumps(digests(argv[2:], argv[1])))
        return 0
    keep = ""
    if argv and argv[0] == "--keep":
        keep, argv = os.path.abspath(argv[1]), argv[2:]
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    other, presets = os.path.abspath(argv[0]), argv[1:] or list(PRESETS)
    got = []
    with tempfile.TemporaryDirectory() as scratch:
        at = os.path.join(scratch, "tree")
        for checkout, side in ((other, "other"), (ROOT, "here")):
            texts = os.path.join(keep, side) if keep else ""
            if texts:
                os.makedirs(texts, exist_ok=True)
            shutil.rmtree(at, ignore_errors=True)
            shutil.copytree(os.path.join(checkout, "polyrl_tpu"),
                            os.path.join(at, "polyrl_tpu"),
                            ignore=shutil.ignore_patterns("__pycache__"))
            found = {}
            for preset in presets:
                ran = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), "--digests",
                     texts, preset], cwd=at, check=True, capture_output=True,
                    text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
                found.update(json.loads(ran.stdout.strip().splitlines()[-1]))
            got.append(found)
    same = True
    for key in got[1]:
        if key not in got[0]:
            print(f"{key}: new {got[1][key][:16]}")
            continue
        verdict = "same" if got[0][key] == got[1][key] else "DIFFERENT"
        same &= verdict == "same"
        print(f"{key}: {verdict} {got[0][key][:16]} {got[1][key][:16]}")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
