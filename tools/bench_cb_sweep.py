"""CB serving knob sweep on the real chip (perf tuning companion to
bench.py's single-point measurement).

Sweeps the knobs that move the decode roofline — ``steps_per_dispatch``
(host↔device round-trips per token batch), ``max_slots`` (decode batch
width = weight-read amortization), ``page_size`` — and prints one JSON line
per point plus a best-point summary, so regressions/wins are attributable
to a specific knob before they're baked into bench.py defaults.

Run EXCLUSIVELY on the TPU chip (no other jax processes):

    python tools/bench_cb_sweep.py                       # default grid
    POLYRL_SWEEP_GRID='{"steps_per_dispatch": [4, 8, 16]}' \
        python tools/bench_cb_sweep.py
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_GRID = {
    "steps_per_dispatch": [4, 8, 16],
    "max_slots": [64, 128, 256],
    "page_size": [64],
    # run-ahead window for the fetcher-thread pipeline (cb_engine):
    # ~2*ceil(fetch RTT / dispatch compute) hides the result round trip
    "pipeline_depth": [8, 16, 32],
}


def run_point(cfg, params, batch, prompt_len, new_tokens, *, max_slots,
              page_size, steps_per_dispatch, pipeline_depth=None) -> dict:
    """One grid point: engine construction + warmup come from bench.py's
    shared helpers, so a best_point here reproduces in bench_cb (the only
    intentional difference: this measures the DIRECT path — knobs under
    sweep are device-side; bench_cb's serve number adds HTTP dispatch on
    top)."""
    import numpy as np

    from bench import engine_phase_trace, make_cb_engine, warmup_cb
    from polyrl_tpu.rollout.sampling import SamplingParams

    engine = make_cb_engine(cfg, params, prompt_len, new_tokens,
                            max_slots=max_slots, page_size=page_size,
                            steps_per_dispatch=steps_per_dispatch)
    if pipeline_depth is not None:
        engine.pipeline_depth = pipeline_depth
    try:
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                   for _ in range(batch)]
        sp = SamplingParams(temperature=1.0, max_new_tokens=new_tokens,
                            stop_token_ids=())
        warmup_cb(engine, cfg, rng, prompt_len)
        t0 = time.monotonic()
        outs = engine.generate(prompts, sp, timeout=1800.0)
        dt = time.monotonic() - t0
        total = sum(len(o["token_ids"]) for o in outs)
        return {"tok_s": round(total / dt, 1), "wall_s": round(dt, 2),
                "trace": {k: v for k, v in engine_phase_trace(engine).items()
                          if isinstance(v, float)}}
    finally:
        engine.stop()
        del engine
        gc.collect()


def main() -> None:
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import decoder

    preset = os.environ.get("POLYRL_BENCH_PRESET", "qwen3-1.7b")
    batch = int(os.environ.get("POLYRL_BENCH_BATCH", "256"))
    prompt_len = int(os.environ.get("POLYRL_BENCH_PROMPT", "128"))
    new_tokens = int(os.environ.get("POLYRL_BENCH_NEW", "128"))
    grid = dict(DEFAULT_GRID,
                **json.loads(os.environ.get("POLYRL_SWEEP_GRID", "{}")))

    cfg = decoder.get_config(preset, dtype=jnp.bfloat16)
    params = jax.jit(lambda: decoder.init_params(jax.random.PRNGKey(0),
                                                 cfg))()
    jax.block_until_ready(params)

    keys = sorted(grid)
    best = None
    for values in itertools.product(*(grid[k] for k in keys)):
        point = dict(zip(keys, values))
        try:
            res = run_point(cfg, params, batch, prompt_len, new_tokens,
                            **point)
        except Exception as exc:  # noqa: BLE001 — a bad point must not end
            # the sweep; OOM at large slots IS a finding
            res = {"error": str(exc)[:200]}
        line = {"point": point, **res}
        print(json.dumps(line), flush=True)
        if res.get("tok_s") and (best is None or res["tok_s"] > best[1]):
            best = (point, res["tok_s"])
        gc.collect()
    if best:
        print(json.dumps({"best_point": best[0], "tok_s": best[1]}),
              flush=True)


if __name__ == "__main__":
    sys.exit(main())
