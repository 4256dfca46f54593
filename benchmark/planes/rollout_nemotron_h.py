"""Cells of the rollout plane for Nemotron-H's family (``nemotron_h``:
layers of ONE sublayer each: Mamba-2 mixers with a float32 state in the
slot, a few attention layers without positions over pages, routed experts
of two matrices): ``planes/rollout_sambay.py``'s plane (which is
``rollout_hybrid.py``'s and ``rollout.py``'s), imported and not copied.
From it, as they are: the mix's further engine options handed on to
``create_server`` (``prefill_first``), the sigmoid router's bias evened on
sequences drawn from ``--seed`` by the reference's own router and pushed as
a trainer's push is, one client thread, the window opened once the client
is level with the engine, the line of what the loop spent set-up on, the
slot's rows read once the window is over (``held_states``:
``CBEngine.recurrent_state`` gives each Mamba-2 layer's state as the
published ``[H, P, N]``, in layer order), and the table of kernels that
must have taken their TPU path (the GQA paged decode attention and the
fused K/V write, ``ops/paged_attention.py``, at 32 query rows over 2 K/V
heads; no other dispatcher may have run: the state kernel and the experts'
kernels note nothing there, ``ssd_kernel_share`` and
``moe_gather_kernel_share`` say whether they ran). Of its own:

- the page arithmetic: the configuration's pool in bytes over what a token
  keeps in pages (``costs_nemotron_h.paged_bytes_per_token``: a K/V pair an
  attention layer, six of 52 layers); the states and tails are the
  engine's, a fixed size a slot, and no part of the pool;
- what ``correct`` compares (``compare``), each stated precision or
  mechanism by its own limit: the log-probability of each sampled token;
  the FIRST Mamba-2 layer's float32 state (the model's first layer: it
  stands on the embedding, no bf16 layer below it) that a scored request's
  slot holds after the window, against the reference's position-by-position
  recurrence over every token consumed, over the quarter of its heads that
  forget slowest; and the PROGRAM's routed experts
  (``blocks._moe_mlp`` without the shared expert, in calls of the cell's
  ``max_slots`` rows: the decode step's own form, rows taken by table) of
  the first, the middle and the last expert layer on the reference's hidden
  states, against the reference's;
- the two numbers of ``correct`` that ``harness.compared`` does not know
  (it prints the two log-probability rows alone) as ``compared`` rows on
  stderr, the way ``planes/rollout_sala.py`` prints its own, and in the
  result line under ``checks.reference``;
- the seconds each program took to build, by program, once the prompts
  are in and once the rows decode (``mark``; ``checks.setup_builds``):
  most of this cell's ``setup_s`` is the compiler's.

With no family key in the configuration (a ``--rehearse-cpu`` walk runs
``configs/rehearsal.json``'s tiny dense model under this plane) the page
arithmetic is GQA's and the comparison is ``planes/rollout.py``'s.
"""

from __future__ import annotations

import functools
import gc
import sys
import time

import numpy as np

from benchmark.lib import costs_nemotron_h, harness

sambay = harness.load_named("planes", "rollout_sambay")
hybrid = sambay.hybrid
base = hybrid.base

# the numbers of ``compare`` that ``correct`` holds to a limit
HELD = ("logprob_mean_abs_diff", "logprob_max_abs_diff", "state_rel_diff",
        "experts_rel_diff")


class NemotronHRolloutPlane(sambay.SambayRolloutPlane):
    builds = ()     # (kind, key, seconds) of each program built in set-up

    def num_pages(self) -> int:
        per_page = (costs_nemotron_h.paged_bytes_per_token(
            self.config["config"]) * self.mix["engine"]["page_size"])
        return int(self.config["serve"]["kv_pool_bytes"] // per_page) + 1

    def mark(self, phase: str) -> None:
        """With the prompts in, and again with every row decoding: each
        program the engine has built so far with the seconds to its first
        return (``EngineProfiler.builds``), so that a later PR sees which
        build to remove."""
        super().mark(phase)
        prof = getattr(self.eng, "profiler", None)
        if phase in ("prefilled", "warm") and prof is not None:
            before = len(self.builds)
            self.builds = [(b["kind"], b["key"], b["seconds"])
                           for b in prof.builds]
            if len(self.builds) > before:
                harness.say(f"programs built until {phase}: " + ", ".join(
                    f"{kind} {key} {s:.1f}s" for kind, key, s in self.builds))


def expert_layers(cfg) -> list[tuple[int, int]]:
    """(place in the plan, index among the expert layers) of the expert
    layers whose routed part ``correct`` compares: the first, the middle
    and the last."""
    from polyrl_tpu.models import cache_spec

    moe = [l for l, p in enumerate(cache_spec.layer_plan(cfg))
           if p.mlp == "moe"]
    picked = sorted({0, len(moe) // 2, len(moe) - 1})
    return [(moe[j], j) for j in picked]


def program_experts(cfg, rows: int) -> dict:
    """The PROGRAM's routed experts of ``expert_layers``, by the layer's
    index among the expert layers: a function of (the tree's ``layers``,
    hidden states [N, d] in the served type) that calls
    ``blocks._moe_mlp`` without the shared expert on ``rows`` positions at
    a time, as a decode step of ``rows`` slots calls it."""
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.models import blocks, hybrid as model

    def block(layers, h, l, j):
        lp = {k: v for k, v in model._layer_params(cfg, layers, l)[1].items()
              if not k.startswith("ws_")}
        return blocks._moe_mlp(cfg, h, lp, None, j)[0]

    def whole(step, layers, h):
        n = h.shape[0]
        h = jnp.pad(h, ((0, -n % rows), (0, 0)))
        out = [step(layers, h[at:at + rows])
               for at in range(0, h.shape[0], rows)]
        return jnp.concatenate(out)[:n]

    return {j: functools.partial(
        whole, jax.jit(functools.partial(block, l=l, j=j)))
        for l, j in expert_layers(cfg)}


def walk(reference, cfg, params, c: dict, samples, held, rows: int,
         control: str = "") -> list[dict]:
    """The reference over each scored request's prompt and consumed answer
    (``reference.trace``), and the program's routed experts (``experts``:
    {expert layer: [N, d] float32}) on the hidden states the reference
    found there, rounded to the served type."""
    import jax.numpy as jnp

    blocks = program_experts(cfg, rows)
    walked = []
    for (prompt, toks, lps), h in zip(samples, held):
        tr = reference.trace(params, c, list(prompt) + h["answer"],
                             len(prompt), min(len(toks), len(lps)), control)
        served = {j: jnp.asarray(tr["moe_in"][j], cfg.dtype) for j in blocks}
        tr["moe_in"] = {j: np.asarray(x, np.float32)
                        for j, x in served.items()}
        tr["experts"] = {j: np.asarray(f(params["layers"], served[j]),
                                       np.float32)
                         for j, f in blocks.items()}
        walked.append(tr)
    return walked


def slow_state_rel(state, walked: dict) -> float:
    """|state - reference| over |reference| of the first Mamba-2 layer's
    state ``[H, P, N]``, over the heads the reference's walk ``walked``
    found slowest."""
    heads = walked["slow"][0]
    return float(hybrid.rel(np.asarray(state)[heads],
                            walked["states"][0][heads]))


def compare(reference, params, c: dict, limits: dict, samples, held,
            walked) -> dict:
    """``correct``'s numbers, each held to its limit of ``limits``:

    - ``logprob_mean_abs_diff``, ``logprob_max_abs_diff``: the system's
      log-probability of each sampled token against the reference's, nats;
    - ``state_rel_diff``: the float32 state ``[H, P, N]`` of the FIRST
      Mamba-2 layer that a scored request's slot held after the window,
      against the reference's recurrence over the same tokens, |difference|
      over |reference| over the quarter of the layer's heads that forget
      slowest (the reference's ``slow``), the mean over the scored
      requests. The first layer, whose inputs no bf16 layer below has
      moved; its slowest heads, because a state's rounding adds up over
      what a head remembers, 1 / (dt |A|) tokens, while the error of its
      bf16 inputs does not: over the whole state a bfloat16 state reads
      1.09 times a sound float32 one (0.00416 against 0.00383), over the
      slow quarter seven times (0.0222 against 0.0026-0.0032; my chip runs,
      PR 58) (``slow_state_rel``; ``state_rel_diffs``: every request, every
      Mamba-2 layer, the whole state);
    - ``experts_rel_diff``: the program's routed experts against the
      reference's on the same hidden states, |difference| over |reference|
      a position, the median over the scored positions of the compared
      expert layers that have a choice held here (a median: a choice that
      flips on a tie is a whole expert's difference and no matter of
      precision).

    ``held`` [requests]: ``{"answer", "states"}`` as ``held_states`` gives
    them; ``walked``: ``walk``'s result."""
    worst, total, count = 0.0, 0.0, 0
    states, slow, rows, tokens = [], [], [], []
    for (prompt, toks, lps), h, tr in zip(samples, held, walked):
        n = min(len(toks), len(lps))
        diff = np.abs(tr["logprobs"] - np.asarray(lps[:n], np.float32))
        worst, total, count = (max(worst, float(diff.max())),
                               total + float(diff.sum()), count + n)
        states.append([float(hybrid.rel(mine, ref)) for mine, ref
                       in zip(h["states"], tr["states"])])
        slow.append(slow_state_rel(h["states"][0], tr))
        tokens.append(len(prompt) + len(h["answer"]))
        for j, mine in tr["experts"].items():
            ref = reference.routed_block(params, c, j, tr["moe_in"][j])
            some = np.linalg.norm(ref, axis=-1) > 0
            rows.append(hybrid.rel(mine[some], ref[some], axis=-1))
    rows = np.concatenate(rows)
    out = {"sequences": len(samples), "positions": count,
           "logprob_mean_abs_diff": total / max(count, 1),
           "logprob_max_abs_diff": worst,
           "state_rel_diff": float(np.mean(slow)),
           "state_rel_diffs": states, "state_tokens": tokens,
           "experts_rel_diff": float(np.median(rows)),
           "experts_positions": int(rows.size)}
    out["failed_by"] = [k for k in HELD if not out[k] <= limits[k + "_max"]]
    out["ok"] = bool(count > 0 and rows.size > 0 and not out["failed_by"])
    return out


def window_counters(observed: dict) -> dict:
    """What the engine's own counters say of the window, in every run (the
    per-layer metrics that read the same keys print in traced runs only):
    the share of the window with device work outstanding, the Mamba-2 rows
    and the experts hit a step, the share of steps through the state
    kernel and through the experts' table form, the rows that yielded."""
    from benchmark.lib import counters

    busy = counters.delta_ratio(observed, "device_busy_s", "device_busy_at_s")
    info = observed.get("server_info") or [{}]
    per_step = {k: counters.delta_ratio(observed, k, "decode_steps_done")
                for k in ("ssd_state_rows", "moe_experts_hit",
                          "ssd_kernel_steps", "moe_gather_kernel_steps")}
    return {"engine_device_busy": None if busy is None else 100.0 * busy,
            **per_step,
            "slot_yields": info[-1].get("slot_yields", 0)
            - info[0].get("slot_yields", 0)}


def run(cell, config, mix, device, seed, seconds, trace, counter, t_proc0):
    work = harness.work_dir(cell["name"])
    plane = NemotronHRolloutPlane(cell, config, mix, device, seed, work,
                                  t_proc0)
    pattern = harness.load_named("patterns", mix["pattern"])
    try:
        plane.start()
        out = pattern.run(plane, seconds, trace, counter)
        held = plane.held_states(out)
    finally:
        plane.stop()
    eng = plane.eng
    out["checks"]["engine_recoveries"] = int(eng.recoveries)
    out["checks"]["setup_builds"] = list(plane.builds)
    k_ok, taken = sambay.kernels_ok(device)
    out["checks"]["kernels"] = {k: list(v) for k, v in taken.items()}
    out["checks"]["kernels_ok"] = k_ok
    out["checks"]["window_counters"] = said = window_counters(out["observed"])
    harness.say("the window by the engine's counters: " + ", ".join(
        f"{k} {v:.4g}" for k, v in said.items() if v is not None))
    out["device"] = device.as_dict()
    # the reference needs room: drop the pages, the states and the tails
    # (all live in the engine's pools), keep the weights
    samples = out.pop("samples")
    params, cfg = eng.params, eng.cfg
    plane.srv = plane.eng = None
    eng._pools = None
    eng._dev_state = None
    del eng
    gc.collect()
    reference = harness.load_named("references", config["reference"])
    if held is None:
        out["checks"]["reference"] = base.check_logprobs(
            reference, params, config, samples)
        return out
    t0 = time.monotonic()
    walked = walk(reference, cfg, params, config["config"], samples, held,
                  int(mix["engine"]["max_slots"]))
    out["checks"]["reference"] = ref = compare(
        reference, params, config["config"], config["correct"], samples,
        held, walked)
    harness.say(f"compared in {time.monotonic() - t0:.1f}s: " + ", ".join(
        f"{k} {ref[k]:.4g} (limit {config['correct'][k + '_max']:g})"
        for k in HELD))
    for k in HELD[2:]:      # ``harness.compared`` prints the first two
        print(f"compared {k}: {ref[k]:g} (limit "
              f"{config['correct'][k + '_max']:g})", file=sys.stderr,
              flush=True)
    return out
