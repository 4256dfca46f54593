"""Cells of the rollout plane for a model of rope'd GQA layers of two kinds
(``laguna``: full layers whose K/V lie in pages, window layers whose last
512 keys lie in a ring of the slot's, a head count and a rope a kind)
with routed experts of which a share is held here beside a shared one:
``planes/rollout_sambay.py``'s plane (which is ``rollout_hybrid.py``'s and
``rollout.py``'s), imported and not copied. From it, as they are: the
mix's further engine options handed on to ``create_server``
(``prefill_first``), one client thread, the window opened once the client
is level with the engine, the line of what the loop spent set-up on, the
slot's rows read once the window is over (``held_states``:
``CBEngine.recurrent_state`` gives each window layer's ring, in layer
order), and a ring against the reference's rows position by position
(``ring_in_order``, ``ring_rel``). Of its own:

- the page arithmetic: the configuration's pool in bytes over what a token
  keeps in pages (``costs_mixed.paged_bytes_per_token``: the FULL layers'
  K and V); the rings are the engine's, a fixed size a slot, and no part
  of the pool;
- the table of kernels that must have taken their TPU path is
  ``rollout_sambay``'s: the GQA paged decode attention and the fused K/V
  write (``ops/paged_attention.py``, here at 48 query heads over 8 on pages
  and 64 over 8 on rings, in one step), and no other dispatcher may have
  run (the experts' grouped matmul notes no
  key: PERF.md section 7 (h)); nothing is evened (a softmax router has no
  balancing bias);
- what ``correct`` compares (``compare``), each stated precision or
  mechanism by its own limit: the log-probability of each sampled token;
  the program's router and held experts on the reference's hidden states
  against the reference's (``rollout_hybrid.program_experts``: the block
  the step and the prefill call); and the FIRST window layer's ring that a
  scored request's slot holds once the window is over against the
  reference's rotated keys and values of that request's last 512 tokens,
  placed by ``t % 512`` (with near-uniform attention over random weights a
  window that is a key short, or a ring row under the wrong position,
  hides inside any log-probability limit; it cannot hide here).

With no family key in the configuration (a ``--rehearse-cpu`` walk runs
``configs/rehearsal.json``'s tiny dense model under this plane) the page
arithmetic is GQA's and the comparison is ``planes/rollout.py``'s.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import costs_mixed, harness

sambay = harness.load_named("planes", "rollout_sambay")
hybrid = sambay.hybrid
base = hybrid.base

# the numbers of ``compare`` that ``correct`` holds to a limit
HELD = ("logprob_mean_abs_diff", "logprob_max_abs_diff", "experts_rel_diff",
        "window_rel_diff")


class MixedRolloutPlane(sambay.SambayRolloutPlane):
    def num_pages(self) -> int:
        per_page = (costs_mixed.paged_bytes_per_token(self.config["config"])
                    * self.mix["engine"]["page_size"])
        return int(self.config["serve"]["kv_pool_bytes"] // per_page) + 1


def walk(reference, cfg, params, c: dict, samples, held,
         control: str = "") -> list[dict]:
    """The reference over each scored request's prompt and consumed answer
    (``reference.trace``), and the program's routed experts (``experts``,
    [N, d] float32 a sparse layer) on the hidden states it found there,
    rounded to the served type (``moe_in``)."""
    import jax.numpy as jnp

    blocks = hybrid.program_experts(cfg) if not control else []
    walked = []
    for (prompt, toks, lps), h in zip(samples, held):
        n = min(len(toks), len(lps))
        tr = reference.trace(params, c, list(prompt) + h["answer"],
                             len(prompt), n, control)
        served = [jnp.asarray(x, cfg.dtype) for x in tr["moe_in"]]
        tr["moe_in"] = [np.asarray(x, np.float32) for x in served]
        tr["experts"] = [np.asarray(f(params["layers"], x), np.float32)
                         for f, x in zip(blocks, served)]
        walked.append(tr)
    return walked


def experts_rel(reference, params, c: dict, walked,
                control: str = "") -> np.ndarray:
    """|mine - reference| over |reference| a position of the held experts'
    part of every sparse layer, over the positions that have a choice held
    here; mine: the program's (``walk``'s ``experts``), or with
    ``control`` the reference's own under that control."""
    rows = []
    for tr in walked:
        for j, x in enumerate(tr["moe_in"]):
            ref = reference.routed_block(params, c, j, x)
            got = reference.routed_block(params, c, j, x, control) \
                if control else tr["experts"][j]
            some = np.linalg.norm(ref, axis=-1) > 0
            rows.append(hybrid.rel(got[some], ref[some], axis=-1))
    return np.concatenate(rows)


def window_counters(observed: dict, c: dict) -> dict:
    """What the engine's own counters say of the window, in every run (the
    per-layer metrics that read the same keys print in traced runs only):
    the share of the window with device work outstanding, the experts a
    sparse layer's step hit, the share of the choices that fell on experts
    held here."""
    from benchmark.lib import counters

    busy = counters.delta_ratio(observed, "device_busy_s", "device_busy_at_s")
    hit = counters.delta_ratio(observed, "moe_experts_hit",
                               "decode_steps_done")
    held = counters.delta_ratio(observed, "moe_routed", "moe_choices")
    sparse = list(c.get("mlp_layer_types") or ()).count("sparse")
    return {"engine_device_busy": None if busy is None else 100.0 * busy,
            "experts_hit_a_layer": None if hit is None or not sparse
            else hit / sparse,
            "experts_held_share": None if held is None else 100.0 * held}


def compare(reference, params, c: dict, limits: dict, samples, held,
            walked) -> dict:
    """``correct``'s numbers, each held to its limit of ``limits``:

    - ``logprob_mean_abs_diff``, ``logprob_max_abs_diff``: the system's
      log-probability of each sampled token against the reference's, nats;
    - ``experts_rel_diff``: the program's router and held experts against
      the reference's on the same hidden states, |difference| over
      |reference| a position, the median over the scored positions of
      every sparse layer that have a choice held here (a median: a choice
      that flips on a near tie is a whole expert's difference and no
      matter of precision);
    - ``window_rel_diff``: the FIRST window layer's ring, the rows that
      hold a token placed by ``t % window``, against the reference's
      rotated keys and values ``[kk | v]`` of the last ``sliding_window``
      tokens, position by position over the positions either holds, a row
      that the other lacks counted whole (``rollout_sambay.ring_rel``),
      the mean over the scored requests (``window_rel_diffs``: every
      request, every window layer).

    ``held`` [requests]: ``{"answer", "states"}`` as ``held_states`` gives
    them; ``walked``: ``walk``'s result."""
    worst, total, count = 0.0, 0.0, 0
    rings, tokens = [], []
    for (prompt, toks, lps), h, tr in zip(samples, held, walked):
        n = min(len(toks), len(lps))
        diff = np.abs(tr["logprobs"] - np.asarray(lps[:n], np.float32))
        worst, total, count = (max(worst, float(diff.max())),
                               total + float(diff.sum()), count + n)
        consumed = len(prompt) + len(h["answer"])
        rings.append([sambay.ring_rel(sambay.ring_in_order(s, consumed), ref)
                      for s, ref in zip(h["states"], tr["rings"])])
        tokens.append(consumed)
    rows = experts_rel(reference, params, c, walked)
    out = {"sequences": len(samples), "positions": count,
           "logprob_mean_abs_diff": total / max(count, 1),
           "logprob_max_abs_diff": worst,
           "experts_rel_diff": float(np.median(rows)) if rows.size else 0.0,
           "experts_positions": int(rows.size),
           "window_rel_diff": float(np.mean([r[0] for r in rings])),
           "window_rel_diffs": rings,
           "ring_tokens": tokens}
    out["failed_by"] = [k for k in HELD if not out[k] <= limits[k + "_max"]]
    out["ok"] = bool(count > 0 and rows.size > 0 and not out["failed_by"])
    return out


def run(cell, config, mix, device, seed, seconds, trace, counter, t_proc0):
    work = harness.work_dir(cell["name"])
    plane = MixedRolloutPlane(cell, config, mix, device, seed, work, t_proc0)
    pattern = harness.load_named("patterns", mix["pattern"])
    try:
        plane.start()
        out = pattern.run(plane, seconds, trace, counter)
        held = plane.held_states(out)
    finally:
        plane.stop()
    eng = plane.eng
    out["checks"]["engine_recoveries"] = int(eng.recoveries)
    k_ok, taken = sambay.kernels_ok(device)
    out["checks"]["kernels"] = {k: list(v) for k, v in taken.items()}
    out["checks"]["kernels_ok"] = k_ok
    out["checks"]["window_counters"] = said = window_counters(
        out["observed"], config["config"])
    harness.say("the window by the engine's counters: " + ", ".join(
        f"{k} {v:.4g}" for k, v in said.items() if v is not None))
    out["device"] = device.as_dict()
    # the reference needs room: drop the pages and the rings (both live in
    # the engine's pools), keep the weights
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
    walked = walk(reference, cfg, params, config["config"], samples, held)
    out["checks"]["reference"] = ref = compare(
        reference, params, config["config"], config["correct"], samples,
        held, walked)
    harness.say(f"compared in {time.monotonic() - t0:.1f}s: " + ", ".join(
        f"{k} {ref[k]:.4g} (limit {config['correct'][k + '_max']:g})"
        for k in HELD))
    return out
