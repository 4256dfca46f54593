"""Cells of the rollout plane for a model that is latent attention in
EVERY layer and keeps nothing outside pages (DeepSeek-V3's decoder:
dots.vlm1): ``planes/rollout_hybrid.py``'s plane (which is
``planes/rollout.py``'s), imported and not copied. From it, as they are:
the mix's further engine options handed on to ``create_server``
(``prefill_first``), the router's bias evened by the reference once the
weights are drawn, one client thread, and the window opened once the
client is level with the engine. Of its own:

- the page arithmetic: the configuration's pool in bytes over what a token
  keeps in pages (``costs_latent.paged_bytes_per_token``: one latent row
  in each layer);
- the table of kernels that must have taken their TPU path: the absorbed
  latent attention alone (this model writes no K/V pair and runs no GQA
  attention; the experts' grouped matmul notes no key);
- what ``correct`` compares (``compare``): the log-probability of each
  sampled token, and the program's routed experts on the reference's
  hidden states against the reference's. There is no recurrent state to
  hold: the latent pool's precision shows in the log-probabilities (every
  layer attends over it), the experts' does not (a sixteenth of a token's
  choices land here), so they have a number of their own.

With no latent key in the configuration (a ``--rehearse-cpu`` walk runs
``configs/rehearsal.json``'s tiny dense model under this plane) the page
arithmetic is GQA's and the comparison is ``planes/rollout.py``'s.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import costs_latent, harness

hybrid = harness.load_named("planes", "rollout_hybrid")
base = hybrid.base

KERNELS_ON_TPU = hybrid.KERNELS_ON_TPU      # latent_attention: pallas


class LatentRolloutPlane(hybrid.HybridRolloutPlane):
    def num_pages(self) -> int:
        per_page = (costs_latent.paged_bytes_per_token(self.config["config"])
                    * self.mix["engine"]["page_size"])
        return int(self.config["serve"]["kv_pool_bytes"] // per_page) + 1

    def stream(self, client, reqs, prompts) -> None:
        lens = sorted(r.prompt_len for r in reqs)
        harness.say(f"{len(lens)} prompts of {lens[0]}-{lens[-1]} tokens, "
                    f"median {lens[len(lens) // 2]}, {sum(lens)} in all")
        super().stream(client, reqs, prompts)


def _trace(reference, params, c: dict, sample) -> dict:
    """The reference over one scored request: its prompt and as much of
    its answer as came with log-probabilities."""
    prompt, toks, lps = sample
    n = min(len(toks), len(lps))
    return reference.trace(params, c, list(prompt) + list(toks[:n]),
                           len(prompt), n)


def walk(reference, cfg, params, c: dict, samples) -> list[dict]:
    """The reference over each scored request's prompt and scored answer
    (``reference.trace``), and the program's routed experts (``experts``,
    [N, d] float32 a sparse layer) on the hidden states it found there,
    rounded to the served type (``moe_in``)."""
    import jax.numpy as jnp

    blocks = hybrid.program_experts(cfg)
    walked = []
    for sample in samples:
        tr = _trace(reference, params, c, sample)
        served = [jnp.asarray(x, cfg.dtype) for x in tr["moe_in"]]
        tr["moe_in"] = [np.asarray(x, np.float32) for x in served]
        tr["experts"] = [np.asarray(f(params["layers"], x), np.float32)
                         for f, x in zip(blocks, served)]
        walked.append(tr)
    return walked


def compare(reference, params, c: dict, limits: dict, samples, walked,
            again: bool = False) -> dict:
    """``correct``'s numbers, each held to its limit of ``limits``:

    - ``logprob_mean_abs_diff``, ``logprob_max_abs_diff``: the system's
      log-probability of each sampled token against the reference's, nats;
    - ``experts_rel_diff``: the program's routed experts against the
      reference's on the same hidden states, |difference| over
      |reference| a position, the median over the scored positions of
      every sparse layer that have a choice held here (a median: a choice
      that flips on a tie is a whole expert's difference and no matter of
      precision).

    ``walked`` is ``walk``'s result with the program's weights; with
    ``again`` (``params`` is another tree: a control that serves rounded
    weights) the reference walks once more with ``params``."""
    if again:
        walked = [{**_trace(reference, params, c, sample),
                   "moe_in": old["moe_in"], "experts": old["experts"]}
                  for sample, old in zip(samples, walked)]
    worst, total, count = 0.0, 0.0, 0
    rows = []
    for (prompt, toks, lps), tr in zip(samples, walked):
        n = min(len(toks), len(lps))
        diff = np.abs(tr["logprobs"] - np.asarray(lps[:n], np.float32))
        worst, total, count = (max(worst, float(diff.max())),
                               total + float(diff.sum()), count + n)
        for j, (x, mine) in enumerate(zip(tr["moe_in"], tr["experts"])):
            ref = reference.routed_block(params, c, j, x)
            some = np.linalg.norm(ref, axis=-1) > 0
            rows.append(hybrid.rel(mine[some], ref[some], axis=-1))
    rows = np.concatenate(rows) if rows else np.zeros((0,))
    out = {"sequences": len(samples), "positions": count,
           "logprob_mean_abs_diff": total / max(count, 1),
           "logprob_max_abs_diff": worst,
           "experts_rel_diff": float(np.median(rows)) if rows.size else 0.0,
           "experts_positions": int(rows.size)}
    out["ok"] = bool(count > 0 and rows.size > 0 and all(
        out[k] <= limits[k + "_max"] for k in
        ("logprob_mean_abs_diff", "logprob_max_abs_diff",
         "experts_rel_diff")))
    return out


def weights_for_reference(plane, eng):
    """The weights the reference scores with: the engine's own. (The
    control of ``correct`` that serves rounded experts redraws the
    unrounded ones from the seed here: ``tests/control_latent_on_chip.py``.)"""
    return eng.params


def run(cell, config, mix, device, seed, seconds, trace, counter, t_proc0):
    work = harness.work_dir(cell["name"])
    plane = LatentRolloutPlane(cell, config, mix, device, seed, work, t_proc0)
    pattern = harness.load_named("patterns", mix["pattern"])
    try:
        plane.start()
        out = pattern.run(plane, seconds, trace, counter)
    finally:
        plane.stop()
    eng = plane.eng
    out["checks"]["engine_recoveries"] = int(eng.recoveries)
    k_ok, taken = hybrid.kernels_ok(device)
    out["checks"]["kernels"] = {k: list(v) for k, v in taken.items()}
    out["checks"]["kernels_ok"] = k_ok
    out["device"] = device.as_dict()
    # the reference needs room: drop the pages, keep the weights
    samples = out.pop("samples")
    plane.srv = plane.eng = None
    eng._pools = None
    eng._dev_state = None
    gc.collect()
    reference = harness.load_named("references", config["reference"])
    if not costs_latent.is_latent(config["config"]):
        out["checks"]["reference"] = base.check_logprobs(
            reference, eng.params, config, samples)
        return out
    t0 = time.monotonic()
    walked = walk(reference, eng.cfg, eng.params, config["config"], samples)
    params = weights_for_reference(plane, eng)
    again = params is not eng.params
    del eng
    gc.collect()
    out["checks"]["reference"] = ref = compare(
        reference, params, config["config"], config["correct"], samples,
        walked, again)
    harness.say(f"compared in {time.monotonic() - t0:.1f}s: experts_rel_diff "
                f"{ref['experts_rel_diff']:.4g} (limit "
                f"{config['correct']['experts_rel_diff_max']:g}) over "
                f"{ref['experts_positions']} positions")
    return out
