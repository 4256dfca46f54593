"""Cells of the rollout plane for a model whose every layer keeps a K/V
pair in pages AND convolution tails in the engine's slot (ZAYA1's
compressed convolutional attention) and routes top-1 through a router MLP
with a latent carried from layer to layer: ``planes/rollout_hybrid.py``'s
plane (which is ``planes/rollout.py``'s), imported and not copied. From
it, as they are: the mix's further engine options handed on to
``create_server`` (``prefill_first``), one client thread, the window
opened once the client is level with the engine, and the slot's rows read
once the window is over (``held_states``: ``CBEngine.recurrent_state``
gives a CCA layer's three tails side by side). Of its own:

- the page arithmetic: the configuration's pool in bytes over what a token
  keeps in pages (``costs_cca.paged_bytes_per_token``: a K/V pair of 2
  heads of 128 in each layer); the tails are the engine's, a row a slot,
  and no part of the pool;
- the table of kernels that must have taken their TPU path: the GQA paged
  decode attention and the fused K/V write (``ops/paged_attention.py``, at
  8 query heads over 2), and no other dispatcher may have run (the
  experts' grouped matmul notes no key: PERF.md section 7 (h));
- the weights: the router's balancing bias is evened once the weights are
  drawn, by the reference's own router MLP with its carry on the prompts
  the run will offer (``cca_moe.even_router_bias``, ``EVEN_LAST``), and
  pushed as a trainer's push is;
- what ``correct`` compares (``compare``): the log-probability of each
  sampled token; the program's router and routed experts on the
  reference's hidden states and carried latents, against the reference's,
  with the share of positions whose top-1 choice differs; and the tails a
  scored request's slot holds after the window against the reference's at
  that token: the one thing a chunk boundary or a re-entry can get wrong
  without the log-probabilities of 512 tokens moving much.

With no CCA key in the configuration (a ``--rehearse-cpu`` walk runs
``configs/rehearsal.json``'s tiny dense model under this plane) the page
arithmetic is GQA's, the comparison is ``planes/rollout.py``'s and nothing
is evened.
"""

from __future__ import annotations

import functools
import gc
import time

import numpy as np

from benchmark.lib import costs_cca, counters, harness

hybrid = harness.load_named("planes", "rollout_hybrid")
base = hybrid.base

KERNELS_ON_TPU = {"paged_attention": ("lib",), "kv_write": ("pallas",)}

# what the router's bias is evened on: the run's OWN prompts
# (``traffic.steady_plan``: a function of the mix and ``--seed``), the last
# ``EVEN_LAST`` positions of each, their residual streams kept in
# ``EVEN_KEEP`` between sublayers (292k tokens: in float32 they would not
# fit beside the pool). The window's own sequences, because with weights
# that were never trained a sequence's rows past a thousand keys route
# alike: a decode step's load is then that of its 128 sequences, 128
# samples of 16 shares, and a bias evened on OTHER sequences (32 or 64 of
# the same lengths) leaves an expert or two of a layer a third of its
# share on these, which a step then misses one time in ten; how many
# experts a step reads (2% of its time each) follows the seed (PERF.md
# section 6). A trained router routes by content and needs none of this
EVEN_LAST = 128
EVEN_KEEP = "bfloat16"

# the numbers of ``compare`` that ``correct`` holds to a limit
HELD = ("logprob_mean_abs_diff", "logprob_max_abs_diff", "experts_rel_diff",
        "tails_rel_diff")


class CcaRolloutPlane(hybrid.HybridRolloutPlane):
    def num_pages(self) -> int:
        per_page = (costs_cca.paged_bytes_per_token(self.config["config"])
                    * self.mix["engine"]["page_size"])
        return int(self.config["serve"]["kv_pool_bytes"] // per_page) + 1

    def start(self) -> None:
        """``HybridRolloutPlane.start`` (the mix's further engine options;
        it evens a sigmoid router's bias, which this model has not); then,
        for a model with a router MLP, its balancing bias evened on the
        prompts this run will offer (drawn from ``--seed``: ``EVEN_LAST``)
        and installed as a trainer's push is. The weights stay a function
        of the seed alone, and the program's router has no part in making
        them."""
        super().start()
        eng = self.eng
        if not getattr(eng.cfg, "router_hidden_size", 0):
            return          # a rehearsal's dense model
        import jax

        from benchmark.lib import traffic

        plan = traffic.steady_plan(
            self.mix, self.seed, int(self.config["config"]["vocab_size"]))
        reference = harness.load_named("references", self.config["reference"])
        tree = eng.params
        bias = reference.even_router_bias(
            tree, self.config["config"], [p["prompt"] for p in plan],
            last=EVEN_LAST, keep=EVEN_KEEP)
        moe = dict(tree["layers"]["moe"], router_bias=bias)
        eng.update_weights(
            {**tree, "layers": {**tree["layers"], "moe": moe}},
            version=eng.weight_version)
        jax.block_until_ready(eng.params)
        self.mark("router_evened")

    def stream(self, client, reqs, prompts) -> None:
        lens = sorted(r.prompt_len for r in reqs)
        harness.say(f"{len(lens)} prompts of {lens[0]}-{lens[-1]} tokens, "
                    f"median {lens[len(lens) // 2]}, {sum(lens)} in all")
        super().stream(client, reqs, prompts)


def window_counters(observed: dict, c: dict) -> dict:
    """What the engine's own counters say of the window, in every run (the
    per-layer metrics that read the same keys print in traced runs only,
    and under the profiler): the share of the window with device work
    outstanding (``engine_device_busy``'s ratio), the experts a layer's
    step hit, and the busiest expert's rows over the mean expert's
    (``expert_load_skew``'s ratio). None where a counter is missing."""
    busy = counters.delta_ratio(observed, "device_busy_s", "device_busy_at_s")
    hit = counters.delta_ratio(observed, "moe_experts_hit",
                               "decode_steps_done")
    skew = counters.delta_ratio(observed, "moe_load_max", "moe_routed")
    return {
        "engine_device_busy": None if busy is None else 100.0 * busy,
        "experts_hit_a_layer": None if hit is None
        else hit / c["num_hidden_layers"],
        "expert_load_skew": None if skew is None
        else skew * c.get("num_experts", 0)}


def tails_stat(tails) -> float:
    """``tails_rel_diff`` of ``tails`` [requests][layers]: the worst
    layer's best request."""
    return float(np.max(np.min(np.asarray(tails, np.float64), axis=0)))


def program_experts(cfg) -> list:
    """The PROGRAM's router and routed experts of each layer, as jitted
    functions of (the tree's ``layers``, hidden states [N, d] in the type
    the program serves in, carried latents [N, R] float32) -> (the block's
    output [N, d], the expert each position chose [N]):
    ``blocks._latent_route`` and ``blocks._moe_mlp`` as the decode step and
    the prefill call them."""
    import jax

    from polyrl_tpu.models import blocks, hybrid as model

    def block(layers, h, carry, l):
        lp = model._layer_params(cfg, layers, l)[1]
        top_p, top_i, _latent = blocks._latent_route(cfg, h, lp, carry)
        out, _load = blocks._moe_mlp(cfg, h, lp, None, l,
                                     route=(top_p, top_i))
        return out, top_i[:, 0]

    return [jax.jit(functools.partial(block, l=l))
            for l in range(cfg.num_layers)]


def walk(reference, cfg, params, c: dict, samples, held) -> list[dict]:
    """The reference over each scored request's prompt and consumed answer
    (``reference.trace``), and the program's routed block (``experts``
    [N, d] float32 and ``chosen`` [N] a layer) on the hidden states and
    carried latents the reference found there, the hidden states rounded
    to the served type (``moe_in``)."""
    import jax.numpy as jnp

    blocks = program_experts(cfg)
    walked = []
    for (prompt, toks, lps), h in zip(samples, held):
        n = min(len(toks), len(lps))
        tr = reference.trace(params, c, list(prompt) + h["answer"],
                             len(prompt), n)
        served = [jnp.asarray(x, cfg.dtype) for x in tr["moe_in"]]
        tr["moe_in"] = [np.asarray(x, np.float32) for x in served]
        got = [f(params["layers"], x, jnp.asarray(s))
               for f, x, s in zip(blocks, served, tr["carry_in"])]
        tr["experts"] = [np.asarray(o, np.float32) for o, _i in got]
        tr["chosen"] = [np.asarray(i) for _o, i in got]
        walked.append(tr)
    return walked


def compare(reference, params, c: dict, limits: dict, samples, held,
            walked, again: bool = False) -> dict:
    """``correct``'s numbers, each held to its limit of ``limits``:

    - ``logprob_mean_abs_diff``, ``logprob_max_abs_diff``: the system's
      log-probability of each sampled token against the reference's, nats;
    - ``experts_rel_diff``: the program's router and routed experts against
      the reference's on the same hidden states and carried latents,
      |difference| over |reference| a position, the median over the scored
      positions of every layer (a median: a top-1 choice that flips on a
      near tie is a whole expert's difference and no matter of precision;
      ``choice_differs_share`` is the share of positions where it did);
    - ``tails_rel_diff``: the tails a scored request's slot held after the
      window (a layer's ``c``, ``u`` and ``vb`` of its last token) against
      the reference's at that token, |difference| over |reference|: in
      each layer the BEST of the scored requests, and of those the worst
      layer (``tails_stat``; ``tails_rel_diffs``: every request, every
      layer). The best request, because the last token's top-1 choice
      flips on a near tie in about one request of six somewhere in twelve
      layers, and from that layer on that request's tails are another
      expert's output away from the reference's (0.03-0.15 against
      0.005-0.0095), which is no matter of precision; every layer,
      because a tail row that is stale, lost at a chunk boundary or
      written to another slot's place is so for every request of that
      layer.

    ``walked`` is ``walk``'s result with the program's weights; with
    ``again`` (``params`` is another tree: a control that serves rounded
    weights) the reference walks once more with ``params``."""
    if again:
        walked = [{**reference.trace(params, c, list(s[0]) + h["answer"],
                                     len(s[0]), min(len(s[1]), len(s[2]))),
                   "moe_in": old["moe_in"], "experts": old["experts"],
                   "chosen": old["chosen"]}
                  for s, h, old in zip(samples, held, walked)]
    rel = hybrid.rel
    worst, total, count = 0.0, 0.0, 0
    tails, rows, differs = [], [], []
    for (prompt, toks, lps), h, tr in zip(samples, held, walked):
        n = min(len(toks), len(lps))
        diff = np.abs(tr["logprobs"] - np.asarray(lps[:n], np.float32))
        worst, total, count = (max(worst, float(diff.max())),
                               total + float(diff.sum()), count + n)
        tails.append([float(rel(mine, ref)) for mine, ref
                      in zip(h["states"], tr["states"])])
        for l, (x, s, mine, chose) in enumerate(zip(
                tr["moe_in"], tr["carry_in"], tr["experts"], tr["chosen"])):
            ref, ref_chose = reference.routed_block(params, c, l, x, s)
            rows.append(rel(mine, ref, axis=-1))
            differs.append(chose != ref_chose)
    rows, differs = np.concatenate(rows), np.concatenate(differs)
    out = {"sequences": len(samples), "positions": count,
           "logprob_mean_abs_diff": total / max(count, 1),
           "logprob_max_abs_diff": worst,
           "tails_rel_diff": tails_stat(tails),
           "tails_rel_diffs": tails,
           "tails_tokens": [len(s[0]) + len(h["answer"])
                            for s, h in zip(samples, held)],
           "experts_rel_diff": float(np.median(rows)),
           "experts_positions": int(rows.size),
           "choice_differs_share": float(np.mean(differs))}
    out["failed_by"] = [k for k in HELD if not out[k] <= limits[k + "_max"]]
    out["ok"] = bool(count > 0 and rows.size > 0 and not out["failed_by"])
    return out


def kernels_ok(device) -> tuple[bool, dict]:
    from polyrl_tpu.ops import dispatch

    taken = dispatch.taken()
    if device.rehearse:
        return True, taken
    return (all(taken[k] == KERNELS_ON_TPU.get(k) for k in taken)
            and all(k in taken for k in KERNELS_ON_TPU)), taken


def weights_for_reference(plane, eng):
    """The weights the reference scores with: the engine's own. (The
    control of ``correct`` that serves rounded experts redraws the
    unrounded ones from the seed here: ``tests/control_cca_on_chip.py``.)"""
    return eng.params


def run(cell, config, mix, device, seed, seconds, trace, counter, t_proc0):
    work = harness.work_dir(cell["name"])
    plane = CcaRolloutPlane(cell, config, mix, device, seed, work, t_proc0)
    pattern = harness.load_named("patterns", mix["pattern"])
    try:
        plane.start()
        out = pattern.run(plane, seconds, trace, counter)
        held = plane.held_states(out)
    finally:
        plane.stop()
    eng = plane.eng
    out["checks"]["engine_recoveries"] = int(eng.recoveries)
    k_ok, taken = kernels_ok(device)
    out["checks"]["kernels"] = {k: list(v) for k, v in taken.items()}
    out["checks"]["kernels_ok"] = k_ok
    out["checks"]["window_counters"] = said = window_counters(
        out["observed"], config["config"])
    harness.say("the window by the engine's counters: " + ", ".join(
        f"{k} {v:.4g}" for k, v in said.items() if v is not None))
    out["device"] = device.as_dict()
    # the reference needs room: drop the pages and the tails (both live in
    # the engine's pools), keep the weights
    samples = out.pop("samples")
    plane.srv = plane.eng = None
    eng._pools = None
    eng._dev_state = None
    gc.collect()
    reference = harness.load_named("references", config["reference"])
    if held is None:
        out["checks"]["reference"] = base.check_logprobs(
            reference, eng.params, config, samples)
        return out
    t0 = time.monotonic()
    walked = walk(reference, eng.cfg, eng.params, config["config"], samples,
                  held)
    params = weights_for_reference(plane, eng)
    again = params is not eng.params
    del eng
    gc.collect()
    out["checks"]["reference"] = ref = compare(
        reference, params, config["config"], config["correct"], samples,
        held, walked, again)
    harness.say(f"compared in {time.monotonic() - t0:.1f}s: " + ", ".join(
        f"{k} {ref[k]:.4g} (limit {config['correct'][k + '_max']:g})"
        for k in HELD) + f", choice differs at "
        f"{100 * ref['choice_differs_share']:.2f}% of "
        f"{ref['experts_positions']} positions")
    return out
