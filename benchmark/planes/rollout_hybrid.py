"""Cells of the rollout plane for a model that keeps a recurrent state in
the engine's slots beside a paged latent cache (the Ling-3.0 hybrid):
``planes/rollout.py``'s plane, imported and not copied, with these things
of its own:

- the page arithmetic: the configuration's pool in bytes over what a token
  really keeps in pages (``costs_hybrid.paged_bytes_per_token``: one
  latent row in each MLA layer, not a K/V pair a layer); the state slots
  are the engine's, ``max_slots`` of them, and no part of the pool;
- the table of kernels that must have taken their TPU path: the absorbed
  latent attention as the program notes it (``ops/dispatch.py``); this
  model writes no K/V pair and runs no GQA attention (the experts' grouped
  matmul notes no key: PERF.md section 7 (h));
- engine options of the mix beyond those ``planes/rollout.py::start``
  names (``prefill_first``) reach ``create_server`` too;
- the weights: the router's bias is evened once the weights are drawn, by
  the reference's own router (``even_router_bias``), and pushed as a
  trainer's push is;
- what ``correct`` compares (``compare``): beside the log-probability of
  each sampled token, the recurrent state the engine holds for a scored
  request after the window, against the reference's recurrence over the
  same tokens, and the program's routed experts on the reference's
  hidden states, against the reference's: each precision the
  configuration states is held on its own;
- the window starts once the client has read what the engine has sent
  (``window``): a stall of the streaming path in set-up is not counted as
  the engine's rate;
- what it frees before the reference runs: the engine's ``_pools`` holds
  the pages AND the state slots of such a model, and both go.

With no latent key in the configuration (a ``--rehearse-cpu`` walk runs
``configs/rehearsal.json``'s tiny dense model under this plane) the page
arithmetic is GQA's, the comparison is ``planes/rollout.py``'s and the
kernel table is never consulted.
"""

from __future__ import annotations

import functools
import gc
import time

import numpy as np

from benchmark.lib import costs_hybrid, harness

base = harness.load_named("planes", "rollout")

# what each kernel dispatcher must have taken on a TPU; a dispatcher that
# is not listed (``paged_attention``, ``kv_write``) must not have run
KERNELS_ON_TPU = {"latent_attention": ("pallas",)}

# the engine options ``planes/rollout.py::start`` hands on by name
BASE_ENGINE_KEYS = ("max_slots", "page_size", "max_seq_len", "prompt_buckets",
                    "prefill_chunk", "steps_per_dispatch")

# how long the window may wait for the client to be level with the engine
LEVEL_WAIT_S = 120.0

# the sequences on which the router's bias is evened: count, length, and
# the position from which a sequence's rows count (a state is warm by then)
EVEN_ON = (8, 512, 256)


class HybridRolloutPlane(base.RolloutPlane):
    def num_pages(self) -> int:
        per_page = (costs_hybrid.paged_bytes_per_token(self.config["config"])
                    * self.mix["engine"]["page_size"])
        return int(self.config["serve"]["kv_pool_bytes"] // per_page) + 1

    def start(self) -> None:
        """``RolloutPlane.start`` with the mix's further engine options;
        then, for a model with a sigmoid router, the router's bias evened
        on sequences drawn from ``--seed`` (what training does to that
        bias, done once for weights that were never trained) and installed
        as a trainer's push is. The weights stay a function of the seed
        alone, and the program's router has no part in making them."""
        from polyrl_tpu.rollout import serve

        more = {k: v for k, v in self.mix["engine"].items()
                if k not in BASE_ENGINE_KEYS}
        create = serve.create_server
        serve.create_server = functools.partial(create, **more)
        try:
            super().start()
        finally:
            serve.create_server = create
        eng = self.eng
        if eng.cfg.scoring_func != "sigmoid":
            return          # a rehearsal's dense model
        import jax

        from benchmark.lib import traffic

        n, length, skip = EVEN_ON
        ids = traffic.rng_for(self.seed, 2).integers(
            1, eng.cfg.vocab_size, size=(n, length))
        reference = harness.load_named("references", self.config["reference"])
        tree = eng.params
        bias = reference.even_router_bias(tree, self.config["config"], ids,
                                          skip)
        moe = dict(tree["layers"]["moe"], router_bias=bias)
        eng.update_weights(
            {**tree, "layers": {**tree["layers"], "moe": moe}},
            version=eng.weight_version)
        jax.block_until_ready(eng.params)
        self.mark("router_evened")

    def stream(self, client, reqs, prompts) -> None:
        self.offered = getattr(self, "offered", []) + list(reqs)
        super().stream(client, reqs, prompts)

    def backlog(self) -> int:
        """Tokens the engine has put on its streams that the client has
        not read yet."""
        return int(self.eng.total_tokens_served) - sum(
            r.n_seen for r in self.offered)

    def window(self, seconds, trace, counter, settle):
        """``RolloutPlane.window``, started once the client is level with
        the engine. 128 streams of one token a line keep this process's
        streaming path (server threads, manager, client) within a few
        percent of what it can carry, so a stall of it during set-up
        leaves the client seconds behind, and a window that starts then
        counts the catching up as the engine's rate (PERF.md section 6, PR
        33: one run of 16 read +3.2% that way). Level: under two
        dispatches' tokens on the way."""
        level = 2 * self.mix["engine"]["max_slots"] \
            * self.mix["engine"]["steps_per_dispatch"]
        before = self.backlog()
        try:
            harness.wait_until(lambda: self.backlog() <= level, LEVEL_WAIT_S,
                               "the client level with the engine", poll_s=0.02)
        except TimeoutError:
            pass        # measured as it is; the checks say so
        after = self.backlog()
        self.mark("level")
        got = super().window(seconds, trace, counter, settle)
        self.checks["client_backlog_tokens"] = {"at_warm": before,
                                                "at_window": after}
        return got

    def held_states(self, out: dict) -> list[dict] | None:
        """For each scored request of ``out`` (the pattern's result, the
        server still up): the recurrent state its slot holds now and the
        answer's tokens that went into it, ``{"answer", "states"}``. None
        for a model without such a state."""
        eng = self.eng
        if not eng.stateful:
            return None
        held = []
        for prompt, toks, _lps in out["samples"]:
            req = next(r for r in out["observed"]["requests"]
                       if r.prompt_len == len(prompt)
                       and r.tokens[:len(toks)] == toks)
            # the manager hands the engine "<rid>#a<attempt>"
            rid = next(i.req.rid for i in eng._slots if i is not None
                       and i.req.rid.split("#")[0] == req.rid)
            got = eng.recurrent_state(rid)
            if got is None:
                raise RuntimeError(f"{req.rid} is not decoding")
            consumed, states = got
            fed = consumed - len(prompt)
            harness.wait_until(lambda: len(req.tokens) >= fed or req.error,
                               60, "the tokens a held state has consumed")
            held.append({"answer": list(req.tokens[:fed]), "states": states})
        self.mark("states_held")
        return held


def rel(a, b, axis=None):
    """|a - b| over |b| (2-norms)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b, axis=axis) / np.maximum(
        np.linalg.norm(b, axis=axis), 1e-30)


def program_experts(cfg) -> list:
    """The PROGRAM's routed experts of each sparse layer, as jitted
    functions of (the tree's ``layers``, hidden states [N, d] in the type
    the program serves in): ``blocks._moe_mlp`` as the decode step and the
    prefill call it, without the shared expert."""
    import jax

    from polyrl_tpu.models import blocks, cache_spec, hybrid

    sparse = [l for l, p in enumerate(cache_spec.layer_plan(cfg))
              if p.mlp == "moe"]

    def block(layers, h, l, j):
        lp = {k: v for k, v in hybrid._layer_params(cfg, layers, l)[1].items()
              if not k.startswith("ws_")}
        return blocks._moe_mlp(cfg, h, lp, None, j)[0]

    return [jax.jit(functools.partial(block, l=l, j=j))
            for j, l in enumerate(sparse)]


def walk(reference, cfg, params, c: dict, samples, held) -> list[dict]:
    """The reference over each scored request's prompt and consumed
    answer (``reference.trace``), and the program's routed experts
    (``experts``, [N, d] float32 a sparse layer) on the hidden states it
    found there, rounded to the served type (``moe_in``)."""
    import jax.numpy as jnp

    blocks = program_experts(cfg)
    walked = []
    for (prompt, toks, lps), h in zip(samples, held):
        n = min(len(toks), len(lps))
        tr = reference.trace(params, c, list(prompt) + h["answer"],
                             len(prompt), n)
        served = [jnp.asarray(x, cfg.dtype) for x in tr["moe_in"]]
        tr["moe_in"] = [np.asarray(x, np.float32) for x in served]
        tr["experts"] = [np.asarray(f(params["layers"], x), np.float32)
                         for f, x in zip(blocks, served)]
        walked.append(tr)
    return walked


def compare(reference, params, c: dict, limits: dict, samples, held,
            walked, again: bool = False) -> dict:
    """``correct``'s numbers for a model with a recurrent state, each held
    to its limit of ``limits``:

    - ``logprob_mean_abs_diff``, ``logprob_max_abs_diff``: the system's
      log-probability of each sampled token against the reference's, nats;
    - ``state_rel_diff``: the recurrent state a scored request's slot held
      after the window against the reference's recurrence over the same
      tokens, |difference| over |reference| of the first KDA layer's
      state, the mean over the scored requests (``state_rel_diffs``: every
      request, every KDA layer);
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
        walked = [{**reference.trace(params, c, list(s[0]) + h["answer"],
                                     len(s[0]), min(len(s[1]), len(s[2]))),
                   "moe_in": old["moe_in"], "experts": old["experts"]}
                  for s, h, old in zip(samples, held, walked)]
    worst, total, count = 0.0, 0.0, 0
    states, rows = [], []
    for (prompt, toks, lps), h, tr in zip(samples, held, walked):
        n = min(len(toks), len(lps))
        diff = np.abs(tr["logprobs"] - np.asarray(lps[:n], np.float32))
        worst, total, count = (max(worst, float(diff.max())),
                               total + float(diff.sum()), count + n)
        states.append([float(rel(mine, ref)) for mine, ref
                       in zip(h["states"], tr["states"])])
        for j, (x, mine) in enumerate(zip(tr["moe_in"], tr["experts"])):
            ref = reference.routed_block(params, c, j, x)
            some = np.linalg.norm(ref, axis=-1) > 0
            rows.append(rel(mine[some], ref[some], axis=-1))
    rows = np.concatenate(rows)
    out = {"sequences": len(samples), "positions": count,
           "logprob_mean_abs_diff": total / max(count, 1),
           "logprob_max_abs_diff": worst,
           "state_rel_diff": float(np.mean([s[0] for s in states])),
           "state_rel_diffs": states,
           "state_tokens": [len(s[0]) + len(h["answer"])
                            for s, h in zip(samples, held)],
           "experts_rel_diff": float(np.median(rows)),
           "experts_positions": int(rows.size)}
    out["ok"] = bool(count > 0 and rows.size > 0 and all(
        out[k] <= limits[k + "_max"] for k in
        ("logprob_mean_abs_diff", "logprob_max_abs_diff", "state_rel_diff",
         "experts_rel_diff")))
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
    unrounded ones from the seed here: ``tests/control_hybrid_on_chip.py``.)"""
    return eng.params


def run(cell, config, mix, device, seed, seconds, trace, counter, t_proc0):
    work = harness.work_dir(cell["name"])
    plane = HybridRolloutPlane(cell, config, mix, device, seed, work, t_proc0)
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
    out["device"] = device.as_dict()
    # the reference needs room: drop the pages and the state slots (both
    # live in the engine's pools), keep the weights
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
    out["checks"]["reference"] = compare(
        reference, params, config["config"], config["correct"], samples,
        held, walked, again)
    harness.say(f"compared in {time.monotonic() - t0:.1f}s: " + ", ".join(
        f"{k} {out['checks']['reference'][k]:.4g}" for k in
        ("state_rel_diff", "experts_rel_diff")))
    return out
