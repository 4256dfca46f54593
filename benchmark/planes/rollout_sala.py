"""Cells of the rollout plane for MiniCPM-SALA's family (``minicpm_sala``:
block-sparse attention layers whose pages carry a pooled-key store, among
linear-attention layers with a float32 state in the slot):
``planes/rollout_sambay.py``'s plane (which is ``rollout_hybrid.py``'s and
``rollout.py``'s), imported and not copied. From it, as they are: the mix's
further engine options handed on to ``create_server`` (``prefill_first``),
one client thread, the window opened once the client is level with the
engine, the line of what the loop spent set-up on, and the table of kernels
that must have taken their TPU path (the GQA paged decode attention and the
fused K/V write, ``ops/paged_attention.py``, here over the pools seen as
ONE K/V head under 16 query rows; no other dispatcher may have run: the
lightning state's kernel notes nothing there, ``lightning_kernel_share``
says whether it ran). Of its own:

- the page arithmetic: the configuration's pool in bytes over what a token
  keeps in pages (``costs_sala.paged_bytes_per_token``: a K/V pair a
  sparse layer and its share of the float32 pooled keys); the states are
  the engine's, a fixed size a slot, and no part of the pool;
- once the window is over and the server still up, what each scored
  request's slot and pages hold (``held``): the lightning layers' states
  and the table of pages that the FIRST layer's last timed decode step
  attended (``CBEngine.recurrent_state``: a sparse layer's step leaves its
  table and the keys it holds in the slot), and the first layer's
  pooled-key store at the request's pages, found through the engine's
  table as the program finds them;
- what ``correct`` compares (``compare``), each stated precision or
  mechanism by its own limit: the log-probability of each sampled token;
  the first lightning layer's float32 state against the reference's
  recurrence over every token consumed; the pooled keys the timed path
  wrote against the reference's means; and the blocks the compiled STEP
  attended for the last consumed token (``step_choice``: its table read
  back through the row's pages, a head's offset, the order and the count
  of keys with it) against the reference's choice (with near-uniform
  attention over random weights a wrong set of pages hides inside any
  log-probability limit; here it cannot). Beside it, held to nothing:
  what the program's choice, run again from that store after the window
  (``program_choice``), differs by (``selected_again_diff``): if the two
  disagree, the fault lies past the scores, in the step's table.

With no family key in the configuration (a ``--rehearse-cpu`` walk runs
``configs/rehearsal.json``'s tiny dense model under this plane) the page
arithmetic is GQA's and the comparison is ``planes/rollout.py``'s.
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from benchmark.lib import costs_sala, harness

sambay = harness.load_named("planes", "rollout_sambay")
hybrid = sambay.hybrid
base = hybrid.base

# the numbers of ``compare`` that ``correct`` holds to a limit
HELD = ("logprob_mean_abs_diff", "logprob_max_abs_diff", "state_rel_diff",
        "selected_set_diff", "pooled_rel_diff")


class SalaRolloutPlane(sambay.SambayRolloutPlane):
    def num_pages(self) -> int:
        per_page = (costs_sala.paged_bytes_per_token(self.config["config"])
                    * self.mix["engine"]["page_size"])
        return int(self.config["serve"]["kv_pool_bytes"] // per_page) + 1

    def held(self, out: dict) -> list[dict] | None:
        """For each scored request of ``out`` (the pattern's result, the
        server still up): ``{"answer", "states", "picked", "pages",
        "pool_pages", "pooled"}``: the answer's tokens that went into its
        slot, the lightning layers' states in layer order, what the first
        layer's last decode step attended (``mixers/sparse.py``'s slot
        row, [Hkv, W + 1]), the pages that hold those tokens in order, the
        pages of a K/V head's pool, and the first layer's pooled-key store
        at those pages ``[pages, r * Hkv, D]`` float32. None for a model
        of another family."""
        if not costs_sala.is_sala(self.config["config"]):
            return None
        import jax.numpy as jnp

        eng = self.eng
        held = self.held_states(out)
        kinds = costs_sala.kinds(self.config["config"])
        if kinds[0] != "sparse":
            raise ValueError("the first layer is no sparse layer")
        for h in held:      # every layer of the family keeps a slot
            rows = h["states"]
            h["picked"] = np.asarray(rows[0])
            h["states"] = [r for r, k in zip(rows, kinds) if k == "lightning"]
        reqs = [next(r for r in out["observed"]["requests"]
                     if r.prompt_len == len(prompt)
                     and r.tokens[:len(toks)] == toks)
                for prompt, toks, _lps in out["samples"]]
        with eng._pool_lock:
            slots = [next(i for i, info in enumerate(eng._slots)
                          if info is not None and eng._active[i]
                          and info.req.rid.split("#")[0] == req.rid)
                     for req in reqs]
            tables = np.array(eng._page_table)
            store = eng._pools[0][0][2]
            for (prompt, _t, _l), h, slot in zip(out["samples"], held, slots):
                consumed = len(prompt) + len(h["answer"])
                pages = tables[slot, :-(-consumed // eng.page_size)]
                h["pages"] = pages
                h["pool_pages"] = eng._pools[0][0][0].shape[1]
                h["pooled"] = np.asarray(store[jnp.asarray(pages)],
                                         np.float32)
        self.mark("pages_held")
        return held


def step_choice(c: dict, picked, pages, n: int,
                pool_pages: int) -> np.ndarray:
    """The blocks the compiled decode step attended for a row's token at
    position ``n - 1``, a mask [Hkv, blocks], from what the step left in
    the row's slot: ``picked`` [Hkv, W + 1], a K/V head's table of page
    numbers (head ``g``'s offset by ``g * pool_pages``) and, last, the
    keys they hold. ``pages``: the row's pages in order (block ``m`` lies
    in ``pages[m]``). A head's row counts for nothing (all False) unless
    it is a table the attention kernel reads as the reference's choice
    would be read: every page one of the row's own under the head's
    offset, the blocks in rising order with the token's own block last,
    and the keys exactly those blocks' up to the token."""
    block = c["sparse_config"]["block_size"]
    picked = np.asarray(picked)
    place = {int(p): m for m, p in enumerate(pages)}
    own = (n - 1) // block
    took = np.zeros((picked.shape[0], len(pages)), bool)
    for g, row in enumerate(picked):
        keys = int(row[-1])
        count = -(-keys // block)
        if not 1 <= count < len(row):
            continue
        at = [place.get(int(p) - g * pool_pages) for p in row[:count]]
        if None in at or any(a >= b for a, b in zip(at, at[1:])):
            continue
        if at[-1] != own or keys != (count - 1) * block + n - own * block:
            continue
        took[g, at] = True
    return took


def program_choice(cfg, params, token: int, store, n: int) -> np.ndarray:
    """The blocks the PROGRAM chooses for the token ``token`` at position
    ``n - 1`` of a sequence whose first layer's pooled keys are ``store``
    [pages, r * Hkv, D]: the program's own embedding, norm, products and
    q/k norms of its first layer (a sparse layer on the embedding: no
    other layer's rounding lies below), its scores and its choice
    (``mixers/sparse.py``): a mask [Hkv, pages]."""
    import jax.numpy as jnp

    from polyrl_tpu.models import blocks, cache_spec, hybrid as model
    from polyrl_tpu.models.mixers import sparse

    if cache_spec.layer_plan(cfg)[0].mixer != "sparse":
        raise ValueError("the first layer is no sparse layer")
    layers = params["layers"]
    x = model._embed(cfg, params, jnp.asarray([token], jnp.int32))
    h_in = blocks.norm(layers, "attn_norm", x, cfg.rms_norm_eps, 0)
    lp = {k: v[0] for k, v in layers["sparse"].items()}
    q = sparse._qkv(cfg, lp, h_in)[0]                         # [1, H, D]
    r = sparse.geometry(cfg)[3]
    pages, _rows, d = store.shape
    pooled = jnp.asarray(store).reshape(1, pages * r, cfg.num_kv_heads, d)
    seen = jnp.asarray([[n]], jnp.int32)
    took = sparse.choose(
        cfg, sparse.block_scores(cfg, q[:, None], pooled, seen), seen)
    return np.asarray(took[0, :, 0])


def store_rows(cfg_keys: dict, store, n: int) -> np.ndarray:
    """The pooled keys ``[J, Hkv, D]`` that a store ``[pages, r * Hkv, D]``
    holds of a sequence of ``n`` tokens: every j with ``stride j + kernel
    <= n``, in order."""
    sp = cfg_keys["sparse_config"]
    r = sp["block_size"] // sp["kernel_stride"]
    hkv = cfg_keys["num_key_value_heads"]
    pages, _rows, d = store.shape
    j = max((n - sp["kernel_size"]) // sp["kernel_stride"] + 1, 0)
    return store.reshape(pages * r, hkv, d)[:j]


def set_diff(mine, theirs) -> float:
    """The share of ``theirs``' chosen blocks (a mask [Hkv, M]) that
    ``mine`` [Hkv, M'] did not choose, the mean over the K/V heads."""
    m = max(mine.shape[1], theirs.shape[1])
    a, b = (np.pad(np.asarray(x, bool), ((0, 0), (0, m - x.shape[1])))
            for x in (mine, theirs))
    return float(np.mean((b & ~a).sum(1) / np.maximum(b.sum(1), 1)))


def walk(reference, cfg, params, c: dict, samples, held,
         control: str = "", upto: int | None = None) -> list[dict]:
    """The reference over each scored request's prompt and consumed answer
    (``reference.trace``), and beside it what the timed step attended for
    the last consumed token (``step_choice``: ``chosen_mine``) and what
    the program chooses for it again from the store its pages hold
    (``program_choice``: ``chosen_again``). ``control``, ``upto``: the
    reference's own (a control of ``correct``; its first layers alone)."""
    walked = []
    for (prompt, toks, lps), h in zip(samples, held):
        seq = list(prompt) + h["answer"]
        tr = reference.trace(params, c, seq, len(prompt),
                             min(len(toks), len(lps)), control, upto)
        tr["chosen_mine"] = step_choice(c, h["picked"], h["pages"], len(seq),
                                        h["pool_pages"])
        tr["chosen_again"] = program_choice(cfg, params, seq[-1],
                                            h["pooled"], len(seq))
        walked.append(tr)
    return walked


def compare(limits: dict, c: dict, samples, held, walked) -> dict:
    """``correct``'s numbers, each held to its limit of ``limits``:

    - ``logprob_mean_abs_diff``, ``logprob_max_abs_diff``: the system's
      log-probability of each sampled token against the reference's, nats;
    - ``state_rel_diff``: the float32 state of the FIRST lightning layer
      that a scored request's slot held after the window, against the
      reference's recurrence over the same tokens, |difference| over
      |reference|, the mean over the scored requests (``state_rel_diffs``:
      every request);
    - ``pooled_rel_diff``: the FIRST layer's pooled keys that the request's
      pages held (written by the prefill's chunks and the decode steps of
      the timed path), every whole one, against the reference's means of
      its own keys, |difference| over |reference|, the mean over the
      scored requests;
    - ``selected_set_diff``: the share of the reference's chosen blocks a
      K/V head, at the last consumed token, that the timed decode step's
      own table did not hold (``step_choice``), the mean over heads and
      scored requests. ``selected_again_diff``, held to nothing: the same
      of the program's choice run again from the store
      (``program_choice``).

    ``held`` [requests]: ``held``'s rows; ``walked``: ``walk``'s result."""
    worst, total, count = 0.0, 0.0, 0
    states, pooled, chosen, again, tokens = [], [], [], [], []
    for (prompt, toks, lps), h, tr in zip(samples, held, walked):
        n = min(len(toks), len(lps))
        diff = np.abs(tr["logprobs"] - np.asarray(lps[:n], np.float32))
        worst, total, count = (max(worst, float(diff.max())),
                               total + float(diff.sum()), count + n)
        consumed = len(prompt) + len(h["answer"])
        states.append(float(hybrid.rel(h["states"][0], tr["state"])))
        pooled.append(float(hybrid.rel(
            store_rows(c, h["pooled"], consumed), tr["pooled"])))
        chosen.append(set_diff(tr["chosen_mine"], tr["chosen"]))
        again.append(set_diff(tr["chosen_again"], tr["chosen"]))
        tokens.append(consumed)
    out = {"sequences": len(samples), "positions": count,
           "logprob_mean_abs_diff": total / max(count, 1),
           "logprob_max_abs_diff": worst,
           "state_rel_diff": float(np.mean(states)),
           "state_rel_diffs": states,
           "pooled_rel_diff": float(np.mean(pooled)),
           "pooled_rel_diffs": pooled,
           "selected_set_diff": float(np.mean(chosen)),
           "selected_set_diffs": chosen,
           "selected_again_diff": float(np.mean(again)),
           "state_tokens": tokens}
    out["failed_by"] = [k for k in HELD if not out[k] <= limits[k + "_max"]]
    out["ok"] = bool(count > 0 and not out["failed_by"])
    return out


def window_counters(observed: dict) -> dict:
    """What the engine's own counters say of the window, in every run (the
    per-layer metrics that read the same keys print in traced runs only):
    the share of the window with device work outstanding, the chosen pages
    a row, head and sparse layer, the live rows at or under ``dense_len``
    a step, the rows that yielded."""
    from benchmark.lib import counters

    busy = counters.delta_ratio(observed, "device_busy_s", "device_busy_at_s")
    info = observed.get("server_info") or [{}]
    return {"engine_device_busy": None if busy is None else 100.0 * busy,
            "sparse_pages_per_row": costs_sala.pages_per_row(observed),
            "sparse_dense_rows": costs_sala.counted_per_step(
                observed, "sparse_dense_rows"),
            "slot_yields": info[-1].get("slot_yields", 0)
            - info[0].get("slot_yields", 0)}


def run(cell, config, mix, device, seed, seconds, trace, counter, t_proc0):
    work = harness.work_dir(cell["name"])
    plane = SalaRolloutPlane(cell, config, mix, device, seed, work, t_proc0)
    pattern = harness.load_named("patterns", mix["pattern"])
    try:
        plane.start()
        out = pattern.run(plane, seconds, trace, counter)
        held = plane.held(out)
    finally:
        plane.stop()
    eng = plane.eng
    out["checks"]["engine_recoveries"] = int(eng.recoveries)
    k_ok, taken = sambay.kernels_ok(device)
    out["checks"]["kernels"] = {k: list(v) for k, v in taken.items()}
    out["checks"]["kernels_ok"] = k_ok
    out["observed"]["config"] = config
    out["checks"]["window_counters"] = said = window_counters(out["observed"])
    harness.say("the window by the engine's counters: " + ", ".join(
        f"{k} {v:.4g}" for k, v in said.items() if v is not None))
    out["device"] = device.as_dict()
    # the reference needs room: drop the pages and the states (all live in
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
        config["correct"], config["config"], samples, held, walked)
    harness.say(f"compared in {time.monotonic() - t0:.1f}s: " + ", ".join(
        f"{k} {ref[k]:.4g} (limit {config['correct'][k + '_max']:g})"
        for k in HELD))
    for k in HELD[2:]:      # ``harness.compared`` prints the first two
        print(f"compared {k}: {ref[k]:g} (limit "
              f"{config['correct'][k + '_max']:g})", file=sys.stderr,
              flush=True)
    return out
