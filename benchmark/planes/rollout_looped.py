"""Cells of the rollout plane for a looped model (``ouro``: ONE stack of
layers run ``total_ut_steps`` times a token, each pass keeping keys and
values of its own in the same logical pages): ``planes/rollout_sambay.py``'s
plane (which is ``rollout_hybrid.py``'s and ``rollout.py``'s), imported and
not copied. From it, as they are: the mix's further engine options handed
on to ``create_server`` (``prefill_first``), one client thread, the window
opened once the client is level with the engine, the line of what the loop
spent set-up on, and the table of kernels that must have taken their TPU
path (the GQA paged decode attention and the fused K/V write,
``ops/paged_attention.py``, here at 16 K/V heads under one query head
each; no other dispatcher may have run). Of its own:

- the page arithmetic: the configuration's pool in bytes over what a token
  keeps in pages (``costs_looped.paged_bytes_per_token``: every layer's K
  and V of EVERY pass); the engine hands out that many logical pages and
  lays each pass's run of them out itself;
- once the window is over and the server still up, what the pages of each
  scored request hold (``held_pages``): the request's logical pages from
  the engine's table, and of the FIRST and the LAST layer's pool the rows
  of every pass, found as the program finds them
  (``cache_spec.pass_offset``);
- what ``correct`` compares (``compare``), each stated precision or
  mechanism by its own limit: the log-probability of each sampled token,
  and each pass's keys and values in pages against the reference's of that
  pass (with near-uniform attention over random weights a pass that
  attends another pass's keys moves the log-probabilities by little; it
  cannot hide here).

With no family key in the configuration (a ``--rehearse-cpu`` walk runs
``configs/rehearsal.json``'s tiny dense model under this plane) the page
arithmetic is GQA's and the comparison is ``planes/rollout.py``'s.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import costs_looped, harness

sambay = harness.load_named("planes", "rollout_sambay")
hybrid = sambay.hybrid
base = hybrid.base

# the numbers of ``compare`` that ``correct`` holds to a limit
HELD = ("logprob_mean_abs_diff", "logprob_max_abs_diff", "pass_kv_rel_diff")


class LoopedRolloutPlane(sambay.SambayRolloutPlane):
    def num_pages(self) -> int:
        per_page = (costs_looped.paged_bytes_per_token(self.config["config"])
                    * self.mix["engine"]["page_size"])
        return int(self.config["serve"]["kv_pool_bytes"] // per_page) + 1

    def window(self, seconds, trace, counter, settle):
        """``HybridRolloutPlane.window``; a traced window lasts as long as
        the profiler takes to stop, and the pool has to hold what the rows
        write meanwhile: the window's length is said with the run."""
        try:
            got = super().window(seconds, trace, counter, settle)
        except TimeoutError:
            self.say_where_it_stands()
            raise
        harness.say(f"the window lasted {got[1] - got[0]:.1f}s")
        return got

    def say_where_it_stands(self) -> None:
        """A window whose end found no tokens: what the engine holds and
        where every thread stands, before the run ends without a result."""
        import faulthandler
        import sys

        eng = self.eng
        harness.say(f"no tokens after the window: server_info "
                    f"{self.server_info()}")
        harness.say(
            f"active {eng._active.tolist()} seq_lens "
            f"{eng._seq_lens.tolist()} generated "
            f"{eng._n_generated.tolist()} free pages "
            f"{eng.allocator.free_count} pending {len(eng._pending)} "
            f"chunk jobs {len(eng._chunk_jobs)} recoveries {eng.recoveries} "
            f"seen {[(r.rid, r.n_seen, r.error) for r in self.offered]}")
        faulthandler.dump_traceback(file=sys.stderr)

    def held_pages(self, out: dict) -> list[dict] | None:
        """For each scored request of ``out`` (the pattern's result, the
        server still up): the answer's tokens that its pages hold beside
        the prompt's, and of the first and the last layer the rows ``[k |
        v]`` [n, H, 2D] float32 that each pass's pages hold of them,
        ``{"answer", "pass_kv": [pass][first, last]}``. None for a model
        that runs its stack once. The loop is held once, for as long as
        the two layers' pools take to reach the host (the rows write a page
        every 2.4 s each; what is not dispatched is not written)."""
        if not costs_looped.is_looped(self.config["config"]):
            return None
        from polyrl_tpu.models import cache_spec

        eng = self.eng
        reqs = [next(r for r in out["observed"]["requests"]
                     if r.prompt_len == len(prompt)
                     and r.tokens[:len(toks)] == toks)
                for prompt, toks, _lps in out["samples"]]
        with eng._pool_lock:
            # the manager hands the engine "<rid>#a<attempt>"
            slots = [next(i for i, info in enumerate(eng._slots)
                          if info is not None and eng._active[i]
                          and info.req.rid.split("#")[0] == req.rid)
                     for req in reqs]
            eng._ensure_dev_state()
            lens = np.asarray(eng._dev_state["seq_lens"])
            tables = np.array(eng._page_table)
            pools = [tuple(np.asarray(a) for a in pool)
                     for pool in (eng._pools[0][0], eng._pools[0][-1])]
        held = []
        for (prompt, _toks, _lps), req, slot in zip(out["samples"], reqs,
                                                    slots):
            consumed = int(lens[slot])
            pages = tables[slot, :-(-consumed // eng.page_size)]
            rows = [tuple(_rows(pool, pages + int(cache_spec.pass_offset(
                eng.cfg, pool[0], t)), consumed) for pool in pools)
                    for t in range(cache_spec.passes(eng.cfg))]
            fed = consumed - len(prompt)
            harness.wait_until(lambda: len(req.tokens) >= fed or req.error,
                               60, "the tokens a request's pages hold")
            held.append({"answer": list(req.tokens[:fed]), "pass_kv": rows})
        self.mark("pages_held")
        return held


def _rows(pool, pages, n: int) -> np.ndarray:
    """The rows ``[k | v]`` [n, H, 2D] float32 that the pages ``pages`` of
    a K/V pair ``pool`` ([H, N, ps, D] each, on the host) hold of a
    sequence's first ``n`` tokens."""
    k, v = (a[:, pages].astype(np.float32).transpose(1, 2, 0, 3).reshape(
        -1, a.shape[0], a.shape[3])[:n] for a in pool)
    return np.concatenate([k, v], axis=-1)


def walk(reference, c: dict, params, samples, held, control: str = ""):
    """The reference over each scored request's prompt and the answer its
    pages hold (``reference.trace``)."""
    return [reference.trace(params, c, list(prompt) + h["answer"],
                            len(prompt), min(len(toks), len(lps)), control)
            for (prompt, toks, lps), h in zip(samples, held)]


def pass_kv_rel(mine, theirs) -> list[float]:
    """|mine - theirs| over |theirs| a pass, of ``[pass][first, last]``
    rows each: both layers' rows of a pass as one vector."""
    return [float(hybrid.rel(np.concatenate([x.ravel() for x in a]),
                             np.concatenate([x.ravel() for x in b])))
            for a, b in zip(mine, theirs)]


def compare(limits: dict, samples, held, walked) -> dict:
    """``correct``'s numbers, each held to its limit of ``limits``:

    - ``logprob_mean_abs_diff``, ``logprob_max_abs_diff``: the system's
      log-probability of each sampled token against the reference's, nats;
    - ``pass_kv_rel_diff``: the keys and values that a scored request's
      pages hold once the window is over, for EACH pass, in the first and
      the last layer, against the reference's rotated keys and values of
      that pass over every token the pages hold, |difference| over
      |reference|; the mean over the scored requests, the WORST pass
      (``pass_kv_rel_diffs``: every request, every pass).

    ``held`` [requests]: ``{"answer", "pass_kv"}`` as ``held_pages`` gives
    them; ``walked``: ``walk``'s result."""
    worst, total, count = 0.0, 0.0, 0
    passes, tokens = [], []
    for (prompt, toks, lps), h, tr in zip(samples, held, walked):
        n = min(len(toks), len(lps))
        diff = np.abs(tr["logprobs"] - np.asarray(lps[:n], np.float32))
        worst, total, count = (max(worst, float(diff.max())),
                               total + float(diff.sum()), count + n)
        passes.append(pass_kv_rel(h["pass_kv"], tr["pass_kv"]))
        tokens.append(len(prompt) + len(h["answer"]))
    out = {"sequences": len(samples), "positions": count,
           "logprob_mean_abs_diff": total / max(count, 1),
           "logprob_max_abs_diff": worst,
           "pass_kv_rel_diff": float(np.max(np.mean(passes, axis=0)))
           if passes else 0.0,
           "pass_kv_rel_diffs": passes,
           "page_tokens": tokens}
    out["failed_by"] = [k for k in HELD if not out[k] <= limits[k + "_max"]]
    out["ok"] = bool(count > 0 and not out["failed_by"])
    return out


def window_counters(observed: dict) -> dict:
    """What the engine's own counters say of the window, in every run (the
    per-layer metrics that read the same keys print in traced runs only):
    the share of the window with device work outstanding, the passes a
    row's step ran, the rows that yielded."""
    from benchmark.lib import counters

    busy = counters.delta_ratio(observed, "device_busy_s", "device_busy_at_s")
    ran = counters.delta_ratio(observed, "ut_passes", "row_steps_done")
    info = observed.get("server_info") or [{}]
    return {"engine_device_busy": None if busy is None else 100.0 * busy,
            "passes_per_token": ran,
            "slot_yields": info[-1].get("slot_yields", 0)
            - info[0].get("slot_yields", 0)}


def run(cell, config, mix, device, seed, seconds, trace, counter, t_proc0):
    work = harness.work_dir(cell["name"])
    plane = LoopedRolloutPlane(cell, config, mix, device, seed, work, t_proc0)
    pattern = harness.load_named("patterns", mix["pattern"])
    try:
        plane.start()
        out = pattern.run(plane, seconds, trace, counter)
        held = plane.held_pages(out)
    finally:
        plane.stop()
    eng = plane.eng
    out["checks"]["engine_recoveries"] = int(eng.recoveries)
    k_ok, taken = sambay.kernels_ok(device)
    out["checks"]["kernels"] = {k: list(v) for k, v in taken.items()}
    out["checks"]["kernels_ok"] = k_ok
    out["checks"]["window_counters"] = said = window_counters(out["observed"])
    harness.say("the window by the engine's counters: " + ", ".join(
        f"{k} {v:.4g}" for k, v in said.items() if v is not None))
    out["device"] = device.as_dict()
    # the reference needs room: drop the pages, keep the weights
    samples = out.pop("samples")
    params = eng.params
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
    walked = walk(reference, config["config"], params, samples, held)
    out["checks"]["reference"] = ref = compare(config["correct"], samples,
                                               held, walked)
    harness.say(f"compared in {time.monotonic() - t0:.1f}s: " + ", ".join(
        f"{k} {ref[k]:.4g} (limit {config['correct'][k + '_max']:g})"
        for k in HELD))
    return out
