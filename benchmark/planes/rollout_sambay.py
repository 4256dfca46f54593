"""Cells of the rollout plane for a model of the SambaY family (``phi4flash``:
Mamba-1 scans and window attention below, ONE full-attention layer whose
paged K/V the cross layers above read, gated memory units, differential
attention): ``planes/rollout_hybrid.py``'s plane (which is
``planes/rollout.py``'s), imported and not copied. From it, as they are:
the mix's further engine options handed on to ``create_server``
(``prefill_first``), one client thread, the window opened once the client
is level with the engine, and the slot's rows read once the window is over
(``held_states``: ``CBEngine.recurrent_state`` gives each Mamba layer's
state ``[I, N]`` and each window layer's ring, in layer order). Of its
own:

- the page arithmetic: the configuration's pool in bytes over what a token
  keeps in pages (``costs_sambay.paged_bytes_per_token``: ONE layer's K and
  V, whatever the depth); the rings and the states are the engine's, a
  fixed size a slot, and no part of the pool;
- at the end of the prompts' prefill, a line of what the engine's loop
  spent set-up on (``mark``);
- the table of kernels that must have taken their TPU path: the GQA paged
  decode attention and the fused K/V write (``ops/paged_attention.py``, at
  40 query rows of 128 over 10 K/V heads), and no other dispatcher may
  have run; nothing is evened (the model is dense);
- what ``correct`` compares (``compare``), each stated precision by its
  own limit: the log-probability of each sampled token; the float32 state
  of the first scan (tightly: no bf16 layer lies below it) and of the scan
  at half depth (layer 16, loosely) that a scored request's slot holds
  after the window, against the reference's recurrence over every token
  it has consumed; and the first window layer's ring, as the SET of its
  rows, against the reference's K and V of the last ``sliding_window``
  tokens (with near-uniform attention over random weights a window that is
  a key short or long hides inside any log-probability limit; a row that
  is missing or one too many cannot hide here).

With no family key in the configuration (a ``--rehearse-cpu`` walk runs
``configs/rehearsal.json``'s tiny dense model under this plane) the page
arithmetic is GQA's and the comparison is ``planes/rollout.py``'s.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import costs_sambay, harness

hybrid = harness.load_named("planes", "rollout_hybrid")
base = hybrid.base

KERNELS_ON_TPU = {"paged_attention": ("lib",), "kv_write": ("pallas",)}

# the numbers of ``compare`` that ``correct`` holds to a limit
HELD = ("logprob_mean_abs_diff", "logprob_max_abs_diff", "state_rel_diff",
        "state_last_rel_diff", "window_rel_diff")


class SambayRolloutPlane(hybrid.HybridRolloutPlane):
    def num_pages(self) -> int:
        per_page = (costs_sambay.paged_bytes_per_token(self.config["config"])
                    * self.mix["engine"]["page_size"])
        return int(self.config["serve"]["kv_pool_bytes"] // per_page) + 1

    def mark(self, phase: str) -> None:
        """With the prompts in, what the engine's loop spent set-up on so
        far (its profiler's cumulative phases and builds): the prompts'
        prefill is most of this cell's ``setup_s``, and most of THAT is
        the compiler's: a program is built inside the dispatch that first
        needs it (``prefill_dispatch``), 22 of them in 325-385 s in every
        run, because the machine's compile cache does not hold them from
        one run to the next (``checks.cache_misses_in_setup``), where the
        device needs 45-76 ms a 512-token chunk
        (``tools/trace_prefill_chunk.py``; PERF.md section 6, PR 43)."""
        super().mark(phase)
        prof = getattr(self.eng, "profiler", None)
        if phase == "prefilled" and prof is not None:
            c = prof.counters()
            spent = {k[len("phase_"):-2]: round(v, 1) for k, v in c.items()
                     if k.startswith("phase_") and v >= 0.5}
            harness.say(f"the loop until the prompts were in: {spent} s of "
                        f"{c['loop_wall_s']:.1f} s, "
                        f"{self.eng.chunk_dispatches} chunks dispatched, "
                        f"{c['programs_built']} programs built in "
                        f"{c['build_s']:.1f} s")

    def stream(self, client, reqs, prompts) -> None:
        lens = sorted(r.prompt_len for r in reqs)
        harness.say(f"{len(lens)} prompts of {lens[0]}-{lens[-1]} tokens, "
                    f"median {lens[len(lens) // 2]}, {sum(lens)} in all")
        super().stream(client, reqs, prompts)


def ring_in_order(ring, consumed: int):
    """A held ring ``[window, pairs, w]`` (token ``t`` at row ``t %
    window``) after ``consumed`` tokens as (the rows that hold a token,
    oldest first, the position of the first)."""
    window = ring.shape[0]
    first = max(0, consumed - window)
    return np.stack([ring[t % window] for t in range(first, consumed)]), first


def ring_rel(mine, theirs) -> float:
    """|mine - theirs| over |theirs| of two sets of rows, each (rows
    oldest first, position of the first): position by position over the
    positions either holds, a row that the other lacks counted whole."""
    (a, a0), (b, b0) = mine, theirs
    lo, hi = min(a0, b0), max(a0 + len(a), b0 + len(b))
    both = np.zeros((2, hi - lo, *b.shape[1:]), np.float64)
    both[0, a0 - lo:a0 - lo + len(a)] = a
    both[1, b0 - lo:b0 - lo + len(b)] = b
    return float(np.linalg.norm(both[0] - both[1])
                 / max(np.linalg.norm(both[1]), 1e-30))


def split_held(states, consumed: int):
    """``CBEngine.recurrent_state``'s rows, in layer order, as (the Mamba
    layers' states [I, N], the window layers' rings in order)."""
    return ([s for s in states if s.ndim == 2],
            [ring_in_order(s, consumed) for s in states if s.ndim == 3])


def slow_state_rel(state, walked: dict) -> float:
    """|state - reference| over |reference| of the first Mamba layer's
    state ``[I, N]``, over the channels the reference's walk ``walked``
    found slowest."""
    rows = walked["slow"][0]
    return float(hybrid.rel(np.asarray(state)[rows],
                            walked["states"][0][rows]))


def walk(reference, c: dict, params, samples, held, control: str = ""):
    """The reference over each scored request's prompt and consumed
    answer (``reference.trace``)."""
    return [reference.trace(params, c, list(prompt) + h["answer"],
                            len(prompt), min(len(toks), len(lps)), control)
            for (prompt, toks, lps), h in zip(samples, held)]


def compare(limits: dict, samples, held, walked) -> dict:
    """``correct``'s numbers, each held to its limit of ``limits``:

    - ``logprob_mean_abs_diff``, ``logprob_max_abs_diff``: the system's
      log-probability of each sampled token against the reference's, nats;
    - ``state_rel_diff``: the float32 state of the FIRST Mamba layer that
      a scored request's slot held after the window, against the
      reference's recurrence over the same tokens, |difference| over
      |reference| over the quarter of the layer's channels that stepped
      least (the reference's ``slow``), the mean over the scored requests.
      The first layer, whose inputs no bf16 layer below has moved; its
      slowest channels, because a state's rounding adds up over what a
      channel remembers, 1 / (dt A) tokens, while the error of its bf16
      inputs averages out over the same tokens: over the whole state a
      bfloat16 state reads 1.5 times a sound float32 one, over the slow
      quarter six times (``slow_state_rel``). ``state_last_rel_diff``:
      the whole state of the LAST Mamba layer (the scan at half depth,
      whose output the gated memory units read), where sixteen layers of
      bf16 activations have moved the inputs ten times as far as a
      state's precision could: held loosely, for a stale, misplaced or
      zeroed row (``state_rel_diffs``: every request, every Mamba layer,
      the whole state);
    - ``window_rel_diff``: the FIRST window layer's ring, as the set of
      the rows that hold a token, against the reference's ``[k0 | k1 | v0
      | v1]`` of the last ``sliding_window`` tokens (``ring_rel``), the
      mean over the scored requests (``window_rel_diffs``: every request,
      every window layer).

    ``held`` [requests]: ``{"answer", "states"}`` as ``held_states`` gives
    them; ``walked``: ``walk``'s result."""
    worst, total, count = 0.0, 0.0, 0
    states, slow, rings, tokens = [], [], [], []
    for (prompt, toks, lps), h, tr in zip(samples, held, walked):
        n = min(len(toks), len(lps))
        diff = np.abs(tr["logprobs"] - np.asarray(lps[:n], np.float32))
        worst, total, count = (max(worst, float(diff.max())),
                               total + float(diff.sum()), count + n)
        consumed = len(prompt) + len(h["answer"])
        mine_s, mine_r = split_held(h["states"], consumed)
        states.append([float(hybrid.rel(a, b))
                       for a, b in zip(mine_s, tr["states"])])
        slow.append(slow_state_rel(mine_s[0], tr))
        rings.append([ring_rel(a, b) for a, b in zip(mine_r, tr["rings"])])
        tokens.append(consumed)
    out = {"sequences": len(samples), "positions": count,
           "logprob_mean_abs_diff": total / max(count, 1),
           "logprob_max_abs_diff": worst,
           "state_rel_diff": float(np.mean(slow)),
           "state_last_rel_diff": float(np.mean([s[-1] for s in states])),
           "state_rel_diffs": states,
           "window_rel_diff": float(np.mean([r[0] for r in rings])),
           "window_rel_diffs": rings,
           "state_tokens": tokens}
    out["failed_by"] = [k for k in HELD if not out[k] <= limits[k + "_max"]]
    out["ok"] = bool(count > 0 and not out["failed_by"])
    return out


def kernels_ok(device) -> tuple[bool, dict]:
    from polyrl_tpu.ops import dispatch

    taken = dispatch.taken()
    if device.rehearse:
        return True, taken
    return (all(taken[k] == KERNELS_ON_TPU.get(k) for k in taken)
            and all(k in taken for k in KERNELS_ON_TPU)), taken


def run(cell, config, mix, device, seed, seconds, trace, counter, t_proc0):
    work = harness.work_dir(cell["name"])
    plane = SambayRolloutPlane(cell, config, mix, device, seed, work,
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
    k_ok, taken = kernels_ok(device)
    out["checks"]["kernels"] = {k: list(v) for k, v in taken.items()}
    out["checks"]["kernels_ok"] = k_ok
    out["device"] = device.as_dict()
    # the reference needs room: drop the pages, the rings and the states
    # (all live in the engine's pools), keep the weights
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
