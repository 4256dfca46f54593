"""One general traffic generator: it reads a mix's parameters from
``benchmark/traffic/<mix>.json`` and a seed, and returns the requests.

Every seed gets the SAME multiset of sizes, in another order, and other
token ids: sizes are the quantiles of the mix's distributions at fixed
points, and the seed only permutes them. Runs with different seeds then do
the same work, so their spread is the system's and not the draw's.
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmark.lib import harness

def load_mix(name: str) -> dict:
    with open(os.path.join(harness.BENCH_DIR, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def quantile(dist: dict, u: float) -> int:
    """Inverse CDF at u in (0, 1) of a length distribution, as a whole
    number of tokens within [lo, hi]. The distribution is the module
    ``benchmark/dists/<dist>.py`` that ``dist["dist"]`` names."""
    x = harness.load_named("dists", dist["dist"]).quantile(dist, u)
    return int(min(float(dist["hi"]), max(float(dist["lo"]), round(x))))


def size_set(dist: dict, n: int) -> list[int]:
    """The fixed multiset: n quantiles at the midpoints (i + 0.5) / n."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    # SeedSequence takes any non-negative whole number, also over 2**32
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def steady_plan(mix: dict, seed: int, vocab: int):
    """The requests of a ``steady_decode`` mix: as many as the mix's
    ``offered_requests`` says, whatever the engine can hold of them (how
    many it admits is the engine's, and the number a change to it moves).
    Their lengths are that many prompt-length quantiles, the same in the
    same shuffled order for every seed; the seed draws the token ids. (The
    order is work: a request prefilled early decodes while the others
    prefill, so the order sets the contexts the window starts from, and
    which requests an engine short of pages admits. With the order drawn
    from the seed, runs spread by 0.6%; PERF.md section 6.) Returns, in the
    order of submission, {"prompt": [ids], "budget": max_new_tokens,
    "rank": place of this length among the n, shortest first}."""
    n = int(mix["offered_requests"])
    budget = quantile(mix["answer_tokens"], 0.5)
    lengths = size_set(mix["prompt_tokens"], n)
    rng = rng_for(seed)
    # ids in [1, vocab): 0 is the engine's pad token
    return [{"prompt": rng.integers(1, vocab, size=lengths[k]).tolist(),
             "budget": budget, "rank": int(k)}
            for k in rng_for(0, 1).permutation(n)]
