"""``steady_decode`` on the rollout plane: as many long requests as the KV
pool admits, all prefilled during set-up; the window sees decode only and
no request ends in it."""

from __future__ import annotations

import threading
import time

from benchmark.lib import harness, stats, traffic


def run(plane, seconds, trace, counter):
    from polyrl_tpu.manager.client import ManagerClient

    mix, seed = plane.mix, plane.seed
    e = mix["engine"]
    vocab = int(plane.config["config"]["vocab_size"])
    # as many requests as the pool admits at once, the same lengths for
    # every seed (admission reserves a request's whole budget in pages)
    plan = traffic.steady_plan(
        mix, seed, vocab, plane.num_pages() - 1,
        e["page_size"], e["max_slots"])
    now = time.monotonic()
    reqs = [plane.Req(f"long{i}", p["rank"], p["budget"], len(p["prompt"]), now)
            for i, p in enumerate(plan)]
    prompts = {r.rid: p["prompt"] for r, p in zip(reqs, plan)}
    client = ManagerClient(plane.endpoint, timeout_s=1200.0)
    reader = threading.Thread(
        target=plane.stream, args=(client, reqs, prompts),
        name="bench-client", daemon=True)
    reader.start()
    plane.clients = [reader]

    def all_have(n_tokens: int) -> bool:
        return (all(r.n_seen >= n_tokens or r.error for r in reqs)
                or not reader.is_alive())

    harness.wait_until(lambda: all_have(1), 900, "every request prefilled",
                       poll_s=0.1)
    plane.mark("prefilled")
    harness.wait_until(lambda: all_have(int(mix["warm_tokens"])), 300,
                       "every request decoding", poll_s=0.1)
    early = sorted({r.error for r in reqs if r.error})
    if early or not reader.is_alive():
        raise RuntimeError(f"requests failed during set-up: {early[:3]}")
    plane.mark("warm")
    setup_s = plane.phases["warm"]
    closing = {}

    def settle(t1: float) -> None:
        # each request's window ends at its first arrival after t1; then
        # freeze what counts before the server is torn down under the
        # unfinished requests
        harness.wait_until(
            lambda: all(r.arrivals[-1][0] >= t1 for r in reqs)
            or not reader.is_alive(), 60, "tokens after the window")
        closing["failed"] = [r for r in reqs
                             if r.error or r.t_done is not None]
        closing["seen"] = {r.rid: r.n_seen for r in reqs}
        closing["arrivals"] = {r.rid: list(r.arrivals) for r in reqs}

    t0, t1, reduced, checks = plane.window(seconds, trace, counter, settle)
    # the rate is the sum of the requests' rates, each between two of its
    # own arrivals (``stats.edge_rate``): all the tokens and all the time
    # of the window, without the quantum of a dispatch's tokens
    edges = [stats.edge_rate(closing["arrivals"][r.rid], t0, t1)
             for r in reqs]
    rate = sum(e[0] for e in edges)
    tokens = sum(e[1] for e in edges)
    e0, e1 = stats.mean(e[2] for e in edges), stats.mean(e[3] for e in edges)
    per_line = stats.median(n for r in reqs
                            for t, n in closing["arrivals"][r.rid] if t >= t0)
    harness.say(f"{tokens} tokens in {e1 - e0:.3f}s between the requests' "
                f"own arrivals, {per_line:g} tokens a line")
    n_ctx = [r.prompt_len + closing["seen"][r.rid] for r in reqs]
    # the sequences at fixed ranks of the set of lengths (the same shapes
    # for every seed: the reference compiles one program a length), each
    # as far as its first ``correct_positions`` generated tokens
    n_pos = int(mix["correct_positions"])
    by_rank = {r.rank: r for r in reqs}
    picked = [by_rank[round(q * (len(reqs) - 1))]
              for q in mix["correct_length_quantiles"]]
    samples = [(prompts[r.rid], list(r.tokens[:n_pos]),
                list(r.logprobs[:n_pos])) for r in picked]
    return {
        "attempted": len(reqs), "failed": len(closing["failed"]),
        "failures": sorted({r.error or "finished inside the window"
                            for r in closing["failed"]})[:5],
        "end_to_end": {"setup_s": setup_s, "rollout_tok_s": rate},
        "checks": checks, "samples": samples,
        "observed": {
            "window": (e0, e1), "requests": reqs, "trace": reduced,
            "server_info": [s for t, s in plane.info_samples
                            if e0 <= t < e1],
            "tokens_in_window": tokens,
            "kv_tokens_at_end": sum(n_ctx),
        },
    }
