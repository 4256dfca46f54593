"""``steady_decode`` on the rollout plane: the mix's ``offered_requests``
long requests, in one batch or (``offer_spacing_seconds``) one after
another; set-up lasts until every one the engine took is prefilled and
decoding and it takes no more; the window sees decode only, and no request
starts or ends in it. How many of the offered
requests the engine holds at once is the engine's own number: it is
printed with the run (``offered``, ``admitted``, ``queued``) and pinned
nowhere."""

from __future__ import annotations

import threading
import time

from benchmark.lib import harness, stats, traffic

INFO_POLL_S = 0.5   # set-up asks the server as often as the window does


class Admission:
    """Whether the engine is still taking requests in, from what the client
    has seen (how many requests have sent a token) and what ``GET
    /get_server_info`` says (``num_running_reqs``: slots decoding;
    ``num_queued_reqs``: requests waiting for pages or a slot). A request
    being prefilled is in neither count, and one on its way through the
    manager is in none yet."""

    def __init__(self, offered: int, settle_s: float):
        self.offered, self.settle_s = int(offered), float(settle_s)
        self._held = None   # ((running, queued), since when)

    def settled(self, now: float, started: int, info: dict | None) -> bool:
        """True once every offered request has started, or once each one
        either decodes or waits in the engine's queue (so no prefill is
        outstanding), as many decode as have sent a token, and those two
        counts have not moved for ``settle_s``."""
        if started >= self.offered:
            return True
        state = None if info is None else (
            int(info.get("num_running_reqs", -1)),
            int(info.get("num_queued_reqs", -1)))
        if (state is None or started == 0 or state[0] != started
                or state[0] + state[1] != self.offered):
            self._held = None
            return False
        if self._held is None or self._held[0] != state:
            self._held = (state, now)
        return now - self._held[1] >= self.settle_s


def pick_by_length(reqs, seen: dict, n_pos: int, quantiles) -> list:
    """The requests at the given quantiles of length (0 the shortest, 1
    the longest) among those that have ``n_pos`` generated tokens."""
    ranked = sorted((r for r in reqs if seen[r.rid] >= n_pos),
                    key=lambda r: r.rank)
    return [ranked[round(q * (len(ranked) - 1))] for q in quantiles] \
        if ranked else []


def run(plane, seconds, trace, counter):
    from polyrl_tpu.manager.client import ManagerClient

    mix, seed = plane.mix, plane.seed
    vocab = int(plane.config["config"]["vocab_size"])
    # what the client offers is the mix's; the same lengths in the same
    # order for every seed, so the engine admits the same ones every run
    plan = traffic.steady_plan(mix, seed, vocab)
    now = time.monotonic()
    reqs = [plane.Req(f"long{i}", p["rank"], p["budget"], len(p["prompt"]), now)
            for i, p in enumerate(plan)]
    prompts = {r.rid: p["prompt"] for r, p in zip(reqs, plan)}
    client = ManagerClient(plane.endpoint, timeout_s=1200.0)
    # one batch; or, where the mix spaces its offers, a batch a request in
    # the plan's order. (The manager hands a batch's requests to the engine
    # in parallel, so the engine sees them in an order that a race decides;
    # that is nothing while it admits them all, and decides WHICH it admits
    # where it cannot.)
    spacing = float(mix.get("offer_spacing_seconds", 0.0))
    readers = [threading.Thread(target=plane.stream,
                                args=(client, batch, prompts),
                                name=f"bench-client-{i}", daemon=True)
               for i, batch in enumerate([[r] for r in reqs] if spacing > 0
                                         else [reqs])]
    plane.clients = readers
    for reader in readers:
        reader.start()
        time.sleep(spacing)
    admission = Admission(len(reqs), mix.get("settle_seconds", 3.0))
    polled = {"at": 0.0, "info": None}

    def started() -> list:
        return [r for r in reqs if r.n_seen > 0]

    def hung_up() -> bool:
        return not all(t.is_alive() for t in readers)

    def broken() -> bool:
        return any(r.error for r in reqs) or hung_up()

    def taken_in() -> bool:
        now = time.monotonic()
        n = len(started())
        # the server is asked only while some request has not started
        if n < len(reqs) and now - polled["at"] >= INFO_POLL_S:
            polled["at"], polled["info"] = now, plane.server_info()
        return admission.settled(now, n, polled["info"]) or broken()

    harness.wait_until(taken_in, 900, "every request prefilled or queued",
                       poll_s=0.1)
    plane.mark("prefilled")
    warm = int(mix["warm_tokens"])
    harness.wait_until(
        lambda: all(r.n_seen >= warm for r in started()) or broken(),
        300, "every admitted request decoding", poll_s=0.1)
    early = sorted({r.error for r in reqs if r.error})
    if early or hung_up():
        raise RuntimeError(f"requests failed during set-up: {early[:3]}")
    plane.mark("warm")
    setup_s = plane.phases["warm"]
    admitted = started()
    took = {r.rid for r in admitted}
    harness.say(f"offered {len(reqs)}, admitted {len(admitted)}, "
                f"queued {len(reqs) - len(admitted)}")
    closing = {}

    def settle(t1: float) -> None:
        # each request's window ends at its first arrival after t1; then
        # freeze what counts before the server is torn down under the
        # unfinished requests
        harness.wait_until(
            lambda: all(r.arrivals[-1][0] >= t1 or r.t_done is not None
                        or r.error for r in admitted)
            or hung_up(), 60, "tokens after the window")
        # the window is decode only: a request that ended, failed or was
        # admitted inside it has changed the work
        closing["failed"] = {
            r.rid: r.error or ("finished inside the window" if r.rid in took
                               else "admitted inside the window")
            for r in reqs if r.error or r.t_done is not None
            or (r.n_seen > 0 and r.rid not in took)}
        closing["seen"] = {r.rid: r.n_seen for r in reqs}
        closing["arrivals"] = {r.rid: list(r.arrivals) for r in admitted}

    t0, t1, reduced, checks = plane.window(seconds, trace, counter, settle)
    # the rate is the sum of the requests' rates, each between two of its
    # own arrivals (``stats.edge_rate``): all the tokens and all the time
    # of the window, without the quantum of a dispatch's tokens
    # (a request that ended in the window has failed the run and has no
    # second edge)
    edges = [stats.edge_rate(closing["arrivals"][r.rid], t0, t1)
             for r in admitted if r.rid not in closing["failed"]]
    rate = sum(e[0] for e in edges)
    tokens = sum(e[1] for e in edges)
    e0, e1 = stats.mean(e[2] for e in edges), stats.mean(e[3] for e in edges)
    per_line = stats.median(n for r in admitted
                            for t, n in closing["arrivals"][r.rid] if t >= t0)
    harness.say(f"{tokens} tokens in {e1 - e0:.3f}s between the requests' "
                f"own arrivals, {per_line:g} tokens a line")
    info = [s for t, s in plane.info_samples if e0 <= t < e1]
    # queued: what the engine says is waiting, in the window's last sample
    # (a window too short for one: what the client never saw start)
    checks.update(
        offered=len(reqs), admitted=len(admitted),
        admitted_ranks=sorted(r.rank for r in admitted),
        queued=int(info[-1]["num_queued_reqs"]) if info
        else len(reqs) - len(admitted))
    # the admitted sequences at fixed ranks of their lengths (the same
    # shapes for every seed: the reference compiles one program a length),
    # each as far as its first ``correct_positions`` generated tokens
    n_pos = int(mix["correct_positions"])
    picked = pick_by_length(admitted, closing["seen"], n_pos,
                            mix["correct_length_quantiles"])
    samples = [(prompts[r.rid], list(r.tokens[:n_pos]),
                list(r.logprobs[:n_pos])) for r in picked]
    e = mix["engine"]
    return {
        "attempted": len(reqs), "failed": len(closing["failed"]),
        "failures": sorted(set(closing["failed"].values()))[:5],
        "end_to_end": {"setup_s": setup_s, "rollout_tok_s": rate},
        "checks": checks, "samples": samples,
        "observed": {
            "window": (e0, e1), "requests": reqs, "trace": reduced,
            "server_info": info,
            "tokens_in_window": tokens,
            "kv_tokens_at_end": sum(r.prompt_len + closing["seen"][r.rid]
                                    for r in admitted),
            "kv_pool_tokens": (plane.num_pages() - 1) * e["page_size"],
        },
    }
