"""Cells of the rollout plane: client -> C++ manager -> ``RolloutServer``
-> ``CBEngine``, all through the entry points a deployment uses
(``spawn_rollout_manager``, ``create_server``, ``ManagerClient``). One
process holds the chip; the manager is its only child and never touches
JAX.

The traffic pattern is the module ``benchmark/patterns/<pattern>.py`` that
the mix names; its ``run(plane, seconds, trace, counter)`` drives the
plane's ``stream`` and ``window`` and returns the run's result.
"""

from __future__ import annotations

import gc
import json
import math
import os
import threading
import time
import urllib.request

import numpy as np

from benchmark.lib import costs, harness

# what each kernel dispatcher must have taken on a TPU (the jnp oracle
# paths are correct and slow: a run on them is not a measurement)
KERNELS_ON_TPU = {"paged_attention": ("lib",), "kv_write": ("pallas",),
                  "grouped": ("pallas",), "train_attention": ("flash",)}
# create_server closes over its seed, so every new seed would compile the
# weight-init program anew (24 s at these sizes, PERF.md section 6). The
# server is built with this one seed and the weights are then drawn from
# --seed by a program that takes the key as an argument.
SERVER_SEED = 0


class Req:
    __slots__ = ("rid", "rank", "budget", "prompt_len", "t_submit",
                 "t_first", "t_done", "n_seen", "ok", "error", "tokens",
                 "logprobs", "arrivals")

    def __init__(self, rid, rank, budget, prompt_len, t_submit):
        self.rid, self.rank, self.budget = rid, rank, budget
        self.prompt_len, self.t_submit = prompt_len, t_submit
        self.t_first = self.t_done = None
        self.n_seen = 0
        self.ok, self.error = False, ""
        self.tokens, self.logprobs = [], []
        self.arrivals: list[tuple[float, int]] = []   # (time, tokens)


class RolloutPlane:
    Req = Req

    def __init__(self, cell, config, mix, device, seed, work, t_proc0):
        self.cell, self.config, self.mix = cell, config, mix
        self.device, self.seed, self.work = device, seed, work
        self.t_proc0 = t_proc0
        self.proc = self.srv = self.eng = None
        self.clients: list[threading.Thread] = []
        self.info_samples: list[tuple[float, dict]] = []
        self.phases: dict[str, float] = {}
        self._poll_stop = threading.Event()

    def num_pages(self) -> int:
        """Pages of the KV pool: the configuration's pool in bytes over a
        page's bytes, plus the engine's null page."""
        per_page = (costs.kv_bytes_per_token(self.config["config"])
                    * self.mix["engine"]["page_size"])
        return int(self.config["serve"]["kv_pool_bytes"] // per_page) + 1

    def mark(self, phase: str) -> None:
        """Seconds since the process started, at the end of a set-up
        phase: printed with the run so that a shift in ``setup_s`` can be
        laid to a phase."""
        self.phases[phase] = time.monotonic() - self.t_proc0
        harness.say(f"{phase} at {self.phases[phase]:.1f}s")

    # -- bring-up and tear-down ------------------------------------------

    def start(self) -> None:
        import jax

        from polyrl_tpu.manager.client import (ManagerClient, build_manager,
                                               spawn_rollout_manager)
        from polyrl_tpu.models import decoder
        from polyrl_tpu.ops import dispatch
        from polyrl_tpu.rollout.serve import create_server

        dispatch.reset()
        self.mark("jax_up")
        build_manager()     # a no-op when the binary is fresh
        self.proc, port = spawn_rollout_manager(
            "127.0.0.1:0", extra_args=list(self.mix["manager_args"]),
            log_path=os.path.join(self.work, "manager.log"))
        harness.CHILDREN.append(self.proc)
        self.endpoint = f"127.0.0.1:{port}"
        self.mgr = ManagerClient(self.endpoint)
        self.mgr.wait_healthy()
        self.mark("manager_up")
        e = self.mix["engine"]
        self.srv = create_server(
            self.config["preset"], manager_endpoint=self.endpoint,
            host="127.0.0.1", dtype=self.config["dtype"],
            seed=SERVER_SEED, backend="cb",
            max_slots=e["max_slots"], page_size=e["page_size"],
            max_seq_len=e["max_seq_len"],
            num_pages=self.num_pages(),
            steps_per_dispatch=e["steps_per_dispatch"],
            prompt_buckets=tuple(e["prompt_buckets"]),
            prefill_chunk=e["prefill_chunk"],
            model_overrides=harness.model_overrides(self.config))
        self.eng = eng = self.srv.engine
        jax.block_until_ready(eng.params)
        self.mark("server_up")
        # the weights of this run, from --seed, in one jitted call and in
        # the type they are served in; the server's first weights are
        # donated so that the two trees never stand side by side, and the
        # new ones go in as a trainer's push does
        draw = jax.jit(lambda old, key: decoder.init_params(key, eng.cfg),
                       donate_argnums=0, keep_unused=True)
        fresh = draw(eng.params,
                     jax.random.PRNGKey(harness.fold_seed(self.seed)))
        eng.update_weights(jax.block_until_ready(fresh),
                           version=eng.weight_version)
        self.mark("weights_drawn")
        harness.wait_until(
            lambda: any(i["active"] for i in
                        self.mgr.get_instances_status()["instances"]),
            120, "instance active in the manager")

    def stop(self) -> None:
        self._poll_stop.set()
        if self.srv is not None:
            self.srv.stop()
        if self.proc is not None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5.0)
            except Exception:  # noqa: BLE001 — it ignored SIGTERM
                self.proc.kill()
                self.proc.wait(timeout=5.0)
            self.proc = None
        for t in self.clients:
            t.join(timeout=30.0)

    def server_info(self) -> dict | None:
        """``GET /get_server_info`` now; None when the server did not
        answer."""
        url = f"http://{self.srv.endpoint}/get_server_info"
        try:
            with urllib.request.urlopen(url, timeout=5.0) as r:
                return json.loads(r.read())
        except OSError:
            return None

    def sample_server_info(self) -> None:
        info = self.server_info()
        if info is not None:
            self.info_samples.append((time.monotonic(), info))

    def poll_server_info(self) -> None:
        """Sample ``GET /get_server_info`` twice a second until stopped."""
        while not self._poll_stop.wait(0.5):
            self.sample_server_info()

    # -- one streaming batch ---------------------------------------------

    def stream(self, client, reqs: list[Req], prompts: dict) -> None:
        """POST one batch and fill ``reqs`` from its NDJSON stream."""
        from polyrl_tpu.manager.client import GenerateProgress

        by_rid = {r.rid: r for r in reqs}
        body = [{"rid": r.rid, "input_ids": prompts[r.rid],
                 "sampling_params": {
                     "temperature": self.mix["temperature"], "top_p": 1.0,
                     "top_k": 0, "max_new_tokens": r.budget,
                     "stop_token_ids": []}} for r in reqs]
        try:
            for item in client.batch_generate_stream(body):
                now = time.monotonic()
                r = by_rid.get(item.rid)
                if r is None:
                    continue
                if isinstance(item, GenerateProgress):
                    n = len(item.token_ids)
                    if n and r.t_first is None:
                        r.t_first = now
                    r.n_seen += n
                    r.tokens += item.token_ids
                    r.logprobs += item.logprobs
                    if n:
                        r.arrivals.append((now, n))
                    continue
                r.t_done = now
                if r.t_first is None:
                    r.t_first = now
                extra = len(item.output_token_ids) - r.n_seen
                if extra > 0:
                    r.arrivals.append((now, extra))
                r.tokens = item.output_token_ids
                r.logprobs = item.output_token_logprobs
                r.ok = (item.success
                        and len(item.output_token_ids) == r.budget
                        and all(math.isfinite(x)
                                for x in item.output_token_logprobs))
                if not r.ok:
                    r.error = item.error or item.finish_reason or "short"
        except Exception as exc:  # noqa: BLE001 — recorded, run goes on
            for r in reqs:
                if r.t_done is None:
                    r.error = f"{type(exc).__name__}: {exc}"

    # -- the measured window ---------------------------------------------

    def window(self, seconds, trace, counter, settle):
        """Run the measured window from now: returns (t0, t1, reduced trace
        or None, checks). ``settle`` is called once, after t1, and returns
        when the pattern has seen what it counts."""
        mix = self.mix
        setup = counter.snapshot()
        self.checks = {"setup_phases_s": dict(self.phases),
                       "programs_readied_in_setup": setup[1],
                       "ready_seconds_in_setup": setup[2],
                       "cache_misses_in_setup": setup[3]}
        poller = threading.Thread(target=self.poll_server_info,
                                  name="bench-poll", daemon=True)
        # the first request of a process pays for what the handler and
        # the client load lazily: that belongs to set-up
        self.sample_server_info()
        t0 = time.monotonic()
        poller.start()
        trace_path = None
        if trace:
            import jax

            prof = harness.ProfilerWindow(self.work + "/trace")
            prof.start()
            with jax.profiler.TraceAnnotation("bench/window"):
                time.sleep(min(float(mix["trace_seconds"]), seconds))
            prof.stop()
            trace_path = prof.xplane_path()
        rest = t0 + seconds - time.monotonic()
        if rest > 0:
            time.sleep(rest)
        t1 = time.monotonic()
        after = counter.snapshot()
        settle(t1)
        self._poll_stop.set()
        poller.join(timeout=10.0)
        self.checks.update(
            traced_in_window=after[0] - setup[0],
            compiled_in_window=after[1] - setup[1],
            compile_seconds_in_window=after[2] - setup[2])
        reduced = None
        # a CPU rehearsal's trace has no device plane to reduce
        if trace and not self.device.rehearse:
            from benchmark.lib import tracered

            reduced = tracered.reduce(tracered.load(trace_path))
        return t0, t1, reduced, self.checks


def check_logprobs(reference, params, config: dict, samples) -> dict:
    """System log-probabilities against the float32 reference for
    ``samples`` = [(prompt ids, generated ids, system logprobs)], held to
    the limits the configuration's file gives under ``correct``."""
    limits = config["correct"]
    worst, total, count = 0.0, 0.0, 0
    for prompt, toks, lps in samples:
        n = min(len(toks), len(lps))
        if n == 0:
            continue
        ref, _ent = reference.score(params, config["config"],
                                    list(prompt) + list(toks[:n]), n)
        diff = np.abs(ref - np.asarray(lps[:n], np.float32))
        worst = max(worst, float(diff.max()))
        total += float(diff.sum())
        count += n
    mean = total / max(count, 1)
    return {"sequences": len(samples), "positions": count,
            "logprob_mean_abs_diff": mean, "logprob_max_abs_diff": worst,
            "ok": bool(count > 0
                       and mean <= limits["logprob_mean_abs_diff_max"]
                       and worst <= limits["logprob_max_abs_diff_max"])}


def kernels_ok(device) -> tuple[bool, dict]:
    from polyrl_tpu.ops import dispatch

    taken = dispatch.taken()
    if device.rehearse:
        return True, taken
    return all(taken[k] == KERNELS_ON_TPU.get(k) for k in taken), taken


def run(cell, config, mix, device, seed, seconds, trace, counter, t_proc0):
    work = harness.work_dir(cell["name"])
    plane = RolloutPlane(cell, config, mix, device, seed, work, t_proc0)
    pattern = harness.load_named("patterns", mix["pattern"])
    try:
        plane.start()
        out = pattern.run(plane, seconds, trace, counter)
    finally:
        plane.stop()
    eng = plane.eng
    out["checks"]["engine_recoveries"] = int(eng.recoveries)
    k_ok, taken = kernels_ok(device)
    out["checks"]["kernels"] = {k: list(v) for k, v in taken.items()}
    out["checks"]["kernels_ok"] = k_ok
    out["device"] = device.as_dict()
    # the reference needs room: drop the KV pools, keep the weights
    params = eng.params
    samples = out.pop("samples")
    plane.srv = plane.eng = None
    eng._pools = None
    eng._dev_state = None
    del eng
    gc.collect()
    out["checks"]["reference"] = check_logprobs(
        harness.load_named("references", config["reference"]), params,
        config, samples)
    return out
