"""Metrics tracking + phase timers + logging backends.

Equivalent of the reference's observability plumbing (SURVEY.md §5.5):
verl's ``marked_timer`` spans per phase (gen/reward/old_log_prob/adv/
update_actor/update_weight — reference ``stream_ray_trainer.py:356-623``)
and the ``Tracking`` logger multiplexing console/tensorboard/wandb
(``:291-298``). Distribution metrics (p50/p95/p99) ride
:class:`polyrl_tpu.obs.histogram.Histogram`; ``marked_timer`` doubles as a
tracer span + optional jax.profiler annotation (ARCHITECTURE.md
"Observability").

Metric naming convention: ``area/name`` (lowercase, ``_``-separated
segments, ``/``-joined) — enforced over every literal key in the tree by
``tools/check_metric_names.py``.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import time
import warnings
from collections import defaultdict
from typing import Any

from polyrl_tpu import obs
from polyrl_tpu.obs.histogram import Histogram

log = logging.getLogger(__name__)

_collision_warned: set[str] = set()


def _strict_metrics() -> bool:
    # collisions raise under pytest (catch them in CI), warn once at
    # runtime (a long training run must not die on a metric-name clash)
    return "PYTEST_CURRENT_TEST" in os.environ


class MetricsTracker:
    """Accumulates metrics within a step; repeated keys average (losses),
    timing keys sum (phase can run many times per step), gauges take the
    last value, counters sum raw, histograms summarize to percentiles."""

    def __init__(self):
        self._sums = defaultdict(float)
        self._counts = defaultdict(int)
        self._timings = defaultdict(float)
        self._gauges: dict[str, float] = {}
        self._counters = defaultdict(float)
        self._hists: dict[str, Histogram] = {}

    def update(self, metrics: dict[str, Any]) -> None:
        for k, v in metrics.items():
            self._sums[k] += float(v)
            self._counts[k] += 1

    def update_gauge(self, metrics: dict[str, Any]) -> None:
        """Last-value-wins metrics: cumulative counters (control-plane
        restart/resume/retry totals) would be distorted by the averaging
        `update` applies to repeated keys within a step."""
        for k, v in metrics.items():
            self._gauges[k] = float(v)

    def incr(self, name: str, amount: float = 1.0) -> None:
        """Within-step counter emitted raw (not averaged): failure counts,
        drop counts — two failures must read 2.0, not a mean of 1.0."""
        self._counters[name] += amount

    def add_timing(self, name: str, seconds: float) -> None:
        self._timings[name] += seconds

    def observe(self, name: str, value: float) -> None:
        """Distribution sample; ``as_dict`` emits ``<name>/{p50,p95,p99,
        max,mean,count}`` (fixed-bucket log2 histogram, obs/histogram.py)."""
        hist = self._hists.get(name)
        if hist is None:
            hist = self._hists[name] = Histogram()
        hist.observe(value)

    def timings(self) -> dict[str, float]:
        """Snapshot of the phase timings accumulated so far (seconds per
        marked_timer/add_timing key) — the goodput ledger's feed."""
        return dict(self._timings)

    def get(self, key: str, default: float = 0.0) -> float:
        """Current value of one metric by key, across kinds (averaged mean,
        then gauge, then raw counter). For step-end consumers (the goodput
        ledger) that need one already-recorded value without as_dict()."""
        if key in self._sums:
            return self._sums[key] / self._counts[key]
        if key in self._gauges:
            return self._gauges[key]
        if key in self._counters:
            return self._counters[key]
        return default

    def merge(self, other: "MetricsTracker") -> None:
        """Fold another tracker in, kind-by-kind (averaged keys keep their
        sample counts so the merged mean is the pooled mean). Used to land a
        pipeline-thread producer's per-step metrics in the foreground step
        record once that step is consumed (trainer/pipeline.py) — the
        hand-off is by ownership transfer through the queue, so no lock."""
        for k, v in other._sums.items():
            self._sums[k] += v
            self._counts[k] += other._counts[k]
        for k, v in other._timings.items():
            self._timings[k] += v
        self._gauges.update(other._gauges)
        for k, v in other._counters.items():
            self._counters[k] += v
        self.merge_histograms(other._hists)

    def merge_histograms(self, hists: dict[str, Histogram]) -> None:
        """Fold externally collected histograms in (the trainer drains the
        obs process-global registry into each step record)."""
        for name, h in hists.items():
            mine = self._hists.get(name)
            if mine is None:
                self._hists[name] = h
            else:
                mine.merge(h)

    def as_dict(self) -> dict[str, float]:
        out = {k: self._sums[k] / self._counts[k] for k in self._sums}
        groups = {
            "timing": {f"timing_s/{k}": v for k, v in self._timings.items()},
            "counter": dict(self._counters),
            "histogram": {k: v for h_name, h in self._hists.items()
                          for k, v in h.summary(h_name).items()},
            "gauge": self._gauges,
        }
        for kind, metrics in groups.items():
            for k, v in metrics.items():
                if k in out:
                    self._collide(kind, k)
                out[k] = v
        return out

    @staticmethod
    def _collide(kind: str, key: str) -> None:
        """A gauge/timing/histogram key silently overwriting an averaged
        metric is a naming bug: raise under pytest, warn once at runtime."""
        msg = (f"metric key collision: {kind} metric {key!r} overwrites an "
               f"earlier metric in the same step record")
        if _strict_metrics():
            raise ValueError(msg)
        if key not in _collision_warned:
            _collision_warned.add(key)
            warnings.warn(msg, RuntimeWarning, stacklevel=3)


@contextlib.contextmanager
def marked_timer(name: str, tracker: MetricsTracker):
    """Phase timer: always emits ``timing_s/<name>`` (even when the phase
    raises — a phase that fails must not vanish from the step record, the
    failure adds a ``<name>/failed`` count instead), opens a tracer span
    ``trainer/<name>`` and, under the same name, a jax.profiler annotation
    so that a device trace holds the trainer's phases on its own clock."""
    t0 = time.monotonic()
    with obs.span("trainer/" + name), \
            obs.phase_annotation("trainer/" + name):
        try:
            yield
        except BaseException:
            tracker.incr(f"{name}/failed")
            raise
        finally:
            tracker.add_timing(name, time.monotonic() - t0)


class Tracking:
    """Console/JSONL/TensorBoard/W&B multiplexing logger (reference
    Tracking, stream_ray_trainer.py:291-298). Unavailable backends degrade
    to no-ops instead of failing the run, and each backend logs inside its
    own try/except — one backend failing mid-run (full disk, dead wandb
    socket, tb flush error) must not abort a training step. Drops count in
    ``log_errors`` (surfaced as the ``obs/log_errors`` gauge)."""

    def __init__(self, backends: tuple[str, ...] = ("console",),
                 path: str | None = None, project: str = "polyrl_tpu",
                 run_name: str | None = None, config: dict | None = None):
        self.backends = backends
        self.log_errors = 0
        self._file = None
        if path and "jsonl" in backends:
            if os.path.dirname(path):
                os.makedirs(os.path.dirname(path), exist_ok=True)
            self._file = open(path, "a")
        self._tb = None
        self._wandb = None
        if "tensorboard" in backends:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(path or "runs")
            except Exception:
                self._tb = None
        if "wandb" in backends:
            try:
                import wandb

                self._wandb = wandb.init(project=project, name=run_name,
                                         config=config or {})
            except Exception:
                self._wandb = None

    def _guard(self, backend: str, fn) -> None:
        try:
            fn()
        except Exception:  # noqa: BLE001 — a logger must never kill a step
            self.log_errors += 1
            log.exception("%s logging backend failed (drop %d)",
                          backend, self.log_errors)

    def log(self, metrics: dict, step: int) -> None:
        if "console" in self.backends:
            def _console():
                keys = ["perf/step_time_s", "reward/mean", "actor/pg_loss"]
                brief = {k: round(metrics[k], 4) for k in keys if k in metrics}
                print(f"[step {step}] {brief}", flush=True)
            self._guard("console", _console)
        if self._file is not None:
            def _jsonl():
                self._file.write(json.dumps({"step": step, **metrics}) + "\n")
                self._file.flush()
            self._guard("jsonl", _jsonl)
        if self._tb is not None:
            def _tb():
                for k, v in metrics.items():
                    self._tb.add_scalar(k, v, step)
            self._guard("tensorboard", _tb)
        if self._wandb is not None:
            self._guard("wandb",
                        lambda: self._wandb.log(metrics, step=step))

    def close(self) -> None:
        if self._file:
            self._guard("jsonl", self._file.close)
        if self._tb:
            self._guard("tensorboard", self._tb.close)
        if self._wandb:
            self._guard("wandb", self._wandb.finish)
