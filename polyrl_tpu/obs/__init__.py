"""Observability subsystem: span tracing + histogram metrics + scraping.

The pieces (ARCHITECTURE.md "Observability"):

- :mod:`polyrl_tpu.obs.trace` — ``Span``/``Tracer`` with thread-local
  context, a bounded ring buffer, and Chrome-trace/Perfetto JSON export.
  Cross-process propagation rides ``X-Trace-Id``/``X-Span-Id`` HTTP headers
  (ManagerClient → C++ manager → rollout server) so one rollout request can
  be followed trainer→manager→engine in a single Perfetto timeline.
- :mod:`polyrl_tpu.obs.histogram` — fixed-bucket log2 ``Histogram``
  (p50/p95/p99/max) plus a process-global registry any component can
  ``observe()`` into; the trainer drains it into each step record.
- :mod:`polyrl_tpu.obs.scrape` — Prometheus text-exposition parser for the
  manager's ``GET /metrics``, merged into step records as ``manager/*``.
- :mod:`polyrl_tpu.obs.goodput` — per-step wall-time attribution ledger
  (``goodput/*`` phase metrics, tokens/chip/s, MFU estimate).
- :mod:`polyrl_tpu.obs.statusz` — the live ``/statusz`` health plane: one
  JSON schema served by both the trainer and the rollout server.
- :mod:`polyrl_tpu.obs.recorder` — anomaly flight recorder: EWMA/z-score
  detection (per-key direction-aware) over the step stream + post-mortem
  bundle dumps.
- :mod:`polyrl_tpu.obs.rlhealth` — training health plane: per-step
  RL-dynamics ledger (advantage/TIS/staleness distributions, GRPO group
  diagnostics) behind the ``training/*`` namespace, the /statusz
  ``training`` section, and ``training.json`` post-mortem bundles.
- :mod:`polyrl_tpu.obs.critical_path` — per-step critical-path
  extraction over the span ring: which chain of spans actually bounded
  the step (``critpath/*`` gauges, ``critical_path.json`` bundles).
- :mod:`polyrl_tpu.obs.timeseries` — fleet time-series rail: bounded
  per-key rings of step snapshots with windowed aggregates + slopes (the
  /statusz ``timeseries`` section, the autoscaling trend input).

Everything here is import-light (no jax at module load) and no-op-cheap
when tracing is disabled, so hot paths can call into it unconditionally.
"""

from __future__ import annotations

from polyrl_tpu.obs.critical_path import (SEGMENTS,  # noqa: F401
                                          CriticalPath,
                                          extract_critical_path)
from polyrl_tpu.obs.goodput import GoodputLedger  # noqa: F401
from polyrl_tpu.obs.histogram import (Histogram, drain_histograms,  # noqa: F401
                                      observe)
from polyrl_tpu.obs.recorder import (AnomalyDetector,  # noqa: F401
                                     FlightRecorder, direction_violates)
from polyrl_tpu.obs.rlhealth import TrainingHealthLedger  # noqa: F401
from polyrl_tpu.obs.scrape import (manager_gauges,  # noqa: F401
                                   manager_gauges_partial,
                                   parse_prometheus_text,
                                   parse_prometheus_text_partial)
from polyrl_tpu.obs.statusz import StatuszServer, build_snapshot  # noqa: F401
from polyrl_tpu.obs.timeseries import (TimeSeriesStore,  # noqa: F401
                                       least_squares_slope)
from polyrl_tpu.obs.trace import Tracer, get_tracer  # noqa: F401

def configure(trace: bool | None = None, max_spans: int | None = None,
              out_dir: str | None = None,
              reset: bool = False) -> Tracer:
    """Configure the process-global tracer. ``None`` leaves a setting
    unchanged; ``reset`` clears the span ring buffer and the histogram
    registry (test isolation / fresh runs)."""
    tracer = get_tracer()
    if trace is not None:
        tracer.enabled = trace
    if max_spans is not None:
        tracer.set_capacity(max_spans)
    if out_dir is not None:
        tracer.out_dir = out_dir or None
    if reset:
        tracer.clear()
        drain_histograms()
    return tracer


def span(name: str, **attrs):
    """Open a span on the global tracer (no-op when tracing is disabled)."""
    return get_tracer().span(name, **attrs)


def trace_headers() -> dict[str, str]:
    """HTTP headers carrying the current trace context ({} when none)."""
    return get_tracer().headers()


def named_program(name: str, fn):
    """``fn`` under the function name ``name``. ``jax.jit`` names a program
    after its function (``jit_<name>``, the name on a device trace's
    ``XLA Modules`` line); a ``partial`` or a lambda has none of its own,
    and two sites that jit one function would share one."""

    def call(*args, **kwargs):
        return fn(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return call


def phase_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation``: the phase as a host span of a
    device trace, on the device's clock. No knob selects it: outside a
    profiler session a TraceMe is a flag test. jax is imported here and
    not with the package, which stays import-light."""
    import jax

    return jax.profiler.TraceAnnotation(name)
