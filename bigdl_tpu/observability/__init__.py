"""Unified telemetry for bigdl_tpu (ISSUE 1 tentpole).

One process-wide surface tying training throughput, serving latency and
LLM decode performance together:

- :mod:`~bigdl_tpu.observability.metrics` — thread-safe Counter / Gauge /
  Histogram registry + Prometheus text exposition (``render()``; served
  by the HTTP front-ends at ``GET /metrics``);
- :mod:`~bigdl_tpu.observability.tracing` — ``with span("train/step",
  step=i):`` nestable trace spans → ring buffer → Chrome-trace/Perfetto
  JSON (``export_chrome_trace``), with optional passthrough to
  ``jax.profiler`` annotations;
- instrumentation hooks live in the hot paths themselves (optimizer
  loop, serving front-ends, LLM engine, collectives) and all write here.

Naming convention: every metric is prefixed ``bigdl_`` (see
docs/OBSERVABILITY.md for the catalog). Overhead contract: everything is
host-side python over clocks the loops already read; the
``bigdl.observability.enabled`` config key (env
``BIGDL_TPU_OBSERVABILITY_ENABLED``) or :func:`disable` turns every
mutator and ``span`` into a no-op that records nothing.
"""

from __future__ import annotations

import time as _time

from bigdl_tpu.observability import _state
from bigdl_tpu.observability.metrics import (
    CONTENT_TYPE, Counter, DEFAULT_BUCKETS, FAST_BUCKETS, Gauge,
    Histogram, MetricRegistry, SUMMARY_QUANTILES, Sketch,
    parse_prometheus, render_prometheus)
from bigdl_tpu.observability.sketch import QuantileSketch
from bigdl_tpu.observability import tracing
from bigdl_tpu.observability.tracing import (
    EXEMPLARS, TRACE, TraceBuffer, add_complete, assemble_trace,
    configure, export_chrome_trace, span)
from bigdl_tpu.observability import request_context
from bigdl_tpu.observability.request_context import (
    PARENT_HEADER, TRACE_HEADER, TraceContext)
from bigdl_tpu.observability import compile_recorder
from bigdl_tpu.observability.compile_recorder import (
    compile_stats, compiled)
from bigdl_tpu.observability import flight
from bigdl_tpu.observability import utilization

#: The process-global registry every built-in hook writes to.
REGISTRY = MetricRegistry()

#: Epoch seconds this module (≈ the process) came up — exported as the
#: standard ``process_start_time_seconds`` so ``time() - start`` uptime
#: panels work against our /metrics unchanged.
PROCESS_START_TIME = _time.time()


def _ensure_standard_series():
    """Declare the self-describing series every Prometheus scrape should
    carry (ISSUE 3 satellite): ``bigdl_build_info`` (value 1, identity
    as labels — the stock *_build_info idiom) and
    ``process_start_time_seconds``. Called at render time, gated on the
    switch, so a disabled process mints zero series."""
    if not _state.enabled:
        return
    try:
        from bigdl_tpu.version import __version__ as version
    except Exception:
        version = "unknown"
    import jax
    from jax._src import xla_bridge
    # a scrape must not take the chip: a router or launcher process
    # that never ran JAX reports no backend instead of initialising one
    backend = (jax.default_backend()
               if xla_bridge.backends_are_initialized() else "none")
    jax_version = jax.__version__
    g = REGISTRY.gauge(
        "bigdl_build_info",
        "Constant 1; the build identity lives in the labels",
        labelnames=("version", "jax_version", "backend"))
    # one identity: a scrape before the backend came up said "none"
    g.only(version=version, jax_version=jax_version,
           backend=backend).set(1)
    REGISTRY.gauge(
        "process_start_time_seconds",
        "Unix epoch seconds this process started").set(
        PROCESS_START_TIME)


def enabled() -> bool:
    return _state.enabled


def enable():
    _state.enabled = True


def disable():
    """No-op mode: every inc/set/observe/span becomes a cheap early
    return; nothing is recorded anywhere."""
    _state.enabled = False


def counter(name: str, help: str = "", labelnames=()):
    return REGISTRY.counter(name, help, labelnames)


def gauge(name: str, help: str = "", labelnames=()):
    return REGISTRY.gauge(name, help, labelnames)


def histogram(name: str, help: str = "", labelnames=(),
              buckets=DEFAULT_BUCKETS):
    return REGISTRY.histogram(name, help, labelnames, buckets)


def sketch(name: str, help: str = "", labelnames=(), alpha=None):
    """Mergeable quantile sketch (ISSUE 12): observed like a histogram,
    rendered as summary quantiles, merged across workers by the
    federation layer."""
    return REGISTRY.sketch(name, help, labelnames, alpha)


def render() -> str:
    """Prometheus text exposition of the global registry."""
    _ensure_standard_series()
    return render_prometheus(REGISTRY)


def reset():
    """Clear the global registry, the trace ring, the exemplar store
    AND the compile ledger. Test isolation only: instruments held by
    live modules detach from the registry."""
    REGISTRY.clear()
    TRACE.clear()
    EXEMPLARS.clear()
    compile_recorder.reset()
    flight.reset()
    utilization.reset()
    from bigdl_tpu.observability import alerts, timeseries
    alerts.reset()
    timeseries.reset()


__all__ = [
    "CONTENT_TYPE", "Counter", "EXEMPLARS", "Gauge", "Histogram",
    "MetricRegistry", "PARENT_HEADER", "PROCESS_START_TIME",
    "QuantileSketch", "REGISTRY", "SUMMARY_QUANTILES", "Sketch",
    "TRACE", "TRACE_HEADER", "TraceBuffer", "TraceContext",
    "DEFAULT_BUCKETS", "FAST_BUCKETS", "add_complete", "assemble_trace",
    "compile_recorder", "compile_stats", "compiled", "configure",
    "counter", "disable", "enable", "enabled", "export_chrome_trace",
    "flight", "gauge", "histogram", "parse_prometheus", "render",
    "render_prometheus", "request_context", "reset", "sketch", "span",
    "tracing", "utilization",
]
