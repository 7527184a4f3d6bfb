"""Unified observability for the serving stack: metrics + span tracing.

One switch, two surfaces::

    from repro import obs

    reg = obs.enable()                  # install a real MetricsRegistry
    ...serve traffic...
    print(reg.render())                 # counters / gauges / p50-p95-p99
    obs.disable()                       # back to the no-op singleton

* **metrics** (``obs/metrics.py``) — every layer records counters, gauges
  and log-bucketed latency histograms into ``obs.registry()``.  Disabled
  (the default), that accessor returns a no-op singleton, so instrumented
  hot paths cost one dynamic call that does nothing.
* **tracing** (``obs/trace.py``) — span trees threaded through a contextvar
  (``obs.trace.recording``); ``DiscoveryServer(trace=True)`` turns them
  into a per-request flight recorder exportable as Chrome trace-event JSON
  (``server.dump_trace``).  Tracing works with metrics disabled and vice
  versa.

Device time comes from a ``jax.profiler`` trace, never from a host clock:
JAX dispatch is asynchronous, so a host timing around a launch measures its
enqueue.  While a recorder is enabled each of its spans also opens a
``jax.profiler.TraceAnnotation`` of the same name, so in a profile the
spans annotate the device ops on the profiler's own clock.
"""
from __future__ import annotations

import time

from repro.obs import trace  # noqa: F401  (re-export: obs.trace.recording)
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, NULL_REGISTRY, NullRegistry)
from repro.obs.trace import (NULL_RECORDER, Recorder, Span,  # noqa: F401
                             chrome_trace, dump_chrome, recording)

_registry = NULL_REGISTRY


def enable(registry: MetricsRegistry | None = None, *,
           now=time.perf_counter) -> MetricsRegistry:
    """Install (and return) the process-local registry.  A fresh registry
    is created unless one is passed."""
    global _registry
    _registry = registry if registry is not None \
        else MetricsRegistry(now=now)
    return _registry


def disable():
    """Back to the no-op singleton."""
    global _registry
    _registry = NULL_REGISTRY


def enabled() -> bool:
    return _registry is not NULL_REGISTRY


def registry():
    """The active registry — the no-op singleton unless :func:`enable` was
    called.  Instrumented code calls this unconditionally."""
    return _registry

