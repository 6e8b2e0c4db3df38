"""repro.obs: dependency-free runtime telemetry for the SZx stack.

One global :class:`Registry` of counters / gauges / fixed-bucket histograms,
a ``span(name, **attrs)`` context manager with monotonic timing and
nesting, and three exporters (Prometheus text, Chrome ``trace_event`` JSON,
human summary table).  While a ``jax.profiler`` trace is recording, each
span also opens a ``jax.profiler.TraceAnnotation`` of its name, so it lands
in the profiler's trace on the device's clock.  See docs/OBSERVABILITY.md.

Telemetry is OFF by default and costs nearly nothing while off: every
instrumented hot path checks :func:`enabled` -- a module-level flag read --
before allocating or recording anything, and ``span()`` returns a shared
no-op context manager when disabled.  Turn it on with ``SZX_OBS=1`` in the
environment or :func:`enable` at runtime::

    from repro import obs

    obs.enable()
    ... run compression / training / serving ...
    print(obs.summary())
    open("trace.json", "w").write(json.dumps(obs.chrome_trace()))

With ``SZX_OBS`` unset the instrumented code paths are byte-identical in
output and within measurement noise in throughput (gated by the
``telemetry_overhead`` benchmark row).
"""
from __future__ import annotations

import functools
import os
import sys
import threading
import time

from repro.obs import stream_stats
from repro.obs.export import (
    chrome_trace,
    prometheus_text,
    summary,
    write_chrome_trace,
)
from repro.obs.registry import DEFAULT_BUCKETS, Registry

__all__ = [
    "Registry", "REGISTRY", "DEFAULT_BUCKETS",
    "enabled", "enable", "disable",
    "counter", "gauge", "histogram", "span", "traced",
    "prometheus_text", "chrome_trace", "write_chrome_trace", "summary",
    "stream_stats", "reset",
]

REGISTRY = Registry()

_ENABLED = os.environ.get("SZX_OBS", "") not in ("", "0")
_local = threading.local()


def enabled() -> bool:
    """True when telemetry is recording (``SZX_OBS=1`` or :func:`enable`)."""
    return _ENABLED


def enable() -> None:
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def reset() -> None:
    """Clear every metric and span in the global registry."""
    REGISTRY.reset()


def counter(name: str, **labels):
    return REGISTRY.counter(name, **labels)


def gauge(name: str, **labels):
    return REGISTRY.gauge(name, **labels)


def histogram(name: str, *, buckets=DEFAULT_BUCKETS, **labels):
    return REGISTRY.histogram(name, buckets=buckets, **labels)


def _depth() -> int:
    return getattr(_local, "depth", 0)


_annotation_cls = None


def _annotation(name: str, attrs: dict | None):
    """A ``jax.profiler.TraceAnnotation`` for a span, or None where no
    profiler trace records: JAX not imported (obs never imports it), or no
    trace active."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation as cls

        _annotation_cls = cls
    if not cls.is_enabled():
        return None
    return cls(name, **attrs) if attrs else cls(name)


class _Span:
    """Live span: times with ``perf_counter_ns``, records on exit, and
    mirrors itself into the profiler's trace while one records."""

    __slots__ = ("name", "attrs", "_t0", "_ann")

    def __init__(self, name: str, attrs: dict | None):
        self.name = name
        self.attrs = attrs or None

    def __enter__(self):
        _local.depth = _depth() + 1
        self._ann = _annotation(self.name, self.attrs)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        depth = _depth()
        _local.depth = depth - 1
        REGISTRY.record_span(
            self.name, self._t0, dur, threading.get_ident(), depth,
            self.attrs,
        )
        return False

    def __call__(self, fn):
        name, attrs = self.name, self.attrs

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _ENABLED:
                return fn(*args, **kwargs)
            with _Span(name, attrs):
                return fn(*args, **kwargs)

        return wrapper


class _NullSpan:
    """Shared disabled-mode span: no allocation, no clock, no record."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def __call__(self, fn):
        # decorator applied while disabled: stay live under the function's
        # qualname so a later obs.enable() still instruments the calls
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _ENABLED:
                return fn(*args, **kwargs)
            with _Span(fn.__qualname__, None):
                return fn(*args, **kwargs)

        return wrapper


_NULL = _NullSpan()


def span(name: str, **attrs):
    """Timed span context manager / decorator.

    When telemetry is disabled this returns a shared no-op object (the
    enabled flag is checked before any allocation).  When enabled, the span
    records (name, start, duration, thread, nesting depth, attrs) into the
    registry's span log on exit, and, while a ``jax.profiler`` trace is
    recording, spans a ``TraceAnnotation`` of the same name and attrs on the
    calling thread.
    """
    if not _ENABLED:
        return _NULL
    return _Span(name, attrs)


def traced(name: str | None = None, **attrs):
    """Decorator form with a late enabled check on every call, so functions
    decorated at import time respond to :func:`enable` later."""

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _ENABLED:
                return fn(*args, **kwargs)
            with _Span(label, attrs or None):
                return fn(*args, **kwargs)

        return wrapper

    return deco
