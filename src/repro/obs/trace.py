"""Profiling trace hooks: named phases for xprof, free when off.

``span(name)`` wraps a region in BOTH profiler primitives:

* ``jax.named_scope(name)`` — applies at *trace* time, so the ops staged
  inside the region carry the scope in their HLO metadata and an xprof /
  TensorBoard trace attributes **device** time to the phase.  This is how
  the kernel dispatch boundary (``kernels/ops.py``), the cp carry exchange
  (``distributed/context.py``), and the engine step show up as named rows.
* ``jax.profiler.TraceAnnotation(name)`` — applies at *run* time, so
  host-side phases (engine scheduling, sampling) show on the host timeline.

Stalls that no caller marks get host spans of their own while tracing is
on: ``host.gc`` for each garbage collection (``gc.callbacks``), and
``jax.trace`` / ``jax.compile`` for each trace and each backend compile
(or compile-cache load) of a jitted function (``jax.monitoring``: JAX
reports the start of each phase as a scalar and its end as a duration).

Gating: the ``REPRO_TRACE`` env var, read **once at import** — when off
(default), :func:`span` returns a shared null context manager: one function
call + one global load, no objects allocated, nothing staged into the
compiled program (a compile-time no-op, not a runtime branch); no GC
callback is installed and the ``jax.monitoring`` listeners return at once.
Tests flip it with :func:`set_enabled`.

Enable with ``REPRO_TRACE=1`` and capture via
``jax.profiler.start_trace(logdir)`` (or ``with jax.profiler.trace(...)``),
then read the trace in xprof/TensorBoard.
"""

from __future__ import annotations

import gc
import os
import threading

TRACE_ENV = "REPRO_TRACE"

#: jax.monitoring event -> the host span that covers it
JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}


def _env_enabled() -> bool:
    return os.environ.get(TRACE_ENV, "").strip().lower() not in (
        "", "0", "false", "off", "no")


_ENABLED = False


def trace_enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> bool:
    """Force the gate (tests); returns the previous value."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(on)
    if _ENABLED and _gc_span not in gc.callbacks:
        _watch_jax()
        gc.callbacks.append(_gc_span)
    elif not _ENABLED and _gc_span in gc.callbacks:
        gc.callbacks.remove(_gc_span)
    return prev


class _NullSpan:
    """Reusable do-nothing context manager (the off path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    """Live span: named_scope (trace time) + TraceAnnotation (run time)."""

    __slots__ = ("name", "_scope", "_annot")

    def __init__(self, name: str):
        self.name = name
        self._scope = None
        self._annot = None

    def __enter__(self):
        import jax

        # The annotation is outermost, so the host timeline counts the
        # scope's own entry and exit inside the span.
        self._annot = jax.profiler.TraceAnnotation(self.name)
        self._annot.__enter__()
        self._scope = jax.named_scope(self.name)
        self._scope.__enter__()
        return self

    def __exit__(self, *exc):
        self._scope.__exit__(*exc)
        self._annot.__exit__(*exc)
        return False


def span(name: str):
    """Context manager naming one phase; the shared no-op when tracing is
    off.  Usage: ``with span("engine.step"): ...``"""
    if not _ENABLED:
        return _NULL
    return _Span(name)


# ---------------------------------------------------------------------------
# Stall spans: garbage collection and JAX's trace / compile phases
# ---------------------------------------------------------------------------

_OPEN = threading.local()    # per thread: stack of (key, open annotation)
_WATCHING_JAX = False


def _open(key: str, name: str) -> None:
    import jax

    annot = jax.profiler.TraceAnnotation(name)
    annot.__enter__()
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    stack.append((key, annot))


def _close(key: str) -> None:
    stack = getattr(_OPEN, "stack", None)
    if stack and stack[-1][0] == key:
        stack.pop()[1].__exit__(None, None, None)


def _gc_span(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook, installed only while tracing is on."""
    if phase == "start":
        _open("gc", "host.gc")
    else:
        _close("gc")


def _phase_start(event: str, value, **kwargs) -> None:
    name = JAX_PHASES.get(event)
    if name is not None and _ENABLED:
        _open(event, name)


def _phase_end(event: str, duration_secs: float, **kwargs) -> None:
    _close(event)


def _watch_jax() -> None:
    """Register the phase listeners once; while tracing is off they
    return after a lookup."""
    global _WATCHING_JAX
    if _WATCHING_JAX:
        return
    import jax.monitoring

    jax.monitoring.register_scalar_listener(_phase_start)
    jax.monitoring.register_event_duration_secs_listener(_phase_end)
    _WATCHING_JAX = True


set_enabled(_env_enabled())
