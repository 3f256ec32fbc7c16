"""Host-side span tracing with optional mirroring into JAX device traces.

``with obs.trace.span("decode.verify_round"): ...`` times a region of
host code and records the wall time into a log2 histogram named
``span.<path>.seconds`` — where ``<path>`` is the slash-joined nesting
path (``service.step/model.decode_step``), built from a thread-local
span stack, so one histogram exists per distinct call *position*, not
just per label.

A span opened with no registry records into the registry of the
innermost open span on its thread, else into the process default
(``current_registry()``; counters of code that takes no registry use
the same rule). So the model's and the coder's spans under a service
step land in the service's registry, next to the step that called them.

When JAX is in the process, every span also enters a
``jax.profiler.TraceAnnotation`` with the same label, so capturing a
device profile (XProf/Perfetto) shows the host spans interleaved with
the XLA ops they bracket — one vocabulary across host and device
timelines. ``TraceAnnotation`` is a no-op-cheap TraceMe when no profiler
session is active; mirroring can still be forced off with
``set_jax_mirror(False)``. JAX is never imported by this module — the
mirror activates only if something else already imported jax.

Spans follow the registry switch: ``span()`` returns a shared null
context manager when the target registry is disabled, so a disabled
registry pays one attribute check per span site.

When a ``obs.timeline.TimelineRecorder`` is installed, every closing
span additionally appends one event (name, path, start, duration,
thread, tags) to the recorder's ring buffer — the raw material for
Chrome-trace export and per-job phase attribution (DESIGN.md §13).
With no recorder installed that costs one module-attribute check.
"""
from __future__ import annotations

import sys
import threading
import time
from . import metrics as _metrics
from . import timeline as _timeline

_tls = threading.local()
_enabled = True          # module master switch (obs.trace.enable(False))
_jax_mirror = True       # mirror into jax.profiler.TraceAnnotation
_TraceAnnotation = None  # resolved lazily; False = unavailable
_compiles = 0            # backend compiles + persistent-cache loads seen
_compiles_lock = threading.Lock()   # compiles may finish on any thread
_listening = False       # the jax.monitoring listener is registered


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = bool(on)


def set_jax_mirror(on: bool) -> None:
    global _jax_mirror
    _jax_mirror = bool(on)


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current() -> str:
    """Slash-joined path of the innermost open span ('' outside spans)."""
    s = _stack()
    return s[-1].path if s else ""


def current_registry():
    """The registry of the innermost open span on this thread, else the
    process default: where a span or counter with no explicit registry
    records."""
    s = _stack()
    return s[-1].reg if s else _metrics.registry()


def _on_compile(event, duration, **_):
    global _compiles
    if event == "/jax/core/compile/backend_compile_duration":
        with _compiles_lock:
            _compiles += 1


def compile_count() -> int:
    """Backend compiles in this process, persistent-cache loads included
    (JAX times both as one backend compile), counted by one
    ``jax.monitoring`` listener registered at the first call made after
    jax is imported; 0 before that."""
    global _listening
    if not _listening and "jax" in sys.modules:
        with _compiles_lock:
            if not _listening:
                from jax import monitoring
                monitoring.register_event_duration_secs_listener(_on_compile)
                _listening = True
    return _compiles


def _resolve_jax():
    """Find jax.profiler.TraceAnnotation iff jax is already imported."""
    global _TraceAnnotation
    if _TraceAnnotation is None and "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation
            _TraceAnnotation = TraceAnnotation
        except Exception:       # pragma: no cover - jax without profiler
            _TraceAnnotation = False
    return _TraceAnnotation


class _NullSpan:
    """Shared no-op context manager for disabled tracing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()

#: shared no-op span, for call sites that decide themselves not to time
#: a region and need the "not this time" branch to cost nothing
NULL = _NULL


class Span:
    __slots__ = ("name", "reg", "_observe", "_t0", "_jax", "path", "tags",
                 "_stack")

    def __init__(self, name: str, reg, observe: bool = True, tags=None):
        self.name = name
        self.reg = reg
        self._observe = observe
        self._jax = None
        self.path = name
        self.tags = tags

    def __enter__(self):
        stack = self._stack = _stack()
        if stack:
            self.path = stack[-1].path + "/" + self.name
        stack.append(self)
        if _jax_mirror:
            ta = _TraceAnnotation or _resolve_jax()
            if ta:
                self._jax = ta(self.name)
                self._jax.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self._jax is not None:
            self._jax.__exit__(*exc)
        self._stack.pop()
        if self._observe:
            self.reg.histogram(
                "span." + self.path + ".seconds",
                "wall seconds spent in this span path").observe(dt)
        rec = _timeline._recorder
        if rec is not None:
            rec.record(self.name, self.path, self._t0, dt, self.tags)
        return False


def span(name: str, registry=None, tags=None):
    """Open a traced region. Records into ``registry``, else into the
    innermost open span's registry, else the process-global one
    (``current_registry()``). Returns a shared null context manager when
    tracing or the target registry is disabled. ``tags`` (e.g.
    ``{"job": 3, "chunk": 7}``) ride along on timeline events only —
    they never fan out histogram names.

    A process-wide timeline recorder (obs.timeline.install) overrides
    the registry gate: spans still land on the timeline even when their
    target registry is disabled — the recorder is process-scoped, so the
    timeline must see every span in the process. Such timeline-only
    spans skip the histogram observe."""
    if not _enabled:
        return _NULL
    reg = registry if registry is not None else current_registry()
    if reg.enabled:
        return Span(name, reg, True, tags)
    if _timeline._recorder is None:
        return _NULL
    return Span(name, reg, False, tags)     # timeline-only span
