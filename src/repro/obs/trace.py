"""Host-side tracing spans: nested, attributed, jit-safe.

A span times a region of *host* code::

    from repro import obs

    with obs.span("serve.flush", due=3):
        ...

Spans nest by the host call stack (one stack per thread) and carry
arbitrary attributes. They are **jit-safe by construction**: a span is
pure host bookkeeping — it never stages anything into a traced program,
so instrumented and uninstrumented runs produce bit-identical results
and identical compile counts. A span entered while a jax trace is being
built (e.g. the fit's stage spans, which run at trace time) is tagged
``traced=True``: it measures trace/compile
construction, fires once per compile, and never re-executes in steady
state — compile-event accounting, not steady-state latency.

Telemetry is **off by default**. Enable with :func:`enable` or the
``REPRO_OBS=1`` environment variable; when disabled, :func:`span`
returns a shared no-op context manager (one flag test and one check
for a profiler session, no allocation), so the instrumented hot paths
cost nothing.

**Profiler view.** While a ``jax.profiler`` session is active (inside
``jax.profiler.trace``), every span is also written into the profiler's
trace as a ``jax.profiler.TraceAnnotation`` of the same name, with no
attributes: host spans and device ops then share one clock, and a
device idle gap can be charged to the span open at that moment. With
telemetry off the annotation is all a span is; with telemetry on the
:class:`Span` enters it itself. The fit's own spans follow the
``lingam.<stage>`` naming of the program's device scopes
(``lingam.fit``, ``lingam.fetch``).

Completed root spans are kept in a bounded ring (newest last); render
them with :func:`format_tree`.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

_ENV_VAR = "REPRO_OBS"

_ENABLED = os.environ.get(_ENV_VAR, "").strip().lower() not in (
    "", "0", "false", "off",
)

_MAX_ROOTS = 256

_lock = threading.Lock()
_roots: "collections.deque" = collections.deque(maxlen=_MAX_ROOTS)


class _Stack(threading.local):
    def __init__(self):
        self.spans: List["Span"] = []


_stack = _Stack()

# A jax.profiler.TraceAnnotation that takes span attributes (and drops
# them), made on first use so that importing telemetry does not import
# jax.
_annotation = None


def _profiler_annotation():
    """The annotation class while a profiler session is active, else
    None."""
    global _annotation
    if _annotation is None:
        import jax.profiler

        class Annotation(jax.profiler.TraceAnnotation):
            def set(self, **attrs) -> "Annotation":
                return self

        _annotation = Annotation
    return _annotation if _annotation.is_enabled() else None


def enable(on: bool = True) -> None:
    """Turn telemetry on (spans + metrics). Off by default."""
    global _ENABLED
    _ENABLED = bool(on)


def disable() -> None:
    enable(False)


def enabled() -> bool:
    return _ENABLED


def _in_jax_trace() -> bool:
    """True while jax is building a trace (span executes at trace time)."""
    import jax

    return not jax.core.trace_ctx.is_top_level()


class Span:
    """One timed host region. Use via :func:`span`, not directly."""

    __slots__ = (
        "name", "attrs", "traced", "t0", "duration_s", "children", "_ann",
    )

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.traced = False
        self.t0 = 0.0
        self.duration_s = 0.0
        self.children: List["Span"] = []
        self._ann = None

    def set(self, **attrs) -> "Span":
        """Attach/overwrite attributes mid-span."""
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        self.traced = _in_jax_trace()
        annotation = _profiler_annotation()
        if annotation is not None:
            self._ann = annotation(self.name)
            self._ann.__enter__()
        _stack.spans.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.duration_s = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        stack = _stack.spans
        if stack and stack[-1] is self:
            stack.pop()
        if stack:
            stack[-1].children.append(self)
        else:
            with _lock:
                _roots.append(self)
        from . import metrics

        metrics.observe(f"span.{self.name}_s", self.duration_s)


class _NoopSpan:
    """Shared disabled-telemetry span: no allocation, no timing."""

    __slots__ = ()

    def set(self, **attrs) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


_NOOP = _NoopSpan()


def span(name: str, **attrs):
    """A timed host-side span. With telemetry off it is the profiler's
    annotation ``name`` while a profiler session is active, else the
    shared no-op."""
    if not _ENABLED:
        annotation = _profiler_annotation()
        return _NOOP if annotation is None else annotation(name)
    return Span(name, attrs)


def roots(last: Optional[int] = None) -> List[Span]:
    """Completed root spans, oldest first (bounded ring)."""
    with _lock:
        out = list(_roots)
    return out if last is None else out[-last:]


def reset() -> None:
    """Drop all recorded spans (the current thread's open stack too)."""
    with _lock:
        _roots.clear()
    _stack.spans.clear()


def _fmt_attrs(attrs: Dict[str, Any]) -> str:
    if not attrs:
        return ""
    body = ", ".join(f"{k}={v}" for k, v in attrs.items())
    return "  {" + body + "}"


def _fmt_span(s: Span, indent: int, lines: List[str]) -> None:
    ms = s.duration_s * 1e3
    tag = "  [trace]" if s.traced else ""
    lines.append(
        f"{'  ' * indent}{s.name}  {ms:.2f}ms{tag}{_fmt_attrs(s.attrs)}"
    )
    for c in s.children:
        _fmt_span(c, indent + 1, lines)


def format_tree(last: Optional[int] = None) -> str:
    """ASCII rendering of the recorded span trees."""
    lines: List[str] = []
    for s in roots(last):
        _fmt_span(s, 0, lines)
    return "\n".join(lines) if lines else "(no spans recorded)"
