"""Host spans at the program's layer boundaries.

``span(name, **attrs)`` times a block of host code (``traced(name)``
wraps a whole function in one). Each span enters a
``jax.profiler.TraceAnnotation``, so when a profile is taken it lands in
the same trace as the device ops, on the profiler's clock. It is also
kept in memory: a span opened with no span around it (on its thread) is
a root, and the last ``RING_ROOTS`` finished roots are kept, each with
its descendants. ``recent(name, n)`` returns them::

    for root in repro.obs.recent("repro.join", 5):
        print(root.duration_ns, root.attrs,
              [(s.name, s.self_ns, s.attrs) for s in root.spans])

Every lowering of a jitted program (the ``jax.monitoring`` event
``COMPILE_EVENT``) adds one to the ``compiles`` attribute of the
innermost span open on its thread: the span that recompiled.

Spans go on the host around existing code, never inside a jitted
function and never in a per-set, per-pair or per-request loop. Counts go
on them as attributes (``Span.set``); scalar ones reach the trace too.
"""
from __future__ import annotations

import collections
import functools
import threading
import time
import weakref

import jax

__all__ = ["RING_ROOTS", "MAX_SPANS", "COMPILE_EVENT", "Span", "span",
           "traced", "current", "recent"]

RING_ROOTS = 4096   # finished roots kept, oldest dropped first
# descendants kept per root; the rest count in ``dropped``. A join opens
# at most 4 + 5 a block: 1,024 keeps a 100,000-row self-join's 98 blocks
MAX_SPANS = 1024
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_clock = time.perf_counter_ns
_ring: collections.deque = collections.deque(maxlen=RING_ROOTS)
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class Span:
    """One timed block of host code (a context manager).

    ``start_ns``/``end_ns`` are ``time.perf_counter_ns`` readings;
    ``child_ns`` is the time its direct children cover, so ``self_ns`` is
    its own work. A root's ``spans`` holds its descendants in the order
    they opened (at most ``MAX_SPANS``; ``dropped`` counts the rest), and
    ``parent`` links each back up the tree.
    """

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "child_ns", "spans",
                 "dropped", "_parent", "_trace", "__weakref__")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs
        self.start_ns = self.end_ns = self.child_ns = self.dropped = 0
        self.spans: list[Span] = []
        self._parent = None
        self._trace = jax.profiler.TraceAnnotation(name, **attrs)

    @property
    def parent(self) -> "Span | None":
        return None if self._parent is None else self._parent()

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    @property
    def self_ns(self) -> int:
        """Duration less the time its children cover."""
        return self.duration_ns - self.child_ns

    def set(self, **attrs) -> None:
        """Add attributes; scalar ones also reach the profiler's trace."""
        self.attrs.update(attrs)
        scalars = {k: v for k, v in attrs.items()
                   if isinstance(v, (int, float, str))}
        if scalars and self._trace is not None:
            self._trace.set_metadata(**scalars)

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            # weak: the root holds its descendants, so a strong link up
            # would make every finished tree a reference cycle
            self._parent = weakref.ref(stack[-1])
            root = stack[0]
            if len(root.spans) < MAX_SPANS:
                root.spans.append(self)
            else:
                root.dropped += 1
        stack.append(self)
        self._trace.__enter__()
        self.start_ns = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = _clock()
        self._trace.__exit__(*exc)
        self._trace = None
        stack = _stack()
        stack.pop()
        if stack:
            stack[-1].child_ns += self.end_ns - self.start_ns
        else:
            _ring.append(self)
        return False


def span(name: str, **attrs) -> Span:
    """A span named ``name`` (``with span(...) as s:``)."""
    return Span(name, attrs)


def traced(name: str):
    """Decorator: each call of the function runs inside ``span(name)``;
    the body reaches its span through ``current()``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with Span(name, {}):
                return fn(*args, **kwargs)
        return call
    return wrap


def current() -> Span | None:
    """The innermost span open on this thread, or None."""
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


def recent(name: str, n: int) -> list[Span]:
    """The last ``n`` finished roots named ``name``, oldest first."""
    if n <= 0:
        return []
    return [r for r in list(_ring) if r.name == name][-n:]


def _on_duration(event: str, _secs: float, **_kw) -> None:
    if event == COMPILE_EVENT:
        sp = current()
        if sp is not None:
            sp.attrs["compiles"] = sp.attrs.get("compiles", 0) + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
