"""Host spans of the checkpoint path, on the profiler's clock.

``with span("ckpt.snapshot.d2h", bytes=n):`` records one interval of host
time: its name, ``t0``/``t1`` on ``time.perf_counter()``, the thread it ran
on, its id, the id of the span it nests in on that thread, and its
attributes (``bytes``, ``elements``, ``step``, ...).  Each span is also a
``jax.profiler.TraceAnnotation``, so while a profiler trace runs it lands
on the host plane of the same ``.xplane.pb`` as the device's operations.

Recording is always on and coarse: spans mark phases and leaves, never
chunks or elements.  Records go into a bounded ring (:data:`MAXLEN`); when
it is full the oldest record is dropped and counted (:func:`dropped`).
:func:`totals` keeps each name's count, seconds and bytes since the
process started, whatever the ring dropped.

A span without a ``step`` takes its parent's, so every span inside one
save carries the save's step; the writer thread's job spans are given the
step explicitly, which joins them to the save's ``ckpt.save``.  A span
marked ``pass_=True`` copies or scans the saved bytes it counts on the
host (one host pass over them per ``bytes``).

The module does not import jax.  A span opens its ``TraceAnnotation``
once the process has imported jax (a profiler trace needs it), and
:func:`record_compiles`, which ``launch.compile_cache.init_compile_cache``
calls, registers one ``jax.monitoring`` listener: XLA's backend compiles
become ``jax.compile`` spans and persistent-cache loads ``jax.cache_load``
spans (``t1`` the event's time, ``t0`` that less its duration).  A compile
that hits the persistent cache holds its cache load.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time
from typing import Iterator

#: ring capacity: about ninety saves of a 34-leaf train state
MAXLEN = 1 << 15
COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
}


class Span:
    """One recorded interval; ``t1`` is set when the span closes."""

    __slots__ = ("name", "t0", "t1", "thread", "span_id", "parent_id",
                 "attrs")

    def __init__(self, name: str, t0: float, thread: str, span_id: int,
                 parent_id: int | None, attrs: dict):
        self.name, self.t0, self.t1 = name, t0, t0
        self.thread, self.span_id, self.parent_id = thread, span_id, parent_id
        self.attrs = attrs

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.seconds:.6f} s, "
                f"{self.thread!r}, {self.attrs})")


_ring: collections.deque[Span] = collections.deque(maxlen=MAXLEN)
_lock = threading.Lock()   # guards _ring's drop count, _totals, _listening
_totals: dict[str, list] = {}      # name -> [count, seconds, bytes]
_dropped = 0
_ids = itertools.count(1)
_local = threading.local()
_annotation = None       # jax.profiler.TraceAnnotation once jax is imported
_listening = False       # the compile listener is registered


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once the process has imported jax;
    a no-op context before that, when no profiler trace can be running."""
    global _annotation
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext
    _annotation = jax.profiler.TraceAnnotation
    return _annotation


def _record(sp: Span) -> None:
    global _dropped
    with _lock:
        if len(_ring) == _ring.maxlen:
            _dropped += 1
        _ring.append(sp)
        tot = _totals.setdefault(sp.name, [0, 0.0, 0])
        tot[0] += 1
        tot[1] += sp.t1 - sp.t0
        tot[2] += int(sp.attrs.get("bytes", 0))


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[Span]:
    """Record the ``with`` block as span ``name``.  The yielded
    :class:`Span` takes attributes measured inside the block
    (``sp.attrs["bytes"] = n``) and holds ``t0``/``t1`` after it."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
        _local.thread = threading.current_thread().name
    parent = stack[-1] if stack else None
    if attrs.get("step") is None:
        attrs.pop("step", None)
        if parent is not None and "step" in parent.attrs:
            attrs["step"] = parent.attrs["step"]
    sp = Span(name, 0.0, _local.thread, next(_ids),
              parent.span_id if parent is not None else None, attrs)
    stack.append(sp)
    with (_annotation or _trace_annotation())(name):
        sp.t0 = time.perf_counter()
        try:
            yield sp
        finally:
            sp.t1 = time.perf_counter()
            stack.pop()
            _record(sp)


def spans() -> list[Span]:
    """The records in the ring, oldest first."""
    with _lock:
        return list(_ring)


def totals() -> dict[str, dict]:
    """Per span name since the process started: ``count``, ``seconds``
    and ``bytes``."""
    with _lock:
        return {n: {"count": c, "seconds": s, "bytes": b}
                for n, (c, s, b) in _totals.items()}


def dropped() -> int:
    """Records the full ring has dropped since the process started."""
    with _lock:
        return _dropped


def _on_duration(event: str, duration: float, **_) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is None:
        return
    t1 = time.perf_counter()
    stack = getattr(_local, "stack", None)
    parent = stack[-1].span_id if stack else None
    sp = Span(name, t1 - duration, threading.current_thread().name,
              next(_ids), parent, {})
    sp.t1 = t1
    _record(sp)


def record_compiles() -> None:
    """Record XLA's compiles and persistent-cache loads as spans from now
    on (idempotent)."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
