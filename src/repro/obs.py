"""Host spans and counters, on the profiler's clock.

:func:`span` marks one stage of the program on the host.  It enters
``jax.profiler.TraceAnnotation(name)``, so in a profiled run the span lands
in the same trace as the device operations, on the same clock.  It also adds
``(count, seconds, bytes)`` under ``name`` to the :class:`Collector` bound
in the current context, if one is bound: :func:`collect` binds one for the
length of a streamed compression (``StreamReport.stages``).  A thread that
should report into the caller's collector runs its work under
``contextvars.copy_context().run``.

With no profiler session and no collector a span costs about 2 us on the
host: one annotation enter and exit and a clock read.  Spans wrap host code
and the device syncs it already has: never a traced function body, and
never a sync of their own.

Span names are stable strings, one prefix per layer: ``gwlz.ingest`` (the
streaming executor), ``gwlz.entropy`` (lane coding), ``gwlz.train``
(enhancer training), ``gwlz.decode`` (full decode).  ``docs/STREAMING.md``
lists them.

The collector also counts the backend compiles that run in its context,
through one process-wide ``jax.monitoring`` listener.
"""
from __future__ import annotations

import contextvars
import threading
import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

_COLLECTOR: contextvars.ContextVar = contextvars.ContextVar("gwlz_collector",
                                                           default=None)
# the event JAX records once per program that its backend compiles (a
# program loaded from the persistent cache records another event)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_LISTEN_LOCK = threading.Lock()
_listening = False


class Collector:
    """Totals per span name, and backend compiles, from every thread that
    runs in the context it is bound to."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stages: dict[str, list] = {}  # guarded-by: _lock
        self.compiles = 0  # guarded-by: _lock

    def add(self, name: str, seconds: float, nbytes: int = 0) -> None:
        with self._lock:
            tot = self._stages.setdefault(name, [0, 0.0, 0])
            tot[0] += 1
            tot[1] += seconds
            tot[2] += nbytes

    def add_compile(self) -> None:
        with self._lock:
            self.compiles += 1

    def stages(self) -> dict[str, tuple[int, float, int]]:
        """``{name: (count, seconds, bytes)}`` so far."""
        with self._lock:
            return {k: tuple(v) for k, v in self._stages.items()}


class span:
    """``with span(name, nbytes):`` times the block as stage ``name``;
    ``nbytes`` is what it moved (kept with the span in the trace too, as the
    annotation's ``nbytes``).  A class, not a generator: a span is entered
    thousands of times per stream."""

    __slots__ = ("name", "nbytes", "_col", "_ann", "_t0")

    def __init__(self, name: str, nbytes: int = 0):
        self.name, self.nbytes = name, int(nbytes)

    def __enter__(self):
        self._col = _COLLECTOR.get()
        self._ann = (TraceAnnotation(self.name, nbytes=self.nbytes) if self.nbytes
                     else TraceAnnotation(self.name))
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._col is not None:
            self._col.add(self.name, time.perf_counter() - self._t0, self.nbytes)
        self._ann.__exit__(*exc)


def count(name: str, nbytes: int) -> None:
    """A byte counter, such as what a stage wrote out: an empty span that
    carries ``nbytes``, so the collector and the trace both see it."""
    with span(name, nbytes):
        pass


def _on_event(event: str, _secs: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        col = _COLLECTOR.get()
        if col is not None:
            col.add_compile()


def _listen() -> None:
    global _listening
    with _LISTEN_LOCK:
        if not _listening:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_event)
            _listening = True


@contextmanager
def collect():
    """Bind a fresh :class:`Collector` to the current context for the
    block, and yield it."""
    _listen()
    col = Collector()
    token = _COLLECTOR.set(col)
    try:
        yield col
    finally:
        _COLLECTOR.reset(token)
