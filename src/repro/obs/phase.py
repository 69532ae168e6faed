"""Named program phases on the profiler's clock.

``with phase("engine.accept", tracer, track):`` marks one phase of a
stage's loop. While a profiler session runs (``jax.profiler.start_trace``)
the phase enters ``jax.profiler.TraceAnnotation("marlaas.<name>")``, so it
lands on the host plane of the trace, on the same clock as the device
ops, whatever ``RuntimeConfig.trace`` says. With no session no annotation
is made, and a phase costs its two clock reads and a check of the
profiler's state. Given a ``Tracer``, the phase also records itself into
the tracer's ring as a span on ``track``.

The phase reads one clock pair (``t0`` at entry, ``t1`` at exit, from the
tracer's clock where one is given, else ``time.monotonic``); a call site
that books the same interval into its own counters reads them off the
phase instead of timing it again.

Rules: never enter a phase inside a jitted function (it would time the
trace, not the run), and give per-step phases no keyword metadata (it is
formatted on every entry while a session runs).

``gc_spans()`` puts every collection of the Python garbage collector on
the profiler's trace as ``marlaas.python.gc``: a collection holds the
interpreter lock, so every thread sees it as a stall.
"""
from __future__ import annotations

import gc
import time

from jax.profiler import TraceAnnotation

PREFIX = "marlaas."


class phase:
    """Context manager for one named phase (see the module doc)."""
    __slots__ = ("_ann", "_tracer", "_track", "name", "t0", "t1")

    def __init__(self, name: str, tracer=None, track=None):
        self.name = name
        self._tracer = tracer
        self._track = track

    def __enter__(self) -> "phase":
        # no profiler session: no annotation object at all
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(PREFIX + self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        tr = self._tracer
        self.t0 = time.monotonic() if tr is None else tr.now()
        return self

    def __exit__(self, *exc) -> None:
        tr = self._tracer
        if tr is None:
            self.t1 = time.monotonic()
        else:
            self.t1 = tr.now()
            tr.span(self._track, self.name, self.t0, self.t1)
        if self._ann is not None:
            self._ann.__exit__(*exc)


class gc_spans:
    """``marlaas.python.gc`` around every garbage collection while
    installed: ``install()`` registers the ``gc.callbacks`` hook,
    ``remove()`` takes it off. The hook records to the profiler only —
    never to a ``Tracer``, whose lock the collecting thread may hold."""

    def __init__(self):
        self._open = None

    def _on_gc(self, stage: str, info: dict) -> None:
        if stage == "start":
            if TraceAnnotation.is_enabled():
                self._open = TraceAnnotation(PREFIX + "python.gc")
                self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def install(self) -> "gc_spans":
        gc.callbacks.append(self._on_gc)
        return self

    def remove(self) -> None:
        gc.callbacks.remove(self._on_gc)
