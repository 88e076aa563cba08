"""positscope spans: nested wall-clock spans serialized as Chrome
``trace_event`` JSON (Perfetto / chrome://tracing's legacy format).

``span(name, **attrs)`` is a context manager that

* always enters ``jax.profiler.TraceAnnotation(name)``, so the span
  shows on the host's line of a JAX/XLA profiler trace, on the device
  trace's clock, whenever one is being captured (with no profiler
  running the annotation records nothing and costs about half a
  microsecond of host time);
* records only when an ``obs.scoped()`` collector is open: it then
  times the region with ``time.perf_counter`` and on exit appends ONE
  complete event (``"ph": "X"``, microsecond ``ts``/``dur`` relative to
  each collector's creation) to every open collector.  Complete
  events on the same pid/tid nest by ts/dur containment, which is
  exactly how Perfetto renders a blocked factorization's panel/update
  structure.

Spans may carry static attributes
(``span("posit.rgetrf", n=256, nb=64)``); attrs land in the event's
``args`` and must be JSON-representable scalars/strings.  The current
nesting depth and dotted path are recorded too, so the JSON is
greppable without a viewer.
"""
from __future__ import annotations

import contextlib
import os
import time

import jax

from repro.obs import metrics as _metrics

# Host-side span stack (names only) — gives events their dotted path.
_SPAN_STACK: list[str] = []


def _jsonable(v):
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return str(v)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Annotate a region under ``name`` for the profiler, and time it
    into every open collector."""
    with jax.profiler.TraceAnnotation(name):
        if not _metrics._STACK:
            yield
            return
        _SPAN_STACK.append(name)
        path = ".".join(_SPAN_STACK)
        depth = len(_SPAN_STACK)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            _SPAN_STACK.pop()
            args = {k: _jsonable(v) for k, v in attrs.items()}
            args["path"] = path
            args["depth"] = depth
            for c in _metrics._STACK:
                c.events.append({
                    "name": name, "cat": "positscope", "ph": "X",
                    "ts": (t0 - c.t0) * 1e6, "dur": (t1 - t0) * 1e6,
                    "pid": os.getpid(), "tid": 0, "args": dict(args),
                })
