"""Spans and counters of the fit path, on the device trace's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: a host span
written into the profiler's own trace, beside the device's operations,
with ``args`` as its stats.  With no trace running it costs one check.

Five process counters, read with ``counters()``, each raised at the
same boundary as a span so that a trace and the counters agree:

* ``h2d_bytes``: bytes ``build_tree`` sent from host arrays to the device
  (``udt.put`` spans, their ``bytes`` arg);
* ``syncs``: device-to-host reads of the level loop (``udt.sync`` spans);
* ``hist_rows``: rows the level steps' feature histograms took in, read
  in those same reads (zero-length ``udt.hist`` spans, their ``rows`` arg);
* ``compiles``: executables compiled or loaded from the persistent cache
  (zero-length ``repro.compile`` spans, their ``fun`` arg the program);
* ``rounds``: boosting rounds begun (``boost.round`` spans, their
  ``round`` and ``rows`` args).
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import numpy as np

__all__ = ["span", "spanned", "counted", "host_bytes", "counters"]

# jax._src.dispatch.BACKEND_COMPILE_EVENT: recorded once per compile or
# persistent-cache load, with the compile's duration
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_COUNTS = {"h2d_bytes": 0, "syncs": 0, "hist_rows": 0, "compiles": 0,
           "rounds": 0}
_LOCK = threading.Lock()     # fits and compiles may run on several threads

span = jax.profiler.TraceAnnotation


def spanned(name: str):
    """A decorator: each call of the function is a span ``name``."""
    return functools.partial(jax.profiler.annotate_function, name=name)


def counters() -> dict:
    """A copy of the process counters."""
    with _LOCK:
        return dict(_COUNTS)


@contextlib.contextmanager
def counted(name: str, counter: str, n: int = 1, **args):
    """A span ``name`` that adds ``n`` to ``counter`` as it opens."""
    with _LOCK:
        _COUNTS[counter] += n
    with span(name, **args):
        yield


def host_bytes(*xs) -> int:
    """Bytes of the arrays among ``xs`` that live on the host: a
    ``jax.Array`` is already on the device and counts 0."""
    return sum(np.asarray(x).nbytes for x in xs
               if x is not None and not isinstance(x, jax.Array))


def _on_compile(event: str, duration: float, **kw):
    if event == COMPILE_EVENT:
        with counted("repro.compile", "compiles", fun=kw.get("fun_name", "")):
            pass


jax.monitoring.register_event_duration_secs_listener(_on_compile)
