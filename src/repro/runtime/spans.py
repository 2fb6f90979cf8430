"""Program spans on the profiler's clock.

``span(name, counter=None, **stats)`` marks a stretch of host work as
``jax.profiler.TraceAnnotation("svff." + name, **stats)``, so a profiler
trace shows the program's layers (``engine.step``, ``pause.precopy_0``,
``staging.d2h``, ...) on the clock of the device ops; the ``svff.`` prefix
tells them from JAX's own host events. Keyword stats ride on the event (a
request id, a byte count). Given a ``collections.Counter``, the span also
adds its elapsed ``perf_counter_ns`` to ``counter[<last part>_ns]``
(``engine.admit`` -> ``admit_ns``), traced or not. Without a running
profiler a span costs one to two microseconds.
"""
from __future__ import annotations

import time

import jax

PREFIX = "svff."


class span:
    """Context manager: one program span, and its length in ``counter``."""

    __slots__ = ("_ann", "_counter", "_key", "_t0")

    def __init__(self, name: str, counter=None, **stats):
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **stats)
        self._counter = counter
        self._key = name.rsplit(".", 1)[-1] + "_ns"

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._counter is not None:
            self._counter[self._key] += time.perf_counter_ns() - self._t0
        return False
