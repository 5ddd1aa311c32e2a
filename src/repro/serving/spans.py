"""Host spans that are also counters.

A :class:`HostClock` holds named host-time accumulators.  ``span(name,
key)`` times a region with ``time.perf_counter``, adds its seconds to
``key`` and opens a :class:`jax.profiler.TraceAnnotation` called ``name``
around it, so one measurement feeds both the counter and the profiler's
timeline, where the span sits beside the device trace.  With no profiler
running the annotation costs one constructor call.
"""
from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, Iterator, Optional

from jax.profiler import TraceAnnotation


class HostClock:
    """Named host-time accumulators whose regions are profiler spans."""

    def __init__(self) -> None:
        """Start with every accumulator at zero."""
        self.seconds: Dict[str, float] = defaultdict(float)

    @contextmanager
    def span(self, name: str, key: Optional[str] = None) -> Iterator[None]:
        """Time the ``with`` body into ``seconds[key]`` (``key`` defaults
        to ``name``) under a profiler span called ``name``."""
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name):
                yield
        finally:
            self.seconds[key or name] += time.perf_counter() - t0
