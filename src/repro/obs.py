"""Host spans that land in the JAX profiler's trace and in their owner's
running totals.

``Spans.span(name, **ids)`` opens a ``jax.profiler.TraceAnnotation`` (so
the span appears, with ``ids`` as its stats, in any profiler trace
running at the time) and adds its ``time.perf_counter`` duration to the
owner's ``seconds[name]``.  With no trace running a span costs a few
microseconds.  The profiler's trace and the owner's totals are the only
outputs: nothing is exported, and nothing switches spans off.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from dataclasses import dataclass, field

import jax


@dataclass
class Spans:
    """Summed seconds of each span name an owner opened."""

    seconds: dict[str, float] = field(default_factory=dict)

    @contextlib.contextmanager
    def span(self, name: str, **ids):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name, **ids):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) \
            + time.perf_counter() - t

    def copy(self):
        """A snapshot whose totals no later span moves."""
        return dataclasses.replace(self, seconds=dict(self.seconds))
