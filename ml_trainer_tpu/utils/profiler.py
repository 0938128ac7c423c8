"""Tracing / profiling utilities.

The reference's only observability is tqdm postfix text and wall-clock in
committed notebook output (SURVEY.md §5 tracing).  TPU-native replacements:

* ``trace(logdir)`` — context manager around ``jax.profiler`` emitting a
  TensorBoard-loadable trace (XLA op timeline, HBM usage) for any code
  region, e.g. ``with trace('/tmp/tb'): trainer.fit()``.
* ``annotate(name)`` — named region that shows up inside the trace.
* ``StepTimer`` — honest steady-state step timing: async dispatch means
  naive wall-clocks lie (SURVEY.md §7 hard part (e)), so the timer fences
  with ``jax.block_until_ready`` (``force``) only at measurement boundaries.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Optional

import jax


def force(fence: Any) -> None:
    """Wait until every array in ``fence`` has been computed — the fence a
    host clock needs around asynchronously dispatched work."""
    jax.block_until_ready(fence)


@contextlib.contextmanager
def trace(logdir: str, create_perfetto_link: bool = False):
    jax.profiler.start_trace(logdir, create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named trace region (``jax.profiler.TraceAnnotation``)."""
    return jax.profiler.TraceAnnotation(name)


class StepTimer:
    """Steady-state samples/sec with warmup exclusion and sync fencing.

    Usage::

        timer = StepTimer(warmup=5)
        for batch in loader:
            state, loss, _ = step(state, *batch)
            timer.tick(state, batch_size)
        print(timer.rate())   # samples/sec, compile excluded

    ``record_steps=True`` additionally records PER-STEP durations —
    each post-warmup ``tick`` fences (``force``) before reading the
    clock, so every duration covers real execution, and ``p50()`` /
    ``p99()`` report the step-time distribution, not just the mean.
    The per-step fence serializes dispatch against the host (that is
    what makes the numbers honest), so use the default mode when only
    the aggregate rate matters and pipelining should stay live.
    """

    def __init__(self, warmup: int = 5, record_steps: bool = False):
        self.warmup = warmup
        self.record_steps = bool(record_steps)
        self._seen = 0
        self._samples = 0
        self._t0: Optional[float] = None
        self._last: Optional[float] = None
        self._fence: Any = None
        self._durs: list = []

    def tick(self, fence: Any, n_samples: int) -> None:
        self._seen += 1
        self._fence = fence
        if self._seen == self.warmup:
            force(fence)
            self._t0 = time.perf_counter()
            self._last = self._t0
        elif self._seen > self.warmup:
            self._samples += n_samples
            if self.record_steps:
                force(fence)
                now = time.perf_counter()
                self._durs.append(now - self._last)
                self._last = now

    def rate(self) -> Optional[float]:
        if self._t0 is None or self._samples == 0:
            return None
        force(self._fence)
        return self._samples / (time.perf_counter() - self._t0)

    def _percentile(self, q: float) -> Optional[float]:
        if not self._durs:
            return None
        s = sorted(self._durs)
        return s[min(len(s) - 1, int(q * (len(s) - 1) + 0.5))]

    def p50(self) -> Optional[float]:
        """Median fenced step duration (seconds); None unless
        ``record_steps`` collected post-warmup samples."""
        return self._percentile(0.5)

    def p99(self) -> Optional[float]:
        """p99 fenced step duration (seconds); with few samples this is
        the max — still the honest tail proxy."""
        return self._percentile(0.99)
