"""The served step's phase timer.

The execution backends open a phase around each piece of a step's work,
and the engine opens spans around a step's submit and await:

* engine (`serving/engine.py`): ``submit`` around the backend's submit,
  ``await`` around its await (spans only; ``plan_walls`` times the plan);
* exec backend (`serving/backends/jax_exec.py`): ``query`` for a query
  materialised (a memo miss), ``resident`` for one resident access's
  partial, ``route`` / ``fetch`` / ``local`` for one dispatch of that
  primitive, ``merge`` for one request's merge;
* shard_map backend: ``stack`` and ``dispatch`` in its submit,
  ``barrier`` and ``merge`` (the whole post-barrier merge) in its await.

A phase does two things. Where a ``jax.profiler`` capture is running it
opens a ``TraceAnnotation`` named ``repro.<name>`` carrying ``step`` and
any other ids, so the capture shows the phase on the host plane, on the
clock of the device planes. And it adds its *self* host seconds (its
wall less that of the phases opened inside it: queries are materialised
lazily inside partials) and a count of one to its step's bucket.

The engine owns each bucket's life: ``open_step`` before the step's
submit, ``close_step`` when the step is accounted, into
``StepStats.phase_s`` / ``phase_n``. A phase whose step has no open
bucket counts nowhere, so a backend driven without an engine (the
benchmark's rehearsal) adds nothing to an engine's steps of the same
number, and steps in flight at ``pipeline_depth`` >= 2 do not mix.

With no capture running a phase costs the profiler's is-enabled check
and two clock reads. This module never imports JAX: before a process
has imported ``jax.profiler`` nothing can be capturing, so a
planner-only process stays JAX-free.
"""

from __future__ import annotations

import sys
import time
from typing import Callable, Dict, List, Tuple

PREFIX = "repro."

Bucket = Tuple[Dict[str, float], Dict[str, int]]   # (self seconds, counts)


def _annotation(name: str, step: int, ids: dict):
    """An entered profiler span, or None where no capture is running."""
    prof = sys.modules.get("jax.profiler")
    if prof is None or not prof.TraceAnnotation.is_enabled():
        return None
    ann = prof.TraceAnnotation(PREFIX + name, step=step, **ids)
    ann.__enter__()
    return ann


class _Span:
    __slots__ = ("name", "step", "ann")

    def __init__(self, name: str, step: int):
        self.name = name
        self.step = step

    def __enter__(self):
        self.ann = _annotation(self.name, self.step, {})
        return self

    def __exit__(self, *exc):
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class _Phase:
    __slots__ = ("timer", "name", "step", "ids", "ann", "t0", "inner")

    def __init__(self, timer: "PhaseTimer", name: str, step: int,
                 ids: dict):
        self.timer = timer
        self.name = name
        self.step = step
        self.ids = ids
        self.inner = 0.0

    def __enter__(self):
        self.ann = _annotation(self.name, self.step, self.ids)
        timer = self.timer
        timer._stack.append(self)
        self.t0 = timer.clock()
        return self

    def __exit__(self, *exc):
        timer = self.timer
        wall = timer.clock() - self.t0
        stack = timer._stack
        stack.pop()
        if stack:
            stack[-1].inner += wall
        bucket = timer._buckets.get(self.step)
        if bucket is not None:
            secs, counts = bucket
            name = self.name
            secs[name] = secs.get(name, 0.0) + wall - self.inner
            counts[name] = counts.get(name, 0) + 1
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class PhaseTimer:
    """Per-step self seconds and counts of named phases, with a profiler
    span around each. ``clock`` is replaceable for tests."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self._buckets: Dict[int, Bucket] = {}
        self._stack: List[_Phase] = []

    def phase(self, name: str, step: int, **ids: int) -> _Phase:
        """Context manager: time phase `name` of `step` and span it."""
        return _Phase(self, name, step, ids)

    def span(self, name: str, step: int) -> _Span:
        """Context manager: a profiler span only, timed nowhere."""
        return _Span(name, step)

    def open_step(self, step: int) -> None:
        """Start `step`'s bucket afresh."""
        self._buckets[step] = ({}, {})

    def close_step(self, step: int) -> Bucket:
        """Pop `step`'s bucket: (self seconds, counts) by phase name."""
        return self._buckets.pop(step, ({}, {}))


# the process's timer: the engine and the backends share it
TIMER = PhaseTimer()
phase = TIMER.phase
span = TIMER.span
open_step = TIMER.open_step
close_step = TIMER.close_step
