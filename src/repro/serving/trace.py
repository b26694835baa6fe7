"""One node's spans, counters and step records.

A ``Recorder`` belongs to one ``RealEngine``; the ``Scheduler`` that
drives the engine records into the engine's.  ``span(name, **meta)``
times a piece of host work twice over:

- while a profiler session runs, it enters
  ``jax.profiler.TraceAnnotation(name, **meta)``, so that the span lands
  in the trace's host plane on the same clock as the device's ops
  (outside a session this costs one ``is_enabled`` check);
- always, it appends ``Span(name, start, end, parent, step, rid)`` to a
  bounded ring (``Recorder.spans``).

``Recorder.step(counters)`` is the span ``sched.step``.  When it closes
it appends a ``StepRecord`` to a second ring: the step's sequence number,
start and end, the total seconds of each span that closed inside it, by
name, and ``counters()``, a snapshot of the node's counters and gauges.

Python's garbage collector is timed by one process-wide ``gc.callbacks``
hook: a full (generation 2) collection is the span ``gc`` in every live
recorder, and the young collections are a count and a total time.

Live recorders sit in a registry of weak references, so that an
exporter or a metric reader finds the newest with ``latest()`` without
being handed the node, and a freed node's recorder leaves it.
"""
from __future__ import annotations

import collections
import gc
import itertools
import time
import weakref
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

CLOCK = time.perf_counter
STEP = "sched.step"
SPAN_CAPACITY = 1 << 14      # spans kept per recorder
STEP_CAPACITY = 1 << 12      # step records kept per recorder

_LIVE: "weakref.WeakValueDictionary[int, Recorder]" = \
    weakref.WeakValueDictionary()
_IDS = itertools.count()


class Span(NamedTuple):
    name: str
    start: float             # CLOCK seconds
    end: float
    parent: Optional[str]    # the enclosing span's name
    step: Optional[int]      # sequence number of the enclosing step
    rid: Optional[int]       # request id, from meta or the parent span


class StepRecord(NamedTuple):
    seq: int
    start: float
    end: float
    spans: dict              # span name -> total seconds inside the step
    counters: dict           # snapshot taken as the step closed


class _Span:
    __slots__ = ("rec", "name", "meta", "ann", "t0", "parent", "rid")

    def __init__(self, rec, name, meta):
        self.rec, self.name, self.meta = rec, name, meta

    def __enter__(self):
        rec = self.rec
        self.ann = None
        if TraceAnnotation.is_enabled():
            self.ann = TraceAnnotation(self.name, **self.meta)
            self.ann.__enter__()
        top = rec._open[-1] if rec._open else None
        self.parent = top.name if top is not None else None
        self.rid = self.meta.get("rid", top.rid if top is not None else None)
        rec._open.append(self)
        self.t0 = CLOCK()
        return self

    def __exit__(self, *exc):
        t1 = CLOCK()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.rec._open.pop()
        self._close(t1)
        return False

    def _close(self, t1: float):
        rec = self.rec
        rec._add(Span(self.name, self.t0, t1, self.parent, rec._step,
                      self.rid))


class _Step(_Span):
    __slots__ = ("counters",)

    def __init__(self, rec, counters):
        super().__init__(rec, STEP, {})
        self.counters = counters

    def __enter__(self):
        rec = self.rec
        rec._step = rec._next_step
        rec._next_step += 1
        rec._totals = {}
        return super().__enter__()

    def _close(self, t1: float):
        rec = self.rec
        totals, rec._totals = rec._totals, None
        super()._close(t1)
        rec.steps.append(StepRecord(rec._step, self.t0, t1, totals,
                                    self.counters()))
        rec._step = None


class Recorder:
    """Spans and step records of one node, in bounded rings."""

    def __init__(self):
        self.spans: collections.deque = collections.deque(
            maxlen=SPAN_CAPACITY)
        self.steps: collections.deque = collections.deque(
            maxlen=STEP_CAPACITY)
        self.gc_young = 0            # generation 0 and 1 collections
        self.gc_young_s = 0.0
        self.gc_full = 0             # generation 2 collections (spans)
        self._open: list = []        # spans entered and not yet left
        self._step: Optional[int] = None
        self._totals: Optional[dict] = None
        self._next_step = 0
        _LIVE[next(_IDS)] = self
        _GC_HOOK.install()

    def span(self, name: str, **meta) -> _Span:
        """Context manager timing host work as span ``name``."""
        return _Span(self, name, meta)

    def step(self, counters) -> _Step:
        """The span ``sched.step``; ``counters()`` is read as it closes
        into the step's record."""
        return _Step(self, counters)

    def _add(self, span: Span):
        self.spans.append(span)
        if self._totals is not None:
            self._totals[span.name] = (self._totals.get(span.name, 0.0)
                                       + span.end - span.start)

    def _gc(self, generation: int, t0: float, t1: float):
        if generation < 2:
            self.gc_young += 1
            self.gc_young_s += t1 - t0
            return
        self.gc_full += 1
        top = self._open[-1] if self._open else None
        self._add(Span("gc", t0, t1, top.name if top is not None else None,
                       self._step, top.rid if top is not None else None))


def live() -> list:
    """Live recorders, oldest first."""
    return list(_LIVE.values())


def latest() -> Optional[Recorder]:
    """The newest live recorder, or None."""
    recs = live()
    return recs[-1] if recs else None


class _GcHook:
    """The one ``gc.callbacks`` entry of the process: it holds no
    recorder, and hands each collection to every live one."""

    def __init__(self):
        self.installed = False
        self.t0 = 0.0
        self.ann = None

    def install(self):
        if not self.installed:
            gc.callbacks.append(self)
            self.installed = True

    def __call__(self, phase, info):
        gen = info["generation"]
        if phase == "start":
            if gen == 2 and TraceAnnotation.is_enabled():
                self.ann = TraceAnnotation("gc", generation=gen)
                self.ann.__enter__()
            self.t0 = CLOCK()
            return
        t1 = CLOCK()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        for rec in live():
            rec._gc(gen, self.t0, t1)


_GC_HOOK = _GcHook()
