"""Set-up to a steady state, and the measured window, on one node.

The window drives ``Scheduler.submit`` and ``Scheduler.step`` in a loop:
each timed request is submitted at its due time (open loop), the offline
backlog keeps the queue full, and every output token is stamped with the
host clock when the ``step`` that produced it returns (the step has
synced it to the host by then).  The window closes at the end of the
last step that started before the deadline, and counts that step whole.
Counts are read from the scheduler's slots and results and from the
engine's counters; nothing of the program is changed.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import jax
import numpy as np

CLOCK = time.perf_counter
# host spans of a traced run: opened by run_window and instrument
SPANS = frozenset({"window", "generator", "step", "record", "wait_arrival",
                   "admission", "prefill", "decode_round", "finish"})


def program_request(req):
    from repro.serving.engine import Request
    return Request(req.rid, list(req.tokens), max_new=req.max_new)


@dataclass
class Window:
    seconds: float = 0.0                # no step starts after this
    elapsed: float = 0.0                # to the end of the last step
    due: dict = field(default_factory=dict)             # rid -> due s
    late: list = field(default_factory=list)            # submit - due, s
    tokens: dict = field(default_factory=dict)          # rid -> [t, ...]
    admitted: list = field(default_factory=list)        # (cached, prompt)
    decode_ctx: list = field(default_factory=list)      # per dispatch
    finished: list = field(default_factory=list)        # program Results
    served: list = field(default_factory=list)  # (rid, out, cached) at close
    worked: set = field(default_factory=set)            # rids served
    steps: list = field(default_factory=list)  # (start s, took s, prefills)
    prefill_dispatches: int = 0
    prefill_tokens: int = 0

    def token_count(self) -> int:
        return sum(len(v) for v in self.tokens.values())


class _Watch:
    """What one ``step`` changed: new tokens, admissions, a decode."""

    def __init__(self, sched):
        self.sched = sched
        self.seen = {}            # rid -> tokens seen so far
        self.n_done = len(sched.done)

    def _counts(self):
        s = self.sched
        for slot in s.slots:
            if slot is not None:
                yield slot.req.req_id, len(slot.out), slot, None
        for r in s.done[self.n_done:]:
            yield r.req_id, len(r.output), None, r
        self.n_done = len(s.done)

    def after_step(self, t: float, win: Window | None, decoded: bool):
        """Record what the step that returned at ``t`` produced."""
        for rid, n, slot, result in self._counts():
            new = rid not in self.seen
            k = n - self.seen.get(rid, 0)
            self.seen[rid] = n
            if win is None:
                continue
            win.worked.add(rid)
            if new:
                if slot is not None:
                    win.admitted.append((slot.cached_tokens,
                                         len(slot.req.tokens)))
                else:
                    win.admitted.append((result.cached_tokens,
                                         result.prompt_tokens))
            if k:
                win.tokens.setdefault(rid, []).extend([t] * k)
            if result is not None:
                win.finished.append(result)
        if win is not None and decoded:
            win.decode_ctx.append([s.pos for s in self.sched.slots
                                   if s is not None])


def _run_until_idle(sched, watch):
    while sched.queue or sched.active:
        sched.step()
        watch.after_step(CLOCK(), None, False)


def warm_programs(sched, vocab: int, rng):
    """Compile every program a window can call: the batched prefill, the
    batched decode and the query-only replay of a block-aligned hit
    (the second request below is a whole-prompt cache hit)."""
    from repro.serving.engine import Request
    blk = sched.engine.block
    toks = rng.integers(1, vocab, blk).tolist()
    watch = _Watch(sched)
    for rid in (-1, -2):
        sched.submit(Request(rid, toks, max_new=3))
        _run_until_idle(sched, watch)
    sched.done.clear()


def settle(sched, traffic, vocab: int, seed: int):
    """Set-up: compile, then bring the node to the state it would hold at
    window start.  Returns the watch that the window carries on with."""
    from repro.serving.engine import Request
    warm_programs(sched, vocab, np.random.default_rng(seed))
    watch = _Watch(sched)
    # prefix pages a steady node holds: prefilled, one token each
    for i, toks in enumerate(traffic.warm):
        sched.submit(Request(-10 - i, list(toks), max_new=1))
    _run_until_idle(sched, watch)
    sched.done.clear()
    watch.n_done = 0
    # requests already decoding at window start: admitted together
    for req in traffic.in_flight:
        sched.submit(program_request(req))
    if traffic.in_flight:
        sched.step()
        watch.after_step(CLOCK(), None, False)
    jax.block_until_ready((sched.engine.arena, sched._logits))
    return watch


def run_window(sched, traffic, seconds: float, watch, spans=None) -> Window:
    """The measured window: from 0 to the end of the last step that
    started before ``seconds`` (``Window.elapsed``), every step in it
    counted whole, so that the rates take all the work over all the time.
    ``spans(name)`` gives a context manager for a host span (traced runs);
    without it nothing is annotated."""
    span = spans or (lambda name: contextlib.nullcontext())
    eng = sched.engine
    win = Window(seconds=seconds)
    timed, backlog = traffic.timed, iter(traffic.backlog)
    nxt = 0
    p_disp, p_tok = eng.prefill_dispatches, eng.prefill_tokens
    decodes = sched.metrics["decode_calls"]
    for req in traffic.in_flight:
        win.worked.add(req.rid)
    t0 = CLOCK()
    while True:
        now = CLOCK() - t0
        if now >= seconds:
            break
        with span("generator"):
            while nxt < len(timed) and timed[nxt][0] <= now:
                due, req = timed[nxt]
                win.due[req.rid] = due
                win.late.append(CLOCK() - t0 - due)
                sched.submit(program_request(req))
                nxt += 1
            while len(sched.queue) < sched.max_active:
                req = next(backlog, None)
                if req is None:
                    break
                sched.submit(program_request(req))
        if sched.queue or sched.active:
            p0 = eng.prefill_dispatches
            with span("step"):
                sched.step()
            win.steps.append((now, CLOCK() - t0 - now,
                              eng.prefill_dispatches - p0))
            with span("record"):
                d = sched.metrics["decode_calls"]
                watch.after_step(CLOCK() - t0, win, d != decodes)
                decodes = d
        else:
            until = timed[nxt][0] if nxt < len(timed) else seconds
            with span("wait_arrival"):
                time.sleep(max(0.0, min(until, seconds) - now))
    win.elapsed = CLOCK() - t0
    # requests due before the close that the loop had no turn to submit
    while nxt < len(timed) and timed[nxt][0] < win.seconds:
        win.due[timed[nxt][1].rid] = timed[nxt][0]
        nxt += 1
    # every request served since set-up, the ones still decoding with
    # what they have emitted so far: what the check draws from
    win.served = [(r.req_id, list(r.output), r.cached_tokens)
                  for r in sched.done]
    win.served += [(s.req.req_id, list(s.out), s.cached_tokens)
                   for s in sched.slots if s is not None and s.out]
    win.prefill_dispatches = eng.prefill_dispatches - p_disp
    win.prefill_tokens = eng.prefill_tokens - p_tok
    return win


def instrument(sched):
    """Host spans around the scheduler's own phases, for a traced run:
    admission, prefill, decode round and retiring a slot.  Wraps bound
    methods of this one object; a phase the program no longer has is
    left out."""
    phases = {"admission": (sched, "_admit_batch"),
              "prefill": (sched.engine, "prefill_requests"),
              "decode_round": (sched, "_decode_round"),
              "finish": (sched, "_finish_slot")}
    for name, (obj, attr) in phases.items():
        fn = getattr(obj, attr, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _name=name, **kw):
            with jax.profiler.TraceAnnotation(_name):
                return _fn(*a, **kw)
        setattr(obj, attr, wrapped)
