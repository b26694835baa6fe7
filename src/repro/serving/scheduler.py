"""Slot-pool continuous-batching scheduler for the real engine.

Every ``step()`` issues ONE jitted batched decode dispatch for the whole
pool — dead rows are masked, not recompiled — and token selection / EOS
handling is vectorized over the batch.

With a **paged engine** (serving/engine.py, pure-attention families) the
pool is a host-side ``(max_active, max_pages)`` page-table array over the
engine's node-wide KV arena: admission installs the request's page list
into a free row (a prefix-cache hit arrives as *aliased* pages — zero KV
bytes copied), a fresh page is allocated only when a row's position
crosses a block boundary, and completion registers the row's pages with
``PrefixCache`` by reference and drops the request's refcount.  Paged
admission is itself **batched**: every round drains the queue into all
free slots through one shared-grid prefill dispatch stream
(engine.prefill_requests) that packs the admitted requests' uncached
chunks one per grid row — K admitted requests cost
ceil(sum(chunks) / max_active) dispatches, not K chunk loops.  Dense
engines (recurrent mixers) keep the PR-1 ``(R, max_active, ...)`` cache
pool with scatter-on-admit / gather-on-finish and per-request
admission.

Admission keeps session stickiness semantics and a longest-prefix-match
preference (the node-local analogue of the HR-tree's group-level cache
affinity).  The match length is probed read-only via ``PrefixCache.peek``
ONCE at submit time and carried with the queued request — the admission
scan ranks on the cached hint instead of re-hashing every queued prompt on
every admission.
"""
from __future__ import annotations

import collections
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.engine import NgramDrafter, RealEngine, Request, Result
from repro.serving.trace import CLOCK


@dataclass
class _Slot:
    req: Request
    pos: int
    out: list = field(default_factory=list)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0       # first token, once there is one
    cached_tokens: int = 0
    pages: list = field(default_factory=list)   # paged engines only
    drafter: object = None                      # NgramDrafter (spec mode)


@dataclass
class _Queued:
    req: Request
    hint: int           # block-aligned prefix-cache match length at submit
    t_submit: float


class Scheduler:
    def __init__(self, engine: RealEngine, max_active: int = 4,
                 prefer_cache_hits: bool = True):
        self.engine = engine
        self.max_active = max_active
        self.prefer_cache_hits = prefer_cache_hits
        self.queue: collections.deque = collections.deque()
        self.slots: list[Optional[_Slot]] = [None] * max_active
        self.done: list[Result] = []
        self.metrics = {"admitted": 0, "completed": 0, "decode_calls": 0}
        self.rec = engine.recorder
        # speculative n-gram decode: one multi-token verify dispatch per
        # round instead of the one-token pool decode (paged engines only;
        # cfg.spec_enabled/spec_k are serving policy, not arch traits)
        self.spec = engine.spec
        self._spec_w = engine.cfg.spec_k + 1 if self.spec else 1
        # device state sits beside the engine's params and arena
        self._logits = jax.device_put(
            np.zeros((max_active, engine.cfg.padded_vocab), np.float32),
            engine.device)
        if engine.paged:
            # page-table pool: rows of physical page ids into the engine's
            # shared arena; 0 = scratch page (inactive / unallocated)
            self._cache = None
            self._ptab = np.zeros((max_active, engine.max_pages), np.int32)
        else:
            # dense pool: one batched cache pytree allocated once for the
            # engine's max_len
            self._cache = jax.device_put(
                engine.model.cache_zeros(max_active, engine.max_len),
                engine.device)
            self._ptab = None

    @property
    def active(self) -> list:
        return [s for s in self.slots if s is not None]

    def submit(self, req: Request):
        t_submit = CLOCK()
        hint = 0
        if self.prefer_cache_hits:
            with self.rec.span("prefix.peek", rid=req.req_id):
                hint, _ = self.engine.prefix_cache.peek(
                    [int(t) for t in req.tokens])
        self.queue.append(_Queued(req, hint, t_submit))

    def counters(self) -> dict:
        """The engine's counter snapshot with the scheduler's active
        rows."""
        return {**self.engine.counters(),
                "active": sum(s is not None for s in self.slots)}

    # ------------------------------------------------------------------
    def _pick_request(self) -> _Queued:
        ix = 0
        if self.prefer_cache_hits and len(self.queue) > 1:
            best, best_len = 0, -1
            for i, q in enumerate(self.queue):
                if q.hint > best_len:
                    best, best_len = i, q.hint
            ix = best
        q = self.queue[ix]
        del self.queue[ix]
        return q

    def _admit_one(self):
        free = next((i for i, s in enumerate(self.slots) if s is None), None)
        if free is None or not self.queue:
            return
        with self.rec.span("sched.admit"):
            q = self._pick_request()
            t = CLOCK()
            eng = self.engine
            st = eng.prefill_request(q.req)
            if eng.paged:
                # zero-copy admission: the slot row IS the page table —
                # shared prefix pages alias the cache holder's pages
                self._ptab[free, :] = 0
                self._ptab[free, :len(st.pages)] = st.pages
            else:
                self._cache = eng._slot_write(self._cache, st.cache, free)
            self._logits = self._logits.at[free].set(st.logits[0])
            self.slots[free] = _Slot(q.req, st.pos, t_submit=q.t_submit,
                                     t_admit=t, cached_tokens=st.matched,
                                     pages=st.pages or [],
                                     drafter=self._new_drafter(q.req))
            self.metrics["admitted"] += 1

    def _admit_batch(self):
        """Paged admission for a whole round: drain the queue into every
        free slot through ONE batched prefill-grid dispatch stream
        (engine.prefill_requests) — the admitted requests' uncached chunks
        fill the ``max_active``-row grid one per row, so K admitted
        requests cost ceil(sum(chunks) / max_active) dispatches instead of
        K separate chunk loops."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free or not self.queue:
            return
        with self.rec.span("sched.admit"):
            picked = []
            for slot in free:
                if not self.queue:
                    break
                picked.append((slot, self._pick_request()))
            t = CLOCK()
            states = self.engine.prefill_requests(
                [q.req for _, q in picked], batch=self.max_active)
            for (slot, q), st in zip(picked, states):
                self._ptab[slot, :] = 0
                self._ptab[slot, :len(st.pages)] = st.pages
                self._logits = self._logits.at[slot].set(st.logits[0])
                self.slots[slot] = _Slot(q.req, st.pos, t_submit=q.t_submit,
                                         t_admit=t, cached_tokens=st.matched,
                                         pages=st.pages or [],
                                         drafter=self._new_drafter(q.req))
                self.metrics["admitted"] += 1

    def _new_drafter(self, req: Request):
        return NgramDrafter(req.tokens) if self.spec else None

    # ------------------------------------------------------------------
    def step(self):
        """One continuous-batching round: admit into free slots, then ONE
        batched decode dispatch for every still-active slot.  The round is
        the recorder's span ``sched.step``; its record closes with the
        node's counters."""
        with self.rec.step(self.counters):
            self._round()

    def _round(self):
        if self.engine.paged:
            self._admit_batch()
        else:
            while self.queue and any(s is None for s in self.slots):
                self._admit_one()
        active_ix = [i for i, s in enumerate(self.slots) if s is not None]
        if not active_ix:
            return
        with self.rec.span("sched.read_tokens"):
            # the host waits here for the previous round's decode
            nxt = np.asarray(jnp.argmax(self._logits, axis=-1))
        now = CLOCK()
        finished, cont = [], []
        for i in active_ix:
            s = self.slots[i]
            tok = int(nxt[i])
            if len(s.out) < s.req.max_new:     # max_new=0 emits nothing,
                if not s.out:                  # matching generate()
                    s.t_first = now
                s.out.append(tok)
            if (tok == s.req.eos_id or len(s.out) >= s.req.max_new
                    or s.pos >= self.engine.max_len - 1):
                finished.append(i)
            else:
                cont.append(i)
        # retire completed rows BEFORE the pool decode.  Dense pool: the
        # batched dispatch writes every row, so a finished slot's KV must
        # be gathered first.  Paged pool: the finished row's pages must be
        # handed to the prefix cache (and its table row zeroed onto the
        # scratch page) before anything else dispatches.
        for i in finished:
            self._finish_slot(i)
        if not cont:
            return
        if self.spec:
            drafts = self._collect_drafts(cont)
            if any(drafts.values()):
                with self.rec.span("sched.verify"):
                    self._verify_round(cont, nxt, drafts)
                return
            # no slot drafted: the full (B, W, V) verify window would
            # commit exactly one token per row anyway — issue the cached
            # one-token pool decode instead (one extra cached trace, a
            # W-times smaller dispatch on novel text)
            self.engine.spec_draftless_rounds += 1
        self._decode_round(cont, nxt)

    def _decode_round(self, cont: list, nxt):
        """ONE one-token batched decode dispatch for the continuing rows
        (the non-speculative pool round, and the speculative scheduler's
        draft-less fallback)."""
        eng = self.engine
        B = self.max_active
        with self.rec.span("sched.decode_prep"):
            tok = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            act = np.zeros((B,), bool)
            for i in cont:
                tok[i, 0] = nxt[i]
                pos[i] = self.slots[i].pos
                act[i] = True
                if eng.paged:
                    # the write position may cross into a new block: grow
                    # the slot's pages before the single pool dispatch
                    s = self.slots[i]
                    eng.ensure_page_for(s.pages, s.pos)
                    self._ptab[i, :len(s.pages)] = s.pages
            ins = (jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(act))
            if eng.paged:
                ins = (jnp.asarray(self._ptab),) + ins
                eng.count_attention(pos, np.where(act, pos + 1, 0))
        with self.rec.span("sched.decode_dispatch"):
            if eng.paged:
                self._logits, eng.arena = eng._decode_batched(
                    eng.params, eng.arena, *ins)
            else:
                self._logits, self._cache = eng._decode_batched(
                    eng.params, self._cache, *ins)
        self.metrics["decode_calls"] += 1
        for i in cont:
            self.slots[i].pos += 1

    # ------------------------------------------------------------------
    # speculative n-gram decode (paged pool)
    # ------------------------------------------------------------------
    def _collect_drafts(self, cont: list) -> dict:
        """Feed each continuing slot's drafter and collect its proposal
        (possibly empty).  Separated from the verify dispatch so a round
        where NO slot drafted can fall back to the one-token pool decode
        instead of paying the full (B, W, V) verify window."""
        eng, W = self.engine, self._spec_w
        drafts: dict = {}
        for i in cont:
            s = self.slots[i]
            dr = s.drafter
            # feed the drafter every committed token (nxt is already in
            # s.out): its index covers prompt + generation so far
            n_new = len(s.req.tokens) + len(s.out) - len(dr.tokens)
            if n_new > 0:
                dr.extend(s.out[-n_new:])
            # drafting past max_new or max_len is wasted verify compute —
            # the accept loop below could never commit those tokens
            cap = min(W - 1, s.req.max_new - len(s.out),
                      eng.max_len - 1 - (s.pos + 1))
            drafts[i] = [int(t) for t in dr.draft(cap)]
            eng.spec_drafted += len(drafts[i])
        return drafts

    def _verify_round(self, cont: list, nxt, drafts: dict):
        """ONE multi-token verify dispatch for every continuing slot.

        Per row the window is [nxt, draft_1 .. draft_k] (k <= spec_k,
        ragged — rows with no n-gram match carry a bare one-token window)
        at positions pos .. pos+k.  The dispatch scatters the window's KV
        into the row's (append-only) pages and returns teacher-forced
        logits for every window position; the host accepts the longest
        draft prefix that matches greedy argmax, so outputs are token-
        identical to non-speculative decoding.  Rollback of rejected tail
        KV is pure bookkeeping: the row position simply doesn't advance
        over rejected tokens, the position mask hides their stale KV, and
        the next window overwrites it."""
        eng = self.engine
        B, W = self.max_active, self._spec_w
        tok = np.zeros((B, W), np.int32)
        pos = np.zeros((B,), np.int32)
        ntk = np.zeros((B,), np.int32)
        for i in cont:
            s = self.slots[i]
            d = drafts[i]
            n = 1 + len(d)
            tok[i, 0] = nxt[i]
            tok[i, 1:n] = d
            pos[i] = s.pos
            ntk[i] = n
            eng.ensure_page_for(s.pages, s.pos + n - 1)
            self._ptab[i, :len(s.pages)] = s.pages
        eng.count_attention(pos, np.where(ntk > 0, pos + W, 0))
        logits, eng.arena = eng._verify_paged_batched(
            eng.params, eng.arena, jnp.asarray(self._ptab),
            jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(ntk))
        eng.spec_dispatches += 1
        self.metrics["decode_calls"] += 1
        with self.rec.span("sched.read_tokens"):
            greedy = np.asarray(jnp.argmax(logits, axis=-1))  # (B, W)
        sel = np.zeros((B,), np.int32)     # per-row next-logits window index
        keep = np.zeros((B,), bool)
        finished = []
        for i in cont:
            s = self.slots[i]
            s.pos += 1                     # nxt committed at the old pos
            accepted = 0
            done = False
            for j, t in enumerate(drafts[i]):
                if t != int(greedy[i, j]):
                    break                  # rejected: greedy diverged here
                # accepted draft == the model's own next greedy token;
                # same append+finish checks a non-spec round would run
                s.out.append(t)
                eng.spec_accepted += 1
                accepted += 1
                if (t == s.req.eos_id or len(s.out) >= s.req.max_new
                        or s.pos >= eng.max_len - 1):
                    done = True            # finishing token: appended but
                    break                  # its KV position stays unclaimed
                s.pos += 1
            eng.spec_tokens += 1 + accepted
            if done:
                finished.append(i)
            else:
                sel[i] = accepted          # logits after the last committed
                keep[i] = True             # window token
        new = jnp.take_along_axis(
            logits, jnp.asarray(sel)[:, None, None], axis=1)[:, 0]
        self._logits = jnp.where(jnp.asarray(keep)[:, None], new,
                                 self._logits)
        for i in finished:
            self._finish_slot(i)

    def _finish_slot(self, i: int):
        s = self.slots[i]
        with self.rec.span("sched.retire", rid=s.req.req_id):
            self.slots[i] = None
            eng = self.engine
            # s.pos counts exactly the tokens whose KV is in the slot (the
            # finishing token was appended but never pool-decoded) —
            # inserting more would register block keys over positions that
            # hold nothing
            full = ([int(t) for t in s.req.tokens] + s.out)[:s.pos]
            with self.rec.span("prefix.insert"):
                if eng.paged:
                    eng.insert_prefix(full, s.pages)  # by reference
                else:
                    kv = eng._slot_read(self._cache, i)
                    eng.prefix_cache.insert(full, kv, eng._cache_nbytes(kv))
            if eng.paged:
                eng.release_pages(s.pages)
                self._ptab[i, :] = 0
            t = CLOCK()
            first = s.t_first if s.out else t
            self.done.append(Result(
                s.req.req_id, s.out, ttft=first - s.t_submit,
                total=t - s.t_submit, queued_s=s.t_admit - s.t_submit,
                cached_tokens=s.cached_tokens,
                prompt_tokens=len(s.req.tokens)))
            self.metrics["completed"] += 1

    def run(self, max_rounds: int = 10_000):
        rounds = 0
        while (self.queue or self.active) and rounds < max_rounds:
            self.step()
            rounds += 1
        return self.done

    # ------------------------------------------------------------------
    def kv_bytes_in_use(self) -> int:
        """Physical KV footprint of this pool: live pages for a paged
        engine, the full dense pool allocation otherwise (the dense pool
        holds max_active x max_len regardless of occupancy — the contrast
        bench_throughput reports)."""
        if self.engine.paged:
            return self.engine.live_kv_bytes()
        return self.engine._cache_nbytes(self._cache)
