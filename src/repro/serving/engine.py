"""Per-node LLM serving engines.

RealEngine — wraps a JAX model (repro.models.lm.LM): prefill + greedy/top-k
decode with KV-prefix reuse.  Pure-attention families serve from a **paged
KV pool**: a node-wide per-layer page arena (models/lm.py
``paged_arena_zeros``) plus per-request page tables, so a prefix-cache hit
*aliases* the holder's pages with a refcount bump (serving/page_pool)
instead of copying a cache pytree — admission is O(suffix), not O(cache
bytes), and KV memory scales with live tokens.  Recurrent families
(mamba/xLSTM) fall back to the dense batch-1 cache path.  This is the
node-local mechanism whose *group-wide* version the HR-tree provides.

LatencyEngine — a calibrated cost model (prefill/decode tokens-per-second,
continuous-batching slots) for overlay-scale simulations where running a
real model per node would be CPU-prohibitive; calibrated against RealEngine
on the reduced config (see benchmarks/bench_serving_latency.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.models.lm import (arena_gather_pages, arena_scatter_pages,
                             cache_slot_read, cache_slot_write)
from repro.serving.page_pool import OutOfPages, PageAllocator, PagedHandle
from repro.serving.prefix_cache import BLOCK, PrefixCache
from repro.serving.trace import CLOCK, Recorder
from repro.training.compression import (compress_kv_blocks,
                                        decompress_kv_blocks)


@dataclass
class Request:
    req_id: int
    tokens: list
    max_new: int = 32
    eos_id: int = -1
    session: Optional[str] = None
    arrival: float = 0.0


@dataclass
class Result:
    """A finished request.  ``ttft`` and ``total`` are the seconds to its
    first token (read to the host) and to its finish, from
    ``Scheduler.submit`` (or from the call of ``generate``); ``queued_s``
    is submit -> admission (0 for ``generate``)."""
    req_id: int
    output: list
    ttft: float = 0.0
    total: float = 0.0
    queued_s: float = 0.0
    cached_tokens: int = 0
    prompt_tokens: int = 0


class NgramDrafter:
    """Self-speculative prompt-lookup drafter (no draft model).

    Indexes every n-gram (n <= ``max_n``) of a request's prompt plus its
    committed generation, mapping it to the position right after its most
    recent occurrence *that has a continuation*.  ``draft(k)`` matches the
    longest indexed suffix of the context and proposes the k tokens that
    followed it last time — fully deterministic, so speculative decode
    stays token-identical to greedy decoding (drafts are only accepted
    when they equal the model's own argmax) and CI can gate the accept
    counters.  Repetitive streams (templates, code, loops — including the
    model's own greedy cycles) draft well; novel text drafts nothing and
    the verify window degenerates to a normal one-token decode."""

    __slots__ = ("tokens", "index", "max_n")

    def __init__(self, tokens, max_n: int = 3):
        self.tokens: list = []
        self.index: dict = {}
        self.max_n = max_n
        self.extend(tokens)

    def extend(self, toks):
        """Append committed tokens, indexing n-grams as they gain a
        continuation (an n-gram ending at the stream head has nothing to
        propose yet, so it is indexed when the next token arrives)."""
        for t in toks:
            pos = len(self.tokens)
            for n in range(1, self.max_n + 1):
                if pos >= n:
                    self.index[tuple(self.tokens[pos - n:pos])] = pos
            self.tokens.append(int(t))

    def draft(self, k: int) -> list:
        """Up to ``k`` proposed continuation tokens (possibly fewer when
        the match sits near the stream head; empty on no match)."""
        if k <= 0:
            return []
        for n in range(min(self.max_n, len(self.tokens)), 0, -1):
            cont = self.index.get(tuple(self.tokens[-n:]))
            if cont is not None:
                return self.tokens[cont:cont + k]
        return []


@dataclass
class PrefillState:
    """Slot-ready request state positioned at ``pos`` with the logits of
    the last prompt token.  Dense engines carry a batch-1 cache pytree in
    ``cache``; paged engines carry the request's physical page list in
    ``pages`` (the KV itself lives in the engine's shared arena)."""
    cache: object
    logits: object      # (1, padded_vocab)
    pos: int
    matched: int        # prefix-cache tokens reused
    pages: Optional[list] = None


class RealEngine:
    def __init__(self, cfg, model, params, cache_bytes: int = 1 << 30,
                 max_len: int = 1024, paged: Optional[bool] = None,
                 num_pages: Optional[int] = None):
        self.cfg = cfg
        self.model = model
        # serve in the compute dtype: a float32 master copy of a full-width
        # model does not fit one chip beside its KV arena
        self.params = jax.tree.map(
            lambda a: a.astype(cfg.compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
        # the engine's state lives where its params do: nodes sharing a
        # host each keep their arena on their own chip
        self.device = next(iter(jax.tree.leaves(self.params)[0].devices()))
        self.max_len = max_len
        self.prefix_cache = PrefixCache(cache_bytes)
        # partial-prefix KV reuse is an attention-cache property: a slot per
        # position, masked by pos.  Recurrent states (mamba/mLSTM/sLSTM)
        # summarize the WHOLE stream and cannot be truncated — those
        # families only reuse on exact full-prefix hits (disabled here).
        self.partial_reuse = all(s.mixer in ("attn", "cross_attn")
                                 for s in cfg.pattern)
        self.batched_traces = 0   # compilations of the slot-pool decode
        self.batched_prefill_traces = 0   # compilations of batched admission
        self.prefill_dispatches = 0       # jitted prefill_paged calls issued
        self.prefill_tokens = 0           # real (non-pad) tokens prefilled
        self.prefill_slots = 0            # grid rows x BLOCK dispatched
        self.pages_evicted = 0            # pages freed by allocator pressure
        # paged attention: live pages the attention visits per row, and
        # rows x table width, summed over dispatches (count_attention)
        self.attn_pages_read = 0
        self.attn_pages_tabled = 0
        # spans and step records of this node (serving/trace.py)
        self.recorder = Recorder()
        # speculative decode counters (scheduler-driven verify rounds)
        self.spec_traces = 0      # compilations of the batched verify
        self.spec_dispatches = 0  # verify_paged dispatches issued
        self.spec_tokens = 0      # tokens committed by verify rounds
        self.spec_drafted = 0     # draft tokens proposed
        self.spec_accepted = 0    # draft tokens accepted (== model argmax)
        self.spec_draftless_rounds = 0  # rounds served by the one-token
                                        # pool decode (no slot drafted)
        # cross-node KV page migration counters (overlay Replicator)
        self.kv_exported_pages = 0   # pages shipped to fetching peers
        self.kv_imported_pages = 0   # pages scattered in from peers
        # wire codec for exported pages (training/compression.py):
        # "fp16" | "int8" | "raw".  fp16 halves f32 arenas; 16-bit arenas
        # (bf16) ship raw — same bytes, and a bf16 -> fp16 cast would
        # overflow |v| > 65504 to inf for zero wire savings
        self.kv_wire_mode = ("fp16" if cfg.compute_dtype.itemsize == 4
                             else "raw")
        # paged KV pool: pure-attention families only (recurrent mixers
        # have O(1) state — nothing to page)
        self.paged = (model.supports_paging() if paged is None
                      else bool(paged) and model.supports_paging())
        # speculative decode needs per-position KV to roll back by position
        # — paged pool only; dense/recurrent engines fall back to one
        # token per round
        self.spec = bool(self.paged and cfg.spec_enabled and cfg.spec_k > 0)
        self.block = BLOCK
        if self.paged:
            self.max_pages = -(-max_len // BLOCK)     # table width (ceil)
            # page 0 is scratch; default arena fits ~16 max_len streams —
            # under pressure the prefix cache is evicted page-by-page
            self.num_pages = num_pages or (1 + 16 * self.max_pages)
            self.allocator = PageAllocator(self.num_pages)
            self.arena = jax.jit(
                model.paged_arena_zeros, static_argnums=(0, 1),
                out_shardings=SingleDeviceSharding(self.device))(
                    self.num_pages, BLOCK)
            self.page_bytes = sum(
                x.shape[0] * BLOCK * x.shape[3] * x.dtype.itemsize
                for x in jax.tree.leaves(self.arena))
            self.prefix_cache.on_release = \
                lambda h: self.allocator.decref(h.pages)
            # the widest window of any layer: the pages a row's attention
            # visits in at least one layer (None: the whole prefix)
            wins = [sp.window for sp in cfg.pattern]
            self.attn_window = None if None in wins else max(wins)

        def _prefill(params, tokens):
            return model.prefill(params, tokens, max_len=max_len,
                                 block_q=64)

        def _decode(params, cache, tok, pos):
            return model.decode(params, cache, tok, pos)

        def _decode_batched(params, cache, tok, pos, active):
            self.batched_traces += 1   # trace-time side effect only
            return model.decode(params, cache, tok, pos, active=active)

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)
        self._decode_batched = jax.jit(_decode_batched)
        self._slot_write = jax.jit(cache_slot_write)
        self._slot_read = jax.jit(cache_slot_read)
        if self.paged:
            # donate the arena so scatters update it in place where the
            # backend supports donation (CPU silently copies)
            donate = () if jax.default_backend() == "cpu" else (1,)

            def _prefill_paged(params, arena, pt, tok, pos0):
                return model.prefill_paged(params, arena, pt, tok, pos0)

            def _decode_paged(params, arena, pt, tok, pos):
                return model.decode_paged(params, arena, pt, tok, pos)

            def _query_paged(params, arena, pt, tok, pos):
                logits, _ = model.decode_paged(params, arena, pt, tok, pos,
                                               write=False)
                return logits

            def _decode_paged_batched(params, arena, pt, tok, pos, active):
                self.batched_traces += 1   # trace-time side effect only
                return model.decode_paged(params, arena, pt, tok, pos,
                                          active=active)

            def _prefill_paged_batched(params, arena, pt, tok, pos0,
                                       active):
                self.batched_prefill_traces += 1   # trace-time only
                return model.prefill_paged(params, arena, pt, tok, pos0,
                                           active=active)

            def _verify_paged_batched(params, arena, pt, tok, pos, n_tok):
                self.spec_traces += 1   # trace-time side effect only
                return model.verify_paged(params, arena, pt, tok, pos,
                                          n_tok=n_tok)

            self._prefill_paged = jax.jit(_prefill_paged,
                                          donate_argnums=donate)
            self._verify_paged_batched = jax.jit(_verify_paged_batched,
                                                 donate_argnums=donate)
            self._prefill_paged_batched = jax.jit(_prefill_paged_batched,
                                                  donate_argnums=donate)
            self._decode_paged = jax.jit(_decode_paged,
                                         donate_argnums=donate)
            self._query_paged = jax.jit(_query_paged)
            # page-import scatter: donate the arena so landing replicated
            # pages updates in place instead of copying every layer's
            # whole arena per import (arena is arg 0 here, not arg 1)
            self._scatter_pages = jax.jit(
                arena_scatter_pages,
                donate_argnums=() if not donate else (0,))
            # same attribute as the dense pool decode on purpose: the
            # scheduler (and dispatch-count tests) treat "the one batched
            # decode" uniformly across modes
            self._decode_batched = jax.jit(_decode_paged_batched,
                                           donate_argnums=donate)

    def _cache_nbytes(self, cache) -> int:
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))

    @property
    def spec_accept_rate(self) -> float:
        """Fraction of proposed draft tokens the model accepted (0 until
        the first draft) — broadcast by ModelNode alongside kv_pressure."""
        return (self.spec_accepted / self.spec_drafted
                if self.spec_drafted else 0.0)

    # ------------------------------------------------------------------
    # paged-pool page management (host side)
    # ------------------------------------------------------------------
    def alloc_pages(self, n: int = 1) -> list:
        """Allocate ``n`` pages, evicting LRU prefix-cache entries under
        pressure (their pages free once no live request aliases them)."""
        while True:
            try:
                return self.allocator.alloc(n)
            except OutOfPages:
                free = self.allocator.free_count
                with self.recorder.span("pages.evict"):
                    popped = self.prefix_cache.pop_lru()
                self.pages_evicted += self.allocator.free_count - free
                if not popped:
                    raise

    def release_pages(self, pages):
        self.allocator.decref(pages)

    def ensure_page_for(self, pages: list, pos: int):
        """Grow ``pages`` so the block holding position ``pos`` exists
        (called before every decode write that may cross into a new
        block)."""
        while len(pages) <= pos // self.block:
            pages.extend(self.alloc_pages(1))

    def page_table_row(self, pages) -> np.ndarray:
        """(1, max_pages) int32 page-table row; unallocated logical blocks
        point at the scratch page 0 and are masked by position."""
        row = np.zeros((1, self.max_pages), np.int32)
        row[0, :len(pages)] = pages
        return row

    def insert_prefix(self, full_tokens, pages):
        """Zero-copy prefix-cache insert: the entry holds page ids (one
        extra reference each), never KV bytes."""
        n_cov = len(full_tokens) // self.block
        if not n_cov:
            return
        covered = list(pages[:n_cov])
        self.allocator.incref(covered)
        handle = PagedHandle(tuple(covered), n_cov * self.block)
        self.prefix_cache.insert(full_tokens, handle,
                                 n_cov * self.page_bytes)

    def count_attention(self, pos0, lengths):
        """Count one paged-attention dispatch from the positions the host
        already holds: ``attn_pages_read`` gains the pages each row's
        attention visits (its queries start at ``pos0``, its keys lie
        below ``lengths``, 0 for a dead row; the window trims the front)
        and ``attn_pages_tabled`` the rows x the table's width, which the
        gather of the jnp path reads whatever the lengths."""
        pos0, lengths = np.asarray(pos0), np.asarray(lengths)
        last = np.minimum((lengths - 1) // self.block, self.max_pages - 1)
        first = (0 if self.attn_window is None else
                 np.maximum(pos0 - self.attn_window + 1, 0) // self.block)
        self.attn_pages_read += int(
            np.where(lengths > 0, last - first + 1, 0).sum())
        self.attn_pages_tabled += lengths.size * self.max_pages

    def counters(self) -> dict:
        """The counters and gauges that the recorder's step records keep:
        those its readers use (the prefill grid's fill, the pool's pages
        in use) and the pool's evictions."""
        c = {"prefill_tokens": self.prefill_tokens,
             "prefill_slots": self.prefill_slots}
        if self.paged:
            c.update(pages_used=self.allocator.used_count,
                     pages_total=self.num_pages - 1,
                     pages_evicted=self.pages_evicted)
        return c

    def live_kv_bytes(self) -> int:
        """Physical KV footprint: pages in use x bytes per page (aliased
        pages counted once — the point of the paged pool)."""
        if not self.paged:
            return self.prefix_cache.used_bytes
        return self.allocator.used_count * self.page_bytes

    # ------------------------------------------------------------------
    # cross-node page migration (overlay kv_fetch / kv_pages)
    # ------------------------------------------------------------------
    def export_pages(self, handle: PagedHandle, depth: Optional[int] = None,
                     mode: Optional[str] = None) -> dict:
        """Gather the first ``depth`` pages of a prefix entry out of the
        per-layer arenas into a host-side wire buffer.

        Read-only: aliased pages are never mutated and no refcounts move
        — the holder keeps serving from (and may later evict) the same
        physical pages while a copy ships.  ``mode`` picks the wire codec
        (``kv_wire_mode`` default); the buffer is a pure dict of bytes /
        ints so the overlay can msgpack + chunk it."""
        assert self.paged, "page export requires the paged pool"
        if depth is not None:
            handle = handle.prefix(depth, self.block)
        pages = list(handle.pages)
        assert pages, "empty page export"
        mode = mode or self.kv_wire_mode
        gathered = arena_gather_pages(self.arena, pages)
        layers = [{n: compress_kv_blocks(layer[n], mode) for n in ("k", "v")}
                  for layer in gathered]
        self.kv_exported_pages += len(pages)
        return {"n_pages": len(pages), "mode": mode, "layers": layers}

    def import_pages(self, buf: dict, chains: list) -> PagedHandle:
        """Allocate local pages, scatter a peer's exported K/V blocks into
        the arenas, and register the prefix in ``PrefixCache`` under its
        BLOCK-chain digests — the next admission aliases it exactly as if
        this node had prefilled it (zero prefill dispatches for the
        replicated blocks).

        ``chains`` is the request's digest chain covering the buffer
        (``chains[i]`` keys blocks 0..i).  Raises ``OutOfPages`` when the
        arena cannot host the pages even after LRU eviction; any pages
        allocated before the failure are released — a failed import
        leaves allocator and arena exactly as they were, and the caller
        falls back to plain prefill.  A buffer that is not an export of
        this model's arena (garbled, short, wrong shape) raises
        ValueError before a page is taken; an error from the device
        scatter propagates as it is."""
        assert self.paged, "page import requires the paged pool"
        # the whole buffer is parsed on the host before a page is taken:
        # whatever a peer's bytes make the decoder raise is a bad payload
        try:
            n = int(buf["n_pages"])
            blocks = tuple(
                {name: decompress_kv_blocks(layer[name],
                                            self.cfg.compute_dtype)
                 for name in ("k", "v")}
                for layer in buf["layers"])
        except Exception as e:
            raise ValueError(f"malformed page buffer: {e!r}") from e
        chains = list(chains)[:n]
        if n < 1 or len(chains) < n:
            raise ValueError(f"import of {n} pages with {len(chains)} "
                             f"chain digests")
        want = [{name: a.shape[:1] + (n,) + a.shape[2:]
                 for name, a in layer.items()} for layer in self.arena]
        got = [{name: b.shape for name, b in layer.items()}
               for layer in blocks]
        if got != want:
            raise ValueError(f"page buffer shapes {got} do not fit "
                             f"the arena ({want})")
        pages = self.alloc_pages(n)
        try:
            self.arena = self._scatter_pages(
                self.arena, jnp.asarray(pages, jnp.int32), blocks)
        except BaseException:
            self.allocator.decref(pages)     # released, never registered
            raise
        handle = PagedHandle(tuple(pages), n * self.block)
        # the pages' initial reference becomes the cache entry's (its
        # on_release decref balances the alloc above)
        self.prefix_cache.insert_chains(chains, handle,
                                        n * self.page_bytes)
        self.kv_imported_pages += n
        return handle

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def prefill_request(self, req: Request) -> PrefillState:
        """Prefix-cache match + prefill of the uncached suffix.

        Shared by the sequential ``generate`` path and slot-pool admission
        (serving/scheduler.py); returns a batch-1 slot-ready state.  Paged
        engines alias a hit's pages (refcount bump, no KV copy) and run
        the suffix through the chunked paged prefill; dense engines keep
        the PR-1 boot-prefill + teacher-forced decode-append replay."""
        if self.paged:
            return self._prefill_request_paged(req)
        toks = [int(t) for t in req.tokens]
        matched, entry = self.prefix_cache.match(toks)
        if entry is not None and matched >= 8 and self.partial_reuse:
            cache, pos, suffix = entry.handle, matched, toks[matched:]
        else:
            matched = 0
            boot = max(1, min(len(toks), 8))
            _, cache = self._prefill(
                self.params, jnp.asarray([toks[:boot]], jnp.int32))
            pos, suffix = boot, toks[boot:]
        # teacher-forced decode-append over the (uncached) suffix
        logits = None
        for t in suffix:
            logits, cache = self._decode(
                self.params, cache, jnp.asarray([[t]], jnp.int32),
                jnp.asarray([pos], jnp.int32))
            pos += 1
        if logits is None:  # full prefix hit: replay last token for logits
            logits, cache = self._decode(
                self.params, cache, jnp.asarray([[toks[-1]]], jnp.int32),
                jnp.asarray([pos - 1], jnp.int32))
        return PrefillState(cache, logits, pos, matched)

    def _match_and_alias(self, toks: list) -> tuple[int, list]:
        """Prefix-cache match + zero-copy alias of the hit's pages.

        Returns (matched, pages): ``matched`` block-aligned tokens whose
        KV the request reuses by reference (refcount bump — zero KV bytes
        move), ``pages`` the aliased physical pages."""
        matched, entry = self.prefix_cache.match(toks)
        if (entry is not None and isinstance(entry.handle, PagedHandle)
                and matched >= self.block):
            shared = list(entry.handle.pages[:matched // self.block])
            self.allocator.incref(shared)        # zero-copy alias
            return matched, shared
        return 0, []

    def _prefill_request_paged(self, req: Request) -> PrefillState:
        """Paged admission: alias cached pages, chunk-prefill the suffix.

        A hit contributes its pages by reference (refcount bump — zero KV
        bytes move); the uncached suffix is processed in BLOCK-token
        teacher-forced chunks, each ONE dispatch that scatters the chunk's
        K/V into a fresh page and attends over the whole page table —
        admission cost is O(suffix), never O(cached prefix)."""
        toks = [int(t) for t in req.tokens]
        matched, pages = self._match_and_alias(toks)
        pos = matched
        logits_last = None
        try:
            pos, logits_last = self._prefill_chunks(toks, pages, pos)
        except BaseException:
            if pages:                # release aliased + fresh references
                self.allocator.decref(pages)
            raise
        if logits_last is None:
            logits_last = self._replay_last_token(toks, pages, pos)
        return PrefillState(None, logits_last, pos, matched, pages=pages)

    def _replay_last_token(self, toks, pages, pos):
        """Block-aligned prompt fully cached: query-only replay of the
        last token — aliased pages are never written."""
        pt = jnp.asarray(self.page_table_row(pages))
        self.count_attention([pos - 1], [pos])
        return self._query_paged(
            self.params, self.arena, pt,
            jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray([pos - 1], jnp.int32))

    def _prefill_chunks(self, toks, pages, pos):
        blk = self.block
        logits_last = None
        while pos < len(toks):
            pages.extend(self.alloc_pages(1))
            # pad tail of the last partial chunk: pad logits are ignored
            # and pad K/V is overwritten by later decode writes before any
            # position mask exposes it
            chunk = toks[pos:pos + blk]
            buf = chunk + [0] * (blk - len(chunk))
            with self.recorder.span("engine.prefill_dispatch", rows=1):
                pt = jnp.asarray(self.page_table_row(pages))
                logits, self.arena = self._prefill_paged(
                    self.params, self.arena, pt,
                    jnp.asarray([buf], jnp.int32),
                    jnp.asarray([pos], jnp.int32))
            self.prefill_dispatches += 1
            self.prefill_tokens += len(chunk)
            self.prefill_slots += blk
            self.count_attention([pos], [pos + blk])
            logits_last = logits[:, len(chunk) - 1]
            pos += len(chunk)
        return pos, logits_last

    # ------------------------------------------------------------------
    # batched admission (paged): one dispatch stream for a whole round
    # ------------------------------------------------------------------
    def prefill_requests(self, reqs: list, batch: Optional[int] = None
                         ) -> list:
        """Batched paged admission: every request's uncached chunks are
        packed into ONE shared ``batch``-row, BLOCK-token grid.

        Prefix hits alias cached pages first exactly like
        ``prefill_request``.  The uncached chunks then queue request
        after request, and each ``prefill_paged`` dispatch takes the next
        ``batch`` of them, one per row, so one request may fill several
        rows.  Each of its rows carries the request's whole page table (a
        fresh page per chunk, allocated before the dispatch) and starts
        at its own chunk: every layer scatters all rows' K/V before any
        row attends, so a later chunk reads its earlier siblings' K/V and
        the causal (and window) mask hides the later ones — exactly what
        sequential chunks compute.  K admitted requests cost
        ``ceil(sum(chunks) / batch)`` dispatches of the one compiled grid;
        only an admission's last dispatch has masked rows.  A request's
        logits come from the row and offset of its last chunk; a prompt
        that is a full block-aligned hit takes no row and replays its
        last token query-only.

        Returns one ``PrefillState`` per request, in input order."""
        assert self.paged, "batched admission requires the paged pool"
        if not reqs:
            return []
        B = batch or len(reqs)
        assert len(reqs) <= B
        blk = self.block
        rows = []
        try:
            for req in reqs:
                toks = [int(t) for t in req.tokens]
                matched, pages = self._match_and_alias(toks)
                rows.append({"toks": toks, "pages": pages, "pos": matched,
                             "matched": matched, "logits": None})
            chunks = [(r, p) for r in rows
                      for p in range(r["pos"], len(r["toks"]), blk)]
            for d in range(0, len(chunks), B):
                grid = chunks[d:d + B]
                with self.recorder.span("engine.prefill_dispatch",
                                        rows=len(grid)):
                    for r, _ in grid:    # whole tables before any row
                        r["pages"].extend(self.alloc_pages(1))
                    tok = np.zeros((B, blk), np.int32)
                    pos0 = np.zeros((B,), np.int32)
                    act = np.zeros((B,), bool)
                    ptab = np.zeros((B, self.max_pages), np.int32)
                    ends = []                # (grid row, last offset, req)
                    for i, (r, p) in enumerate(grid):
                        chunk = r["toks"][p:p + blk]
                        tok[i, :len(chunk)] = chunk
                        pos0[i] = p
                        act[i] = True
                        ptab[i, :len(r["pages"])] = r["pages"]
                        r["pos"] = p + len(chunk)
                        if r["pos"] == len(r["toks"]):
                            ends.append((i, len(chunk), r))
                        self.prefill_tokens += len(chunk)
                    logits, self.arena = self._prefill_paged_batched(
                        self.params, self.arena, jnp.asarray(ptab),
                        jnp.asarray(tok), jnp.asarray(pos0),
                        jnp.asarray(act))
                self.prefill_dispatches += 1
                self.prefill_slots += B * blk
                self.count_attention(pos0, np.where(act, pos0 + blk, 0))
                for i, off, r in ends:
                    r["logits"] = logits[i:i + 1, off - 1]
        except BaseException:
            for r in rows:           # release aliased + fresh references
                if r["pages"]:
                    self.allocator.decref(r["pages"])
            raise
        out = []
        for r in rows:
            if r["logits"] is None:  # full block-aligned hit
                r["logits"] = self._replay_last_token(
                    r["toks"], r["pages"], r["pos"])
            out.append(PrefillState(None, r["logits"], r["pos"],
                                    r["matched"], pages=r["pages"]))
        return out

    # ------------------------------------------------------------------
    # sequential generation
    # ------------------------------------------------------------------
    def generate(self, req: Request, now: float = 0.0) -> Result:
        """One-slot sequential decode (thin wrapper over prefill_request)."""
        if self.paged:
            return self._generate_paged(req)
        t0 = CLOCK()
        st = self.prefill_request(req)
        cache, logits, pos = st.cache, st.logits, st.pos
        ttft, out = None, []
        for _ in range(req.max_new):
            nxt = int(jnp.argmax(logits[0]))
            if ttft is None:
                ttft = CLOCK() - t0
            out.append(nxt)
            if nxt == req.eos_id or pos >= self.max_len - 1:
                break
            logits, cache = self._decode(
                self.params, cache, jnp.asarray([[nxt]], jnp.int32),
                jnp.asarray([pos], jnp.int32))
            pos += 1
        # insert only the KV-covered prefix: after an eos/len break the last
        # appended token was never decoded, so its position holds no KV —
        # pos counts exactly the tokens whose state is in the cache
        full = ([int(t) for t in req.tokens] + out)[:pos]
        self.prefix_cache.insert(full, cache, self._cache_nbytes(cache))
        return self._result(req, out, t0, ttft, st.matched)

    def _generate_paged(self, req: Request) -> Result:
        t0 = CLOCK()
        st = self.prefill_request(req)
        pages, logits, pos = st.pages, st.logits, st.pos
        ttft, out = None, []
        try:
            for _ in range(req.max_new):
                nxt = int(jnp.argmax(logits[0]))
                if ttft is None:
                    ttft = CLOCK() - t0
                out.append(nxt)
                if nxt == req.eos_id or pos >= self.max_len - 1:
                    break
                self.ensure_page_for(pages, pos)
                self.count_attention([pos], [pos + 1])
                logits, self.arena = self._decode_paged(
                    self.params, self.arena,
                    jnp.asarray(self.page_table_row(pages)),
                    jnp.asarray([[nxt]], jnp.int32),
                    jnp.asarray([pos], jnp.int32))
                pos += 1
            full = ([int(t) for t in req.tokens] + out)[:pos]
            self.insert_prefix(full, pages)  # zero-copy (page refs)
        finally:
            self.release_pages(pages)        # request's own reference
        return self._result(req, out, t0, ttft, st.matched)

    @staticmethod
    def _result(req: Request, out: list, t0: float, ttft, matched: int):
        """``generate``'s Result; with no token, ``ttft`` is the total."""
        total = CLOCK() - t0
        return Result(req.req_id, out, ttft=total if ttft is None else ttft,
                      total=total, cached_tokens=matched,
                      prompt_tokens=len(req.tokens))


@dataclass
class LatencyEngineConfig:
    prefill_tps: float = 8_000.0     # prompt tokens/s (single request)
    decode_tps: float = 60.0         # generated tokens/s per request
    batch_slots: int = 8             # continuous-batching concurrency
    overhead_s: float = 0.02
    hw_score: float = 5.0            # the paper's 1..10 capacity score


class LatencyEngine:
    """Deterministic continuous-batching cost model on the simnet clock.

    ``submit`` returns (ttft, completion_time_offset, cached_tokens) given
    the current queue state; slot release is the caller's responsibility
    via the returned completion offset (model_node schedules it)."""

    def __init__(self, ecfg: LatencyEngineConfig,
                 cache_bytes: int = 1 << 28):
        self.ecfg = ecfg
        self.prefix_cache = PrefixCache(cache_bytes)
        self.busy: list[float] = []       # completion times of active slots

    def service_times(self, n_prompt: int, n_cached: int, n_out: int,
                      now: float) -> tuple[float, float]:
        e = self.ecfg
        scale = e.hw_score / 5.0
        # slot admission: wait for a free slot if all are busy
        self.busy = [t for t in self.busy if t > now]
        if len(self.busy) >= e.batch_slots:
            start = sorted(self.busy)[len(self.busy) - e.batch_slots]
        else:
            start = now
        # batching interference: decode tps degrades with occupancy
        occupancy = min(len(self.busy) + 1, e.batch_slots)
        interference = 1.0 + 0.15 * (occupancy - 1)
        t_prefill = (n_prompt - n_cached) / (e.prefill_tps * scale)
        t_decode = n_out * interference / (e.decode_tps * scale)
        ttft = (start - now) + e.overhead_s + t_prefill
        total = ttft + t_decode
        self.busy.append(now + total)
        return ttft, total
