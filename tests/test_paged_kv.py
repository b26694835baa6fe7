"""Paged KV pool: allocator invariants, dense-vs-paged token parity, and
zero-copy prefix sharing (aliased pages, refcount assertions).

Deliberately hypothesis-free so it runs even without dev extras installed;
the hypothesis property suite for the allocator lives in
tests/test_page_pool_props.py.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models.lm import build_model
from repro.serving.engine import RealEngine, Request
from repro.serving.page_pool import (NULL_PAGE, OutOfPages, PageAllocator,
                                     PagedHandle)
from repro.serving.prefix_cache import BLOCK
from repro.serving.scheduler import Scheduler


@pytest.fixture(scope="module")
def gt():
    cfg = base.get_config("gentorrent-llama3-8b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, n, lengths=(20, 40, 36, 33, 64)):
    return [[(37 * i + j) % cfg.vocab
             for j in range(lengths[i % len(lengths)])] for i in range(n)]


# ----------------------------------------------------------- allocator
def test_allocator_basic_lifecycle():
    a = PageAllocator(8)
    p = a.alloc(3)
    assert len(set(p)) == 3 and NULL_PAGE not in p
    assert a.free_count == 4 and a.used_count == 3
    a.incref(p[:1])
    a.decref(p)                      # p[0] still held by the alias
    assert a.refcount(p[0]) == 1 and a.free_count == 6
    a.decref(p[:1])
    assert a.free_count == 7
    a.check()


def test_allocator_errors():
    a = PageAllocator(4)
    with pytest.raises(OutOfPages):
        a.alloc(4)                   # page 0 is reserved scratch
    p = a.alloc(1)
    a.decref(p)
    with pytest.raises(ValueError):
        a.decref(p)                  # double free
    with pytest.raises(ValueError):
        a.incref(p)                  # incref of a free page
    with pytest.raises(ValueError):
        a.incref([NULL_PAGE])        # scratch is never referenceable
    a.check()


def test_allocator_randomized_invariants():
    """Deterministic random churn: model refcounts in pure python and
    check the allocator agrees; aliased pages survive their allocator."""
    random.seed(11)
    a = PageAllocator(32)
    live = {}                        # page -> model refcount
    for _ in range(2000):
        op = random.random()
        if op < 0.4 and a.free_count:
            n = random.randint(1, min(3, a.free_count))
            for p in a.alloc(n):
                live[p] = 1
        elif op < 0.6 and live:
            p = random.choice(list(live))
            a.incref([p])
            live[p] += 1
        elif live:
            p = random.choice(list(live))
            a.decref([p])
            live[p] -= 1
            if not live[p]:
                del live[p]
        for p, rc in live.items():
            assert a.refcount(p) == rc
        assert a.used_count == len(live)
        a.check()


# ------------------------------------------------------- the paged step
# (write, W, live row's position, live row's real tokens, live row's
# pages the window covers): the grid's page-aligned chunk, the decode
# step's one token, and a ragged verify window across a page boundary
# whose pad token goes to the scratch page
WRITES = {"page": ("page", BLOCK, BLOCK, BLOCK, {2}),
          "tokens-1": ("tokens", 1, BLOCK + 5, 1, {2}),
          "tokens-3": ("tokens", 3, 2 * BLOCK - 1, 2, {2, 3}),
          "replay": (None, 1, BLOCK + 5, 1, set())}


@pytest.mark.parametrize("case", list(WRITES))
def test_step_writes_only_its_window_pages(gt, case):
    """One ``step_paged`` over a live row and a dead one whose table
    names live pages: the dead row changes only the scratch page 0, the
    live row only the pages its window covers, and the replay no page."""
    cfg, model, params = gt
    write, W, pos, n, covered = WRITES[case]
    arena = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(1), a.shape, a.dtype),
        model.paged_arena_zeros(9, BLOCK))
    pt = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    tok = np.asarray([[(7 * m + 3) % cfg.vocab for m in range(W)]] * 2,
                     np.int32)
    pos0 = np.asarray([pos, BLOCK if write == "page" else BLOCK + 9],
                      np.int32)
    logits, after = model.step_paged(
        params, arena, jnp.asarray(pt), jnp.asarray(tok), jnp.asarray(pos0),
        n_tok=jnp.asarray([n, 0], jnp.int32), write=write)
    assert logits.shape == (2, W, cfg.padded_vocab)
    want = covered | ({NULL_PAGE} if write else set())
    for b, a in zip(jax.tree.leaves(arena), jax.tree.leaves(after)):
        diff = np.asarray(b != a).any(axis=(0, 3))          # (P, BLOCK)
        assert set(np.flatnonzero(diff.any(axis=1))) == want
        for page in covered:         # within a live page: its window only
            first = (pt[0].tolist().index(page)) * BLOCK
            offs = {p - first for p in range(pos, pos + n)
                    if first <= p < first + BLOCK}
            assert set(np.flatnonzero(diff[page])) == offs


# ------------------------------------------------------- parity vs dense
def test_paged_generate_matches_dense(gt):
    """Same model, same requests: the paged engine's outputs are token-
    identical to the PR-1 dense path (miss path: chunked paged prefill +
    paged decode vs boot prefill + dense decode)."""
    cfg, model, params = gt
    dense = RealEngine(cfg, model, params, max_len=128, paged=False)
    paged = RealEngine(cfg, model, params, max_len=128)
    assert paged.paged and not dense.paged
    for i, p in enumerate(_prompts(cfg, 5)):
        rd = dense.generate(Request(i, p, max_new=8))
        rp = paged.generate(Request(i, p, max_new=8))
        assert rd.output == rp.output


def test_paged_scheduler_matches_dense_scheduler(gt):
    cfg, model, params = gt
    prompts = _prompts(cfg, 6)
    ref = {}
    eng_d = RealEngine(cfg, model, params, max_len=128, paged=False)
    sd = Scheduler(eng_d, max_active=3)
    for i, p in enumerate(prompts):
        sd.submit(Request(i, p, max_new=8))
    ref = {r.req_id: r.output for r in sd.run()}

    eng_p = RealEngine(cfg, model, params, max_len=128)
    sp = Scheduler(eng_p, max_active=3)
    for i, p in enumerate(prompts):
        sp.submit(Request(i, p, max_new=8))
    out = {r.req_id: r.output for r in sp.run()}
    assert out == ref
    # the paged pool decode also compiled exactly once across occupancies
    assert eng_p.batched_traces == 1
    eng_p.allocator.check()


def test_paged_hit_matches_cold_output(gt):
    """A prefix-hit admission (aliased pages + suffix-only prefill) must
    reproduce the cache-free output exactly."""
    cfg, model, params = gt
    shared = [7] * 40
    cold = RealEngine(cfg, model, params, max_len=128)
    a = cold.generate(Request(0, shared + [1, 2, 3], max_new=6)).output

    eng = RealEngine(cfg, model, params, max_len=128)
    eng.generate(Request(1, shared + [9, 9], max_new=6))     # warm the cache
    r = eng.generate(Request(2, shared + [1, 2, 3], max_new=6))
    assert r.cached_tokens >= BLOCK
    assert r.output == a


# ------------------------------------------------- zero-copy prefix sharing
def test_hit_admission_aliases_pages_no_copy(gt):
    """The acceptance check: admitting a prefix-hit request bumps the
    holder's page refcounts and allocates pages only from the divergence
    point — no KV bytes move."""
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128)
    shared = [3] * 64                                  # 2 full blocks
    eng.generate(Request(0, shared + [5], max_new=2))
    matched, entry = eng.prefix_cache.peek(shared + [8] * 8)
    assert matched == 64 and isinstance(entry.handle, PagedHandle)
    cached_pages = entry.handle.pages[:2]
    rc_before = [eng.allocator.refcount(p) for p in cached_pages]
    used_before = eng.allocator.used_count

    st = eng.prefill_request(Request(1, shared + [8] * 8, max_new=4))
    # the admitted request's first two pages ARE the cache entry's pages
    assert tuple(st.pages[:2]) == tuple(cached_pages)
    for p, rc0 in zip(cached_pages, rc_before):
        assert eng.allocator.refcount(p) == rc0 + 1    # aliased, not copied
    # only the divergence suffix allocated fresh pages: 8 suffix tokens in
    # one block -> exactly one new page beyond the aliased prefix
    assert eng.allocator.used_count == used_before + 1
    assert len(st.pages) == 3 and st.matched == 64
    eng.release_pages(st.pages)
    eng.allocator.check()


def test_full_hit_replay_never_writes_aliased_pages(gt):
    """A block-aligned fully cached prompt replays its last token query-
    only: the aliased pages' contents must be bit-identical afterwards."""
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128)
    prompt = [11] * 64                                 # block-aligned
    eng.generate(Request(0, prompt, max_new=2))
    _, entry = eng.prefix_cache.peek(prompt)
    pages = list(entry.handle.pages)
    before = [np.asarray(leaf[:, pages])
              for leaf in jax.tree.leaves(eng.arena)]
    st = eng.prefill_request(Request(1, prompt, max_new=2))
    after = [np.asarray(leaf[:, pages])
             for leaf in jax.tree.leaves(eng.arena)]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)
    assert st.matched == 64
    eng.release_pages(st.pages)


def test_completion_inserts_by_reference_and_releases(gt):
    """Completion hands pages to the prefix cache by reference; evicting
    the entry returns them to the free list only once no request uses
    them."""
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128)
    free0 = eng.allocator.free_count
    eng.generate(Request(0, [9] * 40, max_new=8))      # pos 48 -> 1 page kept
    _, entry = eng.prefix_cache.peek([9] * 40)
    kept = entry.handle.pages
    assert len(kept) == 1 and eng.allocator.refcount(kept[0]) == 1
    # request's own references were dropped; only the entry's survive
    assert eng.allocator.free_count == free0 - 1
    while eng.prefix_cache.pop_lru():
        pass
    assert eng.allocator.free_count == free0
    eng.allocator.check()


def test_allocator_pressure_evicts_prefix_cache(gt):
    """With a tiny arena, sustained distinct traffic must recycle pages
    through LRU eviction instead of dying with OutOfPages."""
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128, num_pages=13)
    for i in range(6):
        out = eng.generate(Request(i, [(53 * i + j) % cfg.vocab
                                       for j in range(40)], max_new=6))
        assert len(out.output) == 6
    eng.allocator.check()


def test_cached_prefixes_deduped_by_entry():
    """An entry registers one chain key per block depth; the HR-tree
    broadcast view must count it once, not once per key."""
    from repro.serving.prefix_cache import PrefixCache
    pc = PrefixCache(block=8)
    pc.insert(list(range(40)), "A", 10)          # 5 block depths, 1 entry
    pc.insert(list(range(200, 216)), "B", 10)    # 2 depths, 1 entry
    got = pc.cached_prefixes()
    assert len(got) == 2
    assert sorted(ln for ln, _ in got) == [16, 40]


def test_model_node_reports_free_page_pressure(gt):
    """The HR-tree sync broadcast carries the paged arena's free-page
    pressure, and peers record it."""
    cfg, model, params = gt
    from repro.overlay.model_node import ModelNode
    eng = RealEngine(cfg, model, params, max_len=128, num_pages=17)
    node = ModelNode("m0", use_crypto=False, real_engine=eng)
    assert node._kv_pressure() == 0.0
    pages = eng.alloc_pages(4)
    assert node._kv_pressure() == pytest.approx(4 / 16)
    peer = ModelNode("m1", use_crypto=False)
    peer._handle_sync(None, {"from": "m0", "paths": [], "active": 1,
                             "hw": 5.0, "kv_pressure": node._kv_pressure()})
    assert peer.peers["m0"].kv_pressure == pytest.approx(4 / 16)
    assert peer._kv_pressure() == 0.0            # latency-model node
    eng.release_pages(pages)


def test_pool_memory_scales_with_live_tokens(gt):
    """The dense pool pins max_active x max_len KV regardless of
    occupancy; the paged pool's footprint is the live pages."""
    cfg, model, params = gt
    eng_d = RealEngine(cfg, model, params, max_len=128, paged=False)
    sd = Scheduler(eng_d, max_active=4)
    eng_p = RealEngine(cfg, model, params, max_len=128)
    sp = Scheduler(eng_p, max_active=4)
    for s in (sd, sp):
        s.submit(Request(0, [5] * 20, max_new=4))
        s.step()                                       # one slot occupied
    assert sp.kv_bytes_in_use() < sd.kv_bytes_in_use() / 4
    sd.run(), sp.run()
