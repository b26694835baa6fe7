"""Property-based page-allocator invariants (hypothesis-optional) plus
deterministic cross-node page-migration edge cases.

The model under test is the host-side refcounted allocator behind the
paged KV pool (serving/page_pool.py).  Invariants:

  * alloc never hands out the scratch page or a page somebody holds
  * refcounts track an independent python model exactly
  * a page returns to the free list precisely when its last reference
    drops — aliased pages are never reclaimed while referenced
  * double free / incref-after-free are hard errors
  * used_count + free_count == num_pages - 1 at all times

The migration edge cases (importer out of pages mid-import, holder
evicted the entry before the fetch landed, refcount parity after
replicate + release) are deterministic and run without hypothesis.
"""
import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.serving.page_pool import (NULL_PAGE, OutOfPages, PageAllocator,
                                     PagedHandle)

if HAVE_HYPOTHESIS:
    # an op is ("alloc", n) | ("incref", i) | ("decref", i) where i picks
    # a live page by index modulo the live set
    OPS = st.lists(
        st.one_of(
            st.tuples(st.just("alloc"), st.integers(0, 4)),
            st.tuples(st.just("incref"), st.integers(0, 63)),
            st.tuples(st.just("decref"), st.integers(0, 63)),
        ),
        min_size=1, max_size=200)

    @given(num_pages=st.integers(2, 40), ops=OPS)
    @settings(max_examples=60, deadline=None)
    def test_refcount_model_agreement(num_pages, ops):
        a = PageAllocator(num_pages)
        model = {}                           # page -> refcount
        for op, arg in ops:
            if op == "alloc":
                if arg <= a.free_count:
                    got = a.alloc(arg)
                    assert NULL_PAGE not in got
                    assert not (set(got) & set(model)), "live page re-handed"
                    for p in got:
                        model[p] = 1
                else:
                    with pytest.raises(OutOfPages):
                        a.alloc(arg)
            elif model:
                pages = sorted(model)
                p = pages[arg % len(pages)]
                if op == "incref":
                    a.incref([p])
                    model[p] += 1
                else:
                    a.decref([p])
                    model[p] -= 1
                    if not model[p]:
                        del model[p]
            # allocator agrees with the model after every op
            assert a.used_count == len(model)
            assert a.free_count == (num_pages - 1) - len(model)
            for p, rc in model.items():
                assert a.refcount(p) == rc
            a.check()

    @given(ops=OPS)
    @settings(max_examples=40, deadline=None)
    def test_freed_pages_are_reusable_and_only_when_unreferenced(ops):
        """An aliased page (refcount >= 2) must survive any single decref
        and must not reappear from alloc until fully released."""
        a = PageAllocator(16)
        held = []                            # pages with an extra alias
        for op, arg in ops:
            if op == "alloc" and a.free_count:
                (p,) = a.alloc(1)
                a.incref([p])                # alias it immediately
                held.append(p)
            elif op == "decref" and held:
                p = held[arg % len(held)]
                a.decref([p])                # drop ONE of two refs
                assert a.refcount(p) == 1    # alias keeps it live
                if a.free_count:
                    fresh = a.alloc(1)
                    assert p not in fresh    # never re-handed while held
                    a.decref(fresh)
                a.decref([p])                # now truly free
                held.remove(p)
            a.check()

    @given(st.lists(st.integers(1, 400), min_size=1, max_size=30))
    @settings(max_examples=40, deadline=None)
    def test_handles_are_pure_indices(lengths):
        """PagedHandle equality/identity never touches device memory —
        the prefix cache can hold thousands of them for free."""
        hs = [PagedHandle(tuple(range(1, 1 + n % 7)), n) for n in lengths]
        for h, n in zip(hs, lengths):
            assert h.length == n
            assert all(p != NULL_PAGE for p in h.pages)


# ==========================================================================
# Cross-node page-migration edge cases (deterministic)
# ==========================================================================

@pytest.fixture(scope="module")
def gt():
    from repro.configs import base
    from repro.models.lm import build_model
    cfg = base.get_config("gentorrent-llama3-8b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


SHARED = [7] * 96                       # three full blocks


def _seeded_export(gt, depth=3, mode="raw"):
    from repro.serving.engine import RealEngine, Request
    from repro.serving.prefix_cache import _chain_hashes
    cfg, model, params = gt
    src = RealEngine(cfg, model, params, max_len=128)
    src.generate(Request(0, SHARED + [1] * 8, max_new=2))
    _, entry = src.prefix_cache.peek(SHARED)
    return (src, src.export_pages(entry.handle, depth=depth, mode=mode),
            _chain_hashes(SHARED)[:depth])


def test_importer_out_of_pages_releases_and_falls_back(gt):
    """An importer whose arena cannot host the pages (free pages pinned
    by live requests, nothing evictable) must raise OutOfPages with every
    allocated page released — and still serve the request by prefill."""
    from repro.serving.engine import RealEngine, Request
    cfg, model, params = gt
    _, buf, chains = _seeded_export(gt)
    dst = RealEngine(cfg, model, params, max_len=128,
                     num_pages=1 + 4)           # 4 usable pages
    pinned = dst.alloc_pages(2)                 # live requests, not cache:
    free0 = dst.allocator.free_count            # pop_lru can't reclaim them
    with pytest.raises(OutOfPages):
        dst.import_pages(buf, chains)           # needs 3, only 2 free
    # nothing leaked, nothing registered
    assert dst.allocator.free_count == free0
    assert dst.prefix_cache.peek(SHARED) == (0, None)
    dst.allocator.check()
    # fallback: plain prefill of a tail-block request still works
    out = dst.generate(Request(1, SHARED[:32] + [9] * 8, max_new=2))
    assert out.output and out.cached_tokens == 0
    dst.release_pages(pinned)
    dst.allocator.check()


def test_import_failure_mid_scatter_releases_pages(gt, monkeypatch):
    """A failure AFTER allocation (a device error in the scatter) must
    hand the fresh pages back before propagating as it is."""
    from repro.serving.engine import RealEngine
    cfg, model, params = gt
    _, buf, chains = _seeded_export(gt)
    dst = RealEngine(cfg, model, params, max_len=128)
    free0 = dst.allocator.free_count

    def boom(arena, pages, blocks):
        assert dst.allocator.free_count == free0 - len(pages)
        raise RuntimeError("device fault in scatter")
    monkeypatch.setattr(dst, "_scatter_pages", boom)
    with pytest.raises(RuntimeError, match="device fault"):
        dst.import_pages(buf, chains)
    assert dst.allocator.free_count == free0
    assert dst.prefix_cache.peek(SHARED) == (0, None)
    dst.allocator.check()


def test_holder_eviction_refuses_fetch(gt):
    """The holder evicted the entry between the sketch broadcast that
    attracted the fetch and the kv_fetch itself: it must refuse (ok=False)
    rather than export stale or foreign pages."""
    from repro.serving.prefix_cache import _chain_hashes
    src, _, _ = _seeded_export(gt)

    class _Capture:
        def __init__(self):
            self.sent = []

        def send(self, src_id, dst, msg, size_bytes=0):
            self.sent.append(msg)

    from repro.overlay.model_node import ModelNode
    holder = ModelNode("m0", use_crypto=False, real_engine=src)
    net = _Capture()
    chains = _chain_hashes(SHARED)
    while src.prefix_cache.pop_lru():           # the eviction race
        pass
    holder._handle_kv_fetch(net, {"type": "kv_fetch", "from": "m1",
                                  "fetch_id": 1, "chains": chains,
                                  "depth": 3})
    assert len(net.sent) == 1 and net.sent[0]["ok"] is False
    assert holder.metrics["kv_export_refused"] == 1
    src.allocator.check()


def test_refcount_parity_after_replicate_and_release(gt):
    """Both allocators stay consistent through export -> import ->
    aliased admission -> completion -> full release: the holder never
    moves a refcount, the importer ends exactly where it started."""
    from repro.serving.engine import RealEngine, Request
    from repro.serving.scheduler import Scheduler
    cfg, model, params = gt
    src, buf, chains = _seeded_export(gt)
    src_refs = [src.allocator.refcount(p) for p in range(src.num_pages)]
    dst = RealEngine(cfg, model, params, max_len=128)
    handle = dst.import_pages(buf, chains)
    assert [src.allocator.refcount(p) for p in range(src.num_pages)] \
        == src_refs                              # export moved nothing
    # an admitted sibling aliases the replica (refcount 2) and returns it
    s = Scheduler(dst, max_active=2)
    s.submit(Request(1, SHARED + [9] * 8, max_new=4))
    s.step()
    assert all(dst.allocator.refcount(p) == 2 for p in handle.pages)
    s.run()
    # completion re-registered the deeper prefix over the same physical
    # pages; dropping every cache entry frees the whole arena
    while dst.prefix_cache.pop_lru():
        pass
    assert dst.allocator.free_count == dst.num_pages - 1
    dst.allocator.check()
    src.allocator.check()


def test_int8_wire_mode_imports_and_serves(gt):
    """The quantized wire mode lands near-exact K/V: admission over an
    int8 replica still serves (bounded error, never a crash path)."""
    from repro.serving.engine import RealEngine, Request
    cfg, model, params = gt
    src, buf, chains = _seeded_export(gt, mode="int8")
    dst = RealEngine(cfg, model, params, max_len=128)
    handle = dst.import_pages(buf, chains)
    a = np.asarray(src.arena[0]["k"][:, list(
        src.prefix_cache.peek(SHARED)[1].handle.pages[:3])])
    b = np.asarray(dst.arena[0]["k"][:, list(handle.pages)])
    assert np.max(np.abs(a - b)) <= np.max(np.abs(a)) / 127.0 + 1e-7
    out = dst.generate(Request(1, SHARED + [9] * 8, max_new=2))
    assert out.cached_tokens == 96 and out.output
    dst.allocator.check()

