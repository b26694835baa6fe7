"""One node's recorder (serving/trace.py) on a tiny paged engine: spans
nest and carry their step and request, the rings are bounded, every step
record holds the counter snapshot, the prefill grid and the page pool are
counted by hand, a freed node leaves the registry, a profiler session
holds the program's spans, the device programs carry their scopes, and
``Result`` times a request from its submit."""
import gc
import re
import time
import weakref

import jax
import jax.numpy as jnp
import pytest

from repro.configs import base
from repro.models.lm import build_model
from repro.serving import trace
from repro.serving.engine import RealEngine, Request
from repro.serving.prefix_cache import BLOCK
from repro.serving.scheduler import Scheduler

SCOPES = ("embed", "qkv", "kv_write", "attention", "out_proj", "mlp",
          "logits")


@pytest.fixture(scope="module")
def gt():
    cfg = base.get_config("gentorrent-llama3-8b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, seed, n):
    return [(37 * seed + 11 * j + 1) % cfg.vocab for j in range(n)]


def _served(gt, n_req=2, max_new=3, **kw):
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128, **kw)
    s = Scheduler(eng, max_active=4)
    for i in range(n_req):
        s.submit(Request(i, _prompt(cfg, i, 40 + 8 * i), max_new=max_new))
    s.run()
    return eng, s


def test_spans_nest_and_carry_step_and_rid(gt):
    eng, s = _served(gt)
    spans = list(eng.recorder.spans)
    steps = {sp.step: sp for sp in spans if sp.name == trace.STEP}
    assert len(steps) == len(eng.recorder.steps) == \
        s.metrics["decode_calls"] + 1
    parents = {"sched.admit": trace.STEP, "sched.read_tokens": trace.STEP,
               "sched.decode_prep": trace.STEP,
               "sched.decode_dispatch": trace.STEP,
               "sched.retire": trace.STEP,
               "engine.prefill_dispatch": "sched.admit",
               "prefix.insert": "sched.retire"}
    for sp in spans:
        assert sp.start <= sp.end
        if sp.name in parents:
            assert sp.parent == parents[sp.name], sp
            outer = steps[sp.step]
            assert outer.start <= sp.start <= sp.end <= outer.end
    peeks = [sp for sp in spans if sp.name == "prefix.peek"]
    assert [(sp.rid, sp.step, sp.parent) for sp in peeks] == \
        [(0, None, None), (1, None, None)]
    retired = [sp for sp in spans if sp.name == "sched.retire"]
    inserted = [sp for sp in spans if sp.name == "prefix.insert"]
    assert sorted(sp.rid for sp in retired) == [0, 1]
    assert [sp.rid for sp in inserted] == [sp.rid for sp in retired]
    # one admission of both requests: their 2 + 2 chunks fill one
    # dispatch of the 4-row grid
    grid = [sp for sp in spans if sp.name == "engine.prefill_dispatch"]
    assert len(grid) == eng.prefill_dispatches == 1
    assert len({sp.step for sp in grid}) == 1


def test_rings_are_bounded(monkeypatch):
    monkeypatch.setattr(trace, "SPAN_CAPACITY", 8)
    monkeypatch.setattr(trace, "STEP_CAPACITY", 3)
    rec = trace.Recorder()
    snap = iter(range(100))
    for _ in range(5):
        with rec.step(lambda: {"n": next(snap)}):
            for j in range(3):
                with rec.span("work", rid=j):
                    pass
    assert len(rec.spans) == 8 and len(rec.steps) == 3
    assert [r.seq for r in rec.steps] == [2, 3, 4]
    assert [r.counters["n"] for r in rec.steps] == [2, 3, 4]
    # the newest spans are kept: the last step's three and its own
    assert [sp.name for sp in list(rec.spans)[-4:]] == \
        ["work", "work", "work", trace.STEP]
    assert [sp.rid for sp in list(rec.spans)[-4:]] == [0, 1, 2, None]


def test_step_record_holds_the_counter_snapshot(gt):
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128)
    s = Scheduler(eng, max_active=4)
    for i in range(3):
        s.submit(Request(i, _prompt(cfg, i, 33), max_new=4))
    while s.queue or s.active:
        s.step()
        rec = eng.recorder.steps[-1]
        assert rec.counters == s.counters()
        assert rec.start <= rec.end
        assert set(rec.spans) <= {"sched.admit", "engine.prefill_dispatch",
                                  "sched.read_tokens", "sched.decode_prep",
                                  "sched.decode_dispatch", "sched.retire",
                                  "prefix.insert", "gc"}
        dt = rec.end - rec.start
        assert all(0 <= v <= dt for v in rec.spans.values())
    first, last = eng.recorder.steps[0], eng.recorder.steps[-1]
    assert "engine.prefill_dispatch" in first.spans
    assert first.counters["active"] == 3 and last.counters["active"] == 0
    assert first.counters["prefill_tokens"] == 3 * 33
    assert first.counters["prefill_slots"] == eng.prefill_slots
    assert last.counters["pages_used"] == eng.allocator.used_count
    assert last.counters["pages_total"] == eng.num_pages - 1


@pytest.mark.parametrize("rows, n", [(1, 1), (4, 2)],
                         ids=["one_of_four", "full"])
def test_prefill_slots_and_tokens_by_hand(gt, rows, n):
    """Admission into a 4-row grid of BLOCK-token chunks: each 40-token
    prompt is 2 chunks, packed one per row, so one prompt fills 1
    dispatch and four fill 2; every dispatch counts 4 x BLOCK slots."""
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128)
    s = Scheduler(eng, max_active=4)
    for i in range(rows):
        s.submit(Request(i, _prompt(cfg, i, 40), max_new=1))
    s.step()
    assert eng.prefill_dispatches == n
    assert eng.prefill_tokens == 40 * rows
    assert eng.prefill_slots == n * 4 * BLOCK
    c = eng.recorder.steps[-1].counters
    assert (c["prefill_tokens"], c["prefill_slots"]) == (40 * rows,
                                                         n * 128)
    rows_meta = [sp for sp in eng.recorder.spans
                 if sp.name == "engine.prefill_dispatch"]
    assert len(rows_meta) == n
    # the sequential admission path: one row of BLOCK slots per chunk
    eng.prefill_request(Request(9, _prompt(cfg, 9, 70), max_new=1))
    assert eng.prefill_dispatches == n + 3
    assert eng.prefill_tokens == 40 * rows + 70
    assert eng.prefill_slots == n * 128 + 3 * BLOCK


def test_pages_evicted_and_used_under_a_small_arena(gt):
    """8 usable pages; four 64-token requests leave 2 cached pages each
    and fill the arena; a fifth needs 2 pages and evicts the oldest
    entry's 2 on its first chunk."""
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128, num_pages=9)
    s = Scheduler(eng, max_active=4)
    used = []
    for i in range(5):
        s.submit(Request(i, _prompt(cfg, i, 64), max_new=1))
        s.run()
        c = eng.recorder.steps[-1].counters
        used.append((c["pages_used"], c["pages_evicted"]))
    assert used == [(2, 0), (4, 0), (6, 0), (8, 0), (8, 2)]
    assert eng.pages_evicted == 2
    assert c["pages_total"] == 8
    ev = [sp for sp in eng.recorder.spans if sp.name == "pages.evict"]
    assert len(ev) == 1
    assert ev[0].parent == "engine.prefill_dispatch" and ev[0].rid is None
    eng.allocator.check()


def test_freed_node_leaves_the_registry(gt):
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=64)
    ref = weakref.ref(eng.recorder)
    assert trace.latest() is eng.recorder
    assert ref() in trace.live()
    del eng
    gc.collect()
    assert ref() is None
    assert all(r is not None for r in trace.live())


def test_profiler_session_holds_the_programs_spans(gt, tmp_path):
    from jax.profiler import ProfileData
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128)
    s = Scheduler(eng, max_active=4)
    s.submit(Request(0, _prompt(cfg, 0, 40), max_new=3))
    s.step()                        # compiles outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        s.submit(Request(1, _prompt(cfg, 1, 40), max_new=3))
        s.run()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.rglob("*.xplane.pb"))
    assert found
    names, stats = set(), {}
    for plane in ProfileData.from_file(str(found[-1])).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                names.add(e.name)
                stats.setdefault(e.name, []).append(dict(e.stats))
    assert {"sched.step", "sched.admit", "engine.prefill_dispatch",
            "sched.read_tokens", "sched.decode_prep",
            "sched.decode_dispatch", "sched.retire", "prefix.insert",
            "prefix.peek", "gc"} <= names
    # one 40-token prompt: its 2 chunks fill 2 rows of one dispatch
    assert stats["engine.prefill_dispatch"] == [{"rows": 2}]
    assert {"rid": 1} in stats["sched.retire"]
    assert {"generation": 2} in stats["gc"]
    assert any(sp.name == "gc" for sp in eng.recorder.spans)
    assert eng.recorder.gc_full >= 1


@pytest.mark.parametrize("W, write", [(1, "tokens"), (BLOCK, "page"),
                                      (3, "tokens"), (1, None)],
                         ids=["decode_paged", "prefill_paged",
                              "verify_paged", "query_paged"])
def test_device_programs_carry_the_scopes(gt, W, write):
    """Every scope names some op of the compiled ``step_paged`` program
    (the op names the device trace's ops carry): the decode step, the
    prefill grid, the verify window, and the query-only replay, which
    writes nothing and so has no ``kv_write`` scope."""
    cfg, model, params = gt
    arena = model.paged_arena_zeros(5, BLOCK)
    B = 2
    pt = jnp.zeros((B, 4), jnp.int32)
    tok = jnp.zeros((B, W), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    n_tok = jnp.asarray([W, 0], jnp.int32)

    def step(params, arena, pt, tok, pos, n_tok):
        return model.step_paged(params, arena, pt, tok, pos, n_tok=n_tok,
                                write=write)

    text = jax.jit(step).lower(
        params, arena, pt, tok, pos, n_tok).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in SCOPES:
        want = scope != "kv_write" or write is not None
        assert any(f"/{scope}/" in n for n in names) == want, scope


def test_result_times_a_request_from_its_submit(gt):
    """Two requests through a one-row pool: the second waits in the queue
    for the whole of the first."""
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128)
    s = Scheduler(eng, max_active=1)
    s.submit(Request(0, _prompt(cfg, 0, 20), max_new=3))
    s.submit(Request(1, _prompt(cfg, 1, 20), max_new=3))
    time.sleep(0.05)
    first, second = s.run()
    for r in (first, second):
        assert 0.05 <= r.queued_s <= r.ttft <= r.total
    # submitted together, the second is admitted as the first finishes
    assert second.queued_s == pytest.approx(first.total, abs=0.01)


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_generate_times_the_first_token(gt, paged):
    """``generate`` stamps ``ttft`` when the first token is on the host:
    the decodes after it (each 50 ms slower here) fall outside it.  With
    no token to make, ``ttft`` is the whole call."""
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128, paged=paged)
    name = "_decode_paged" if paged else "_decode"
    fast = getattr(eng, name)

    def slow(*a):
        time.sleep(0.05)
        return fast(*a)
    setattr(eng, name, slow)
    r = eng.generate(Request(0, _prompt(cfg, 0, 40), max_new=3))
    assert len(r.output) == 3 and r.queued_s == 0.0
    assert r.ttft > 0 and r.total - r.ttft >= 2 * 0.05
    empty = eng.generate(Request(1, _prompt(cfg, 1, 40), max_new=0))
    assert empty.output == [] and empty.ttft == empty.total > 0
