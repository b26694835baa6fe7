"""Packed batched admission (engine.prefill_requests): the admitted
requests' uncached chunks fill the shared grid one per row, request after
request, so one prompt may hold several rows of a dispatch and an
admission costs ceil(sum(chunks) / rows) dispatches.  Each case checks
the dispatch count, the grid's counters and the rows each dispatch
filled by hand, then the served tokens against per-request ``generate``
(sequential one-chunk dispatches) and the allocator after release."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import base
from repro.models.lm import build_model
from repro.serving.engine import RealEngine, Request
from repro.serving.prefix_cache import BLOCK
from repro.serving.scheduler import Scheduler

ROWS = 4


def _setup(name, **changes):
    cfg = dataclasses.replace(base.get_config(name).reduced(), **changes)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def gt():
    return _setup("gentorrent-llama3-8b")


@pytest.fixture(scope="module")
def windowed():
    """Two layers under a 48-token window: a 200-token prompt's later
    chunks see only their nearest sibling chunks, through the mask."""
    cfg = base.get_config("h2o-danube-1.8b").reduced()
    pattern = tuple(dataclasses.replace(s, window=48) for s in cfg.pattern)
    return _setup("h2o-danube-1.8b", pattern=pattern,
                  n_layers=2 * len(pattern))


def _prompt(cfg, seed, n):
    return [(37 * seed + 11 * j + 1) % cfg.vocab for j in range(n)]


def _spy_rows(eng):
    """Record the ``rows`` meta of every prefill dispatch span."""
    rows, span = [], eng.recorder.span

    def spy(name, **meta):
        if name == "engine.prefill_dispatch":
            rows.append(meta["rows"])
        return span(name, **meta)

    eng.recorder.span = spy
    return rows


def _reference(setup, prompts, max_new, max_len):
    cfg, model, params = setup
    eng = RealEngine(cfg, model, params, max_len=max_len)
    return {i: eng.generate(Request(i, p, max_new=max_new)).output
            for i, p in enumerate(prompts)}


def _admit_and_serve(eng, prompts, max_new):
    """Submit every prompt, admit them in ONE round, serve them out."""
    s = Scheduler(eng, max_active=ROWS)
    for i, p in enumerate(prompts):
        s.submit(Request(i, p, max_new=max_new))
    s.step()
    assert s.metrics["admitted"] == len(prompts)
    return s, {r.req_id: r.output for r in s.run()}


def test_one_prompt_fills_every_row(gt):
    """300 tokens = 10 chunks in a 4-row grid: 3 dispatches of 4, 4 and
    2 filled rows (the old grid ran 10, each with 1 live row)."""
    cfg, model, params = gt
    prompts = [_prompt(cfg, 0, 300)]
    eng = RealEngine(cfg, model, params, max_len=384)
    rows = _spy_rows(eng)
    _, out = _admit_and_serve(eng, prompts, max_new=6)
    assert eng.prefill_dispatches == 3 and rows == [4, 4, 2]
    assert eng.prefill_slots == 3 * ROWS * BLOCK
    assert eng.prefill_tokens == 300
    assert eng.batched_prefill_traces == 1
    assert out == _reference(gt, prompts, 6, 384)
    eng.allocator.check()


def test_mixed_admission_with_a_prefix_hit(gt):
    """A 64-token cached prefix and four suffixes of 50 (after the hit),
    45, 100 and 20 tokens: 2 + 2 + 4 + 1 = 9 chunks, most with a partial
    last chunk, in ceil(9 / 4) = 3 dispatches (the old grid ran
    max(chunks) = 4)."""
    cfg, model, params = gt
    shared = _prompt(cfg, 7, 64)
    prompts = [shared + _prompt(cfg, 1, 50), _prompt(cfg, 2, 45),
               _prompt(cfg, 3, 100), _prompt(cfg, 4, 20)]
    eng = RealEngine(cfg, model, params, max_len=192)
    s = Scheduler(eng, max_active=ROWS)
    s.submit(Request(9, shared + [5] * 8, max_new=2))   # seed the cache
    s.run()
    d0, t0, slots0 = (eng.prefill_dispatches, eng.prefill_tokens,
                      eng.prefill_slots)
    rows = _spy_rows(eng)
    s, out = _admit_and_serve(eng, prompts, max_new=5)
    chunks = sum(-(-n // BLOCK) for n in (50, 45, 100, 20))
    assert chunks == 9
    assert eng.prefill_dispatches - d0 == -(-chunks // ROWS) == 3
    assert rows == [4, 4, 1]
    assert eng.prefill_slots - slots0 == 3 * ROWS * BLOCK
    assert eng.prefill_tokens - t0 == 50 + 45 + 100 + 20
    assert {r.req_id: r.cached_tokens for r in s.done} == \
        {0: 64, 1: 0, 2: 0, 3: 0}
    assert out == _reference(gt, prompts, 5, 192)
    assert eng.batched_prefill_traces == 1
    eng.allocator.check()


def test_windowed_rows_read_their_siblings(windowed):
    """A window shorter than the prompt: every later row of one dispatch
    attends to the K/V its sibling rows scattered into the same layer
    moments before, only as far back as the window reaches.  The packed
    state's logits match the sequential path's, and the served tokens
    match ``generate``."""
    cfg, model, params = windowed
    prompts = [_prompt(cfg, 0, 200), _prompt(cfg, 1, 70)]
    eng = RealEngine(cfg, model, params, max_len=256)
    rows = _spy_rows(eng)
    states = eng.prefill_requests(
        [Request(i, p, max_new=1) for i, p in enumerate(prompts)],
        batch=ROWS)
    assert eng.prefill_dispatches == 3 and rows == [4, 4, 2]   # 7 + 3
    seq = RealEngine(cfg, model, params, max_len=256)
    for st, p in zip(states, prompts):
        ref = seq.prefill_request(Request(0, p, max_new=1))
        assert st.pos == ref.pos == len(p)
        assert len(st.pages) == len(ref.pages) == -(-len(p) // BLOCK)
        np.testing.assert_allclose(np.asarray(st.logits),
                                   np.asarray(ref.logits),
                                   rtol=1e-5, atol=1e-5)
        eng.release_pages(st.pages)
        seq.release_pages(ref.pages)
    eng.allocator.check()
    seq.allocator.check()
    served = RealEngine(cfg, model, params, max_len=256)
    _, out = _admit_and_serve(served, prompts, max_new=8)
    assert out == _reference(windowed, prompts, 8, 256)
    served.allocator.check()
