"""The paged attention kernel on the served path, on the CPU: a
``RealEngine`` whose decode and prefill programs run the Pallas kernel in
interpret mode serves the same greedy tokens as the jnp gather path over a
packed admission and the decode rounds after it, and the engine's
``attn_pages_read`` / ``attn_pages_tabled`` counters match a hand count."""
import dataclasses
import functools

import jax
import pytest

from repro.configs import base
from repro.models.lm import LM, build_model
from repro.serving.engine import RealEngine, Request
from repro.serving.prefix_cache import BLOCK
from repro.serving.scheduler import Scheduler

MAX_LEN = 128
MAX_NEW = 16
LENGTHS = (70, 45, 90, 33)          # 3 + 2 + 3 + 2 chunks: 3 dispatches


def _model(name, impl):
    cfg = base.get_config(name).reduced()
    if name == "gemma2-9b":             # local layers under a 40-token window
        cfg = dataclasses.replace(cfg, pattern=tuple(
            dataclasses.replace(s, window=s.window and 40)
            for s in cfg.pattern))
    model = build_model(cfg)
    if impl != "auto":
        for fn in ("prefill_paged", "decode_paged", "verify_paged"):
            setattr(model, fn,
                    functools.partial(getattr(LM, fn), model, impl=impl))
    return cfg, model


def _serve(name, impl):
    cfg, model = _model(name, impl)
    params = model.init(jax.random.PRNGKey(0))
    eng = RealEngine(cfg, model, params, max_len=MAX_LEN)
    sched = Scheduler(eng, max_active=len(LENGTHS))
    for i, n in enumerate(LENGTHS):
        sched.submit(Request(i, [(29 * i + 7 * j + 3) % cfg.vocab
                                 for j in range(n)], max_new=MAX_NEW))
    out = {r.req_id: r.output for r in sched.run()}
    eng.allocator.check()
    return eng, out


@pytest.mark.parametrize("name", ["h2o-danube-1.8b", "yi-34b", "gemma2-9b"],
                         ids=["danube-window", "yi-head-pad",
                              "gemma2-softcap-local-global"])
def test_kernel_serving_matches_jnp(name):
    """One packed admission of four prompts (3 dispatches of the 4-row
    grid) and 16 decode rounds: the kernel's tokens are the jnp path's."""
    ref_eng, ref = _serve(name, "ref")
    eng, out = _serve(name, "interpret")
    assert eng.prefill_dispatches == ref_eng.prefill_dispatches == 3
    assert all(len(o) == MAX_NEW for o in ref.values())
    assert out == ref
    assert (eng.attn_pages_read, eng.attn_pages_tabled) == \
        (ref_eng.attn_pages_read, ref_eng.attn_pages_tabled)


def test_attention_page_counters_by_hand():
    """Each dispatch adds the pages its live rows' attention visits and
    rows x table width: the 4-row grid's 10 chunks, then 15 decode rounds
    (the first token comes from the prefill) of the four rows, each
    visiting the pages up to its position, as the window allows."""
    eng, _ = _serve("gentorrent-llama3-8b", "auto")     # no window
    n_pg = MAX_LEN // BLOCK
    # prefill: chunk k of a prompt reads pages 0..k
    read = sum(k + 1 for n in LENGTHS for k in range(-(-n // BLOCK)))
    tabled = 3 * len(LENGTHS) * n_pg
    # decode round t writes position n + t - 1 and reads pages up to it
    for t in range(1, MAX_NEW):
        read += sum((n + t - 1) // BLOCK + 1 for n in LENGTHS)
        tabled += len(LENGTHS) * n_pg
    assert (eng.attn_pages_read, eng.attn_pages_tabled) == (read, tabled)
    assert eng.attn_pages_read < eng.attn_pages_tabled

    win, _ = _serve("h2o-danube-1.8b", "auto")          # 8-token window
    assert win.attn_window == 8
    assert win.attn_pages_tabled == tabled
    # a chunk or a decode row reaches back at most one page past its own
    assert win.attn_pages_read < read
