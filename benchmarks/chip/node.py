"""The system under test: one model node's served path, built from a
configuration file as a node builds it.

``RealEngine`` over a paged KV arena, driven by the continuous-batching
``Scheduler``.  The configuration's family gives the program's
configuration (its widths checked against the registered one) and the
parameter tree; implementation options (``head_pad``, ``use_kernels``,
``spec_enabled``) are the program's own, so a PR that changes them is
measured.
"""
from __future__ import annotations

import cells
from weights import make_params


def build(spec: dict, seed: int, device, check: bool = True):
    """(engine, scheduler) of one node on ``device``, weights from
    ``seed``."""
    from repro.models.lm import build_model
    from repro.serving.engine import RealEngine
    from repro.serving.scheduler import Scheduler
    fam = cells.family(spec["family"])
    cfg = fam.arch_config(spec, check)
    model = build_model(cfg)
    params = fam.program_params(make_params(spec, seed, device), model)
    dep = spec["deployment"]
    pages = dep["arena_pages"]
    # page_bytes is only known once the engine has built its arena
    eng = RealEngine(cfg, model, params, max_len=dep["max_len"],
                     num_pages=pages)
    eng.prefix_cache.max_bytes = dep["prefix_cache_pages"] * eng.page_bytes
    return eng, Scheduler(eng, max_active=dep["max_active"])
