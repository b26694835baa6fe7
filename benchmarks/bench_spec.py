"""Speculative n-gram decode over the slot pool vs one-token pool decode.

Same requests through the same paged scheduler twice: baseline (one
token per round, PR 1-3 path) and speculative (`cfg.spec_enabled`: a
host-side prompt-lookup drafter proposes up to `spec_k` tokens per slot
and every round verifies the whole pool's drafts in ONE multi-token
verify dispatch, `RealEngine._verify_paged_batched`).  Outputs are token-identical by construction
(drafts are only accepted when they equal the model's own greedy
argmax); what changes is dispatches per generated token.

Two workloads: **repetitive** prompts (short token cycles — greedy
decode of the reduced model locks onto cycles, so the drafter keeps
proposing the right continuation and verify rounds commit several
tokens per dispatch) and **random** prompts (novel streams — drafting
mostly misses and the verify window degenerates to a one-token round,
bounding the overhead of speculation when it cannot help).

Reported per workload and mode: generated tokens, wall-clock tokens/s,
decode dispatches, dispatches/token, live KV bytes after the run, and
for spec mode the drafted/accepted counters.  Token and dispatch
counters are deterministic (greedy decode, fixed seeds) —
scripts/check_bench.py gates them against results/bench/baseline/.
"""
from __future__ import annotations

import sys
import time

from benchmarks.common import emit, save


def _prompts(kind: str, cfg, n: int, length: int):
    if kind == "repetitive":
        cycles = ([5, 9, 2, 7], [3, 3, 8], [1, 4], [6, 2, 9, 9])
        return [(cycles[i % len(cycles)] * length)[:length]
                for i in range(n)]
    return [[(37 * (i + 1) + 13 * j) % cfg.vocab for j in range(length)]
            for i in range(n)]


def _run_mode(cfg, params, prompts, spec: bool, spec_k: int,
              max_active: int, max_new: int) -> dict:
    import dataclasses

    from repro.models.lm import build_model
    from repro.serving.engine import RealEngine, Request
    from repro.serving.scheduler import Scheduler

    rcfg = dataclasses.replace(cfg, spec_enabled=spec, spec_k=spec_k)
    eng = RealEngine(rcfg, build_model(rcfg), params, max_len=256)
    sched = Scheduler(eng, max_active=max_active)
    # warm every jit trace (admission grid + pool decode / verify window)
    # with a repetitive prompt so the timed runs are compile-free
    sched.submit(Request(0, [2, 4] * 10, max_new=6))
    sched.run()
    sched.done.clear()
    d0 = sched.metrics["decode_calls"]
    sd0, sa0, sp0 = eng.spec_drafted, eng.spec_accepted, eng.spec_dispatches

    for i, p in enumerate(prompts):
        sched.submit(Request(1 + i, p, max_new=max_new))
    t0 = time.perf_counter()
    done = sched.run()
    wall = time.perf_counter() - t0

    tokens = sum(len(r.output) for r in done)
    dispatches = sched.metrics["decode_calls"] - d0
    out = {
        "generated_tokens": tokens,
        "wall_s": wall,
        "tok_s": tokens / wall if wall > 0 else 0.0,
        "decode_dispatches": dispatches,
        "dispatches_per_token": dispatches / max(1, tokens),
        "kv_bytes_live": eng.live_kv_bytes(),
    }
    if spec:
        out["drafted_tokens"] = eng.spec_drafted - sd0
        out["accepted_tokens"] = eng.spec_accepted - sa0
        out["accept_rate"] = ((eng.spec_accepted - sa0)
                              / max(1, eng.spec_drafted - sd0))
        out["spec_dispatches"] = eng.spec_dispatches - sp0
        out["spec_traces"] = eng.spec_traces
    return out


def bench_spec(spec_k: int = 4, max_active: int = 4, n_req: int = 8,
               max_new: int = 48, prompt_len: int = 48) -> dict:
    import jax

    from repro.configs import base
    from repro.models.lm import build_model

    cfg = base.get_config("gentorrent-llama3-8b").reduced()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    out = {"params": {"spec_k": spec_k, "max_active": max_active,
                      "n_req": n_req, "max_new": max_new,
                      "prompt_len": prompt_len}}
    for kind in ("repetitive", "random"):
        prompts = _prompts(kind, cfg, n_req, prompt_len)
        res = {}
        for mode, spec in (("baseline", False), ("spec", True)):
            res[mode] = _run_mode(cfg, params, prompts, spec, spec_k,
                                  max_active, max_new)
        res["speedup"] = (res["spec"]["tok_s"]
                          / max(res["baseline"]["tok_s"], 1e-9))
        res["dispatch_ratio"] = (res["spec"]["dispatches_per_token"]
                                 / max(res["baseline"]
                                       ["dispatches_per_token"], 1e-9))
        out[kind] = res
    rep = out["repetitive"]
    out["spec_lt_one_dispatch_per_token"] = (
        rep["spec"]["dispatches_per_token"] < 1.0)
    out["spec_strictly_fewer_dispatches"] = (
        rep["spec"]["decode_dispatches"]
        < rep["baseline"]["decode_dispatches"])
    return out


def _emit(res: dict):
    for kind in ("repetitive", "random"):
        r = res[kind]
        emit(f"spec_{kind}_tok_s", r["spec"]["wall_s"] * 1e6,
             {"tok_s": r["spec"]["tok_s"],
              "dispatches_per_token": r["spec"]["dispatches_per_token"],
              "accept_rate": r["spec"].get("accept_rate", 0.0)})
        emit(f"spec_{kind}_baseline_tok_s", r["baseline"]["wall_s"] * 1e6,
             {"tok_s": r["baseline"]["tok_s"],
              "dispatches_per_token":
                  r["baseline"]["dispatches_per_token"]})


def main():
    res = bench_spec()
    save("bench_spec", res)
    _emit(res)
    return res


def quick():
    """Reduced sizes for the CI artifact + regression gate."""
    res = bench_spec(n_req=4, max_new=24, prompt_len=40)
    save("bench_spec_quick", res)
    _emit(res)
    return res


if __name__ == "__main__":
    quick() if "quick" in sys.argv[1:] else main()
