#!/usr/bin/env python3
"""Bring-up smoke run of the served path on a TPU chip.

Serves h2o-danube-1.8b at its published widths (random bf16 weights made
from ``--seed``) through the objects a deployment uses: ``ModelNode`` on a
``SimNet`` overlay -> ``RealEngine`` + ``Scheduler`` -> the paged KV arena.

One-chip run (the default) checks, and fails on the first that does not
hold:
  * every request completes, and a request sharing a block-aligned
    256-token prefix with an earlier one admits from cached pages;
  * the served paged prefill's last-position logits agree with
    ``LM.apply`` on the same chip;
  * one decode batch and one packed prefill dispatch through the Pallas
    paged kernel agree with the same batches through the jnp gather path,
    and a decode batch reading the wrong pages misses the tolerance;
  * the node's served decode and prefill programs contain the kernel
    (``tpu_custom_call``);
  * a page export/import round trip lands the same bytes.

It also prints the share of the page tables the attention visited
(``attn_pages_read / attn_pages_tabled``).

``--four-chips`` runs only the overlay's multi-chip shape: four one-chip
nodes, node i on device i with its own params, a shared-prefix load whose
holder is vetoed so that prefix pages cross chips, compared token for token
with the same requests served on one device.

Without a TPU it exits non-zero and prints no result.  The last line of a
passing run is one JSON object naming the device.

    python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

MODEL = "h2o-danube-1.8b"
MAX_LEN = 4096           # the model's attention window; default arena
MAX_NEW = 16
SHARED_LEN = 256         # block-aligned shared prefix
# max |logit delta| against the reference: bf16 runs of this script on the
# CPU at 24 layers stayed below 0.03 at d=512, and logits grow as sqrt(d)
LOGIT_TOL = 0.25


def log(*parts):
    print("smoke:", *parts, flush=True)


class SmokeFailure(AssertionError):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)
    log("ok", what)


# ---------------------------------------------------------------- set-up
def serving_config(cfg):
    """The published config with params held in the compute dtype."""
    return dataclasses.replace(cfg, param_dtype=cfg.dtype)


def init_params(model, seed, device):
    """Random weights made on ``device`` in the params dtype, under jit."""
    import jax
    from jax.sharding import SingleDeviceSharding
    init = jax.jit(model.init, out_shardings=SingleDeviceSharding(device))
    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def make_node(node_id, cfg, model, params, replicate=False):
    from repro.core.forwarding import ForwardingConfig
    from repro.overlay.model_node import ModelNode
    from repro.serving.engine import RealEngine
    eng = RealEngine(cfg, model, params, max_len=MAX_LEN)
    return ModelNode(node_id, use_crypto=False, real_engine=eng,
                     fwd_cfg=ForwardingConfig(replicate=replicate))


def overlay(nodes):
    from repro.net.simnet import SimNet
    from repro.overlay.probe import ResponseSink
    net = SimNet(seed=0)
    for nd in nodes:
        net.add_node(nd.node_id, nd)
    ids = [nd.node_id for nd in nodes]
    for nd in nodes:
        nd.join_group(ids)
    sink = ResponseSink()
    net.add_node("sink", sink)
    return net, sink


def serve(net, node, reqs, forwarded=False):
    """Send ``{msg_id: tokens}`` to ``node`` and run the overlay until
    every response is back.  Returns the engine Results ``node`` handed
    out meanwhile, in the order it handed them out."""
    from repro.overlay.probe import direct_payload
    results = []
    drain = node._drain_real

    def record(rid):
        r = drain(rid)
        results.append(r)
        return r
    node._drain_real = record
    try:
        for mid, toks in reqs.items():
            net.call_after(0.01, node._process, net,
                           direct_payload(mid, toks, MAX_NEW), forwarded)
        net.run_until(net.t + 600)
    finally:
        del node._drain_real
    return results


def prompts(rng, vocab):
    """Six prompts of 100-600 tokens; ``share`` repeats the first 256
    tokens of ``p2`` and is served after it."""
    lens = {"p0": 100, "p1": 230, "p2": SHARED_LEN + 90, "p3": 450,
            "p4": 600}
    ps = {k: rng.integers(1, vocab, n).tolist() for k, n in lens.items()}
    ps["share"] = ps["p2"][:SHARED_LEN] + rng.integers(1, vocab, 150).tolist()
    return ps


def max_delta(got, ref):
    import numpy as np
    return float(np.abs(np.asarray(got, np.float64)
                        - np.asarray(ref, np.float64)).max())


def compare_logits(name, got, ref):
    """max |delta| within LOGIT_TOL, and the same top-1 token wherever the
    reference's top-1 margin exceeds it."""
    import numpy as np
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          f"{name}: finite logits {got.shape}")
    delta = max_delta(got, ref)
    log(f"{name}: max |delta| = {delta!r} (tolerance {LOGIT_TOL}); "
        f"reference logits std {float(ref.std())!r}")
    check(delta <= LOGIT_TOL, f"{name}: logits within tolerance")
    top2 = np.sort(ref, axis=-1)[..., -2:]
    clear = (top2[..., 1] - top2[..., 0]) > LOGIT_TOL
    same = got.argmax(-1) == ref.argmax(-1)
    check(bool(same[clear].all()),
          f"{name}: top-1 agrees on {int(clear.sum())} of {clear.size} "
          f"rows with a clear margin")


# ---------------------------------------------------------------- phases
def logit_check(engine, model, toks):
    """Served paged prefills of prefixes of ``toks`` against one
    full-sequence forward of ``toks``.  Each prefix aliases the cached
    pages of ``toks`` and prefills a different suffix (576 tokens is a
    whole-page hit, which replays its last token query-only)."""
    import jax
    import jax.numpy as jnp
    from repro.serving.engine import Request
    lens = [n for n in (600, 599, 577, 576, 560, 500, 429, 300)
            if n <= len(toks)]
    states = engine.prefill_requests(
        [Request(-1 - i, toks[:n], max_new=1) for i, n in enumerate(lens)])
    for st in states:
        engine.release_pages(st.pages)
    log("logit check: served prefills of " + ", ".join(
        f"{st.pos} tokens ({st.matched} cached)" for st in states))
    ref = jax.jit(model.apply)(engine.params, jnp.asarray([toks], jnp.int32))
    vocab = model.cfg.vocab
    compare_logits("served prefill vs LM.apply",
                   jnp.concatenate([st.logits[:, :vocab] for st in states]),
                   ref[0, jnp.asarray(lens) - 1, :vocab])


def _cached(engine, toks, need):
    n, entry = engine.prefix_cache.peek(toks)
    check(n > need, f"kernel check prompt: {n} cached tokens")
    return n, entry.handle.pages[:n // engine.block]


def kernel_check(engine, prompt_list, offsets=(1, 40, 97, 190)):
    """One decode batch (query-only, over cached pages: one row per
    prompt and offset from its cached end) with the Pallas paged kernel
    and with the jnp gather path.  The same batch with each prompt's rows
    reading another prompt's pages must miss the tolerance, or the check
    could not see a wrong KV read."""
    import jax
    import numpy as np
    model, vocab = engine.model, engine.cfg.vocab
    n_off = len(offsets)
    B = len(prompt_list) * n_off
    pt = np.zeros((B, engine.max_pages), np.int32)
    tok = np.zeros((B, 1), np.int32)
    pos = np.zeros((B,), np.int32)
    for i, toks in enumerate(prompt_list):
        n, pages = _cached(engine, toks, max(offsets))
        for j, off in enumerate(offsets):
            r = i * n_off + j
            pt[r, :len(pages)] = pages
            tok[r, 0], pos[r] = toks[n - off], n - off
    outs, compiled = {}, {}
    for impl in ("pallas", "ref"):
        def query(params, arena, pt, tok, pos, impl=impl):
            return model.step_paged(params, arena, pt, tok, pos, write=None,
                                    impl=impl)[0][:, -1, :vocab]

        args = (engine.params, engine.arena, pt, tok, pos)
        compiled[impl] = jax.jit(query).lower(*args).compile()
        outs[impl] = compiled[impl](*args)
    check("tpu_custom_call" not in compiled["ref"].as_text(),
          "jnp decode has no custom call")
    compare_logits("kernel decode vs jnp decode", outs["pallas"],
                   outs["ref"])
    wrong = compiled["ref"](engine.params, engine.arena,
                            np.roll(pt, n_off, axis=0), tok, pos)
    delta = max_delta(wrong, outs["pallas"])
    log(f"wrong page table vs kernel decode: max |delta| = {delta!r}")
    check(delta > LOGIT_TOL, "a wrong page table misses the tolerance")


def prefill_kernel_check(engine, prompt_list, rows=4, starts=(0, 64, 192)):
    """One packed prefill dispatch of the engine's grid width: chunks of
    the cached prompts at block-aligned starts re-prefilled over their own
    pages (the arena is not donated, so the engine's stays as it is), the
    last row left masked, with the kernel and with the jnp gather path:
    the logits of every position of every live row agree."""
    import jax
    import numpy as np
    model, vocab, blk = engine.model, engine.cfg.vocab, engine.block
    grid = list(zip(prompt_list, starts))[:rows - 1]
    pt = np.zeros((rows, engine.max_pages), np.int32)
    tok = np.zeros((rows, blk), np.int32)
    pos0 = np.zeros((rows,), np.int32)
    n_tok = np.zeros((rows,), np.int32)
    for r, (toks, p0) in enumerate(grid):
        _, pages = _cached(engine, toks, p0 + blk)
        pt[r, :len(pages)] = pages
        tok[r], pos0[r], n_tok[r] = toks[p0:p0 + blk], p0, blk
    outs = {}
    for impl in ("pallas", "ref"):
        def prefill(params, arena, pt, tok, pos0, n_tok, impl=impl):
            return model.step_paged(params, arena, pt, tok, pos0, n_tok=n_tok,
                                    write="page", impl=impl)[0]

        outs[impl] = jax.jit(prefill)(engine.params, engine.arena, pt, tok,
                                      pos0, n_tok)[:, :, :vocab]
    live = np.flatnonzero(n_tok)
    compare_logits("kernel prefill vs jnp prefill",
                   np.asarray(outs["pallas"])[live],
                   np.asarray(outs["ref"])[live])


def served_programs_check(engine, rows):
    """The node's own jitted decode and prefill programs, compiled for
    the shapes the scheduler serves, contain the paged kernel."""
    import numpy as np
    i32 = np.int32
    pt = np.zeros((rows, engine.max_pages), i32)
    act = np.zeros((rows,), bool)
    for name, fn, tok in (
            ("decode", engine._decode_batched, np.zeros((rows, 1), i32)),
            ("prefill", engine._prefill_paged_batched,
             np.zeros((rows, engine.block), i32))):
        text = fn.lower(engine.params, engine.arena, pt, tok,
                        np.zeros((rows,), i32), act).compile().as_text()
        check("tpu_custom_call" in text,
              f"served {name} program compiled to tpu_custom_call")


def page_roundtrip(engine, toks):
    """Export the cached pages of ``toks`` and import them again under
    fresh digests: the imported pages must hold the same bytes."""
    import jax
    import numpy as np
    from repro.models.lm import arena_gather_pages
    n, entry = engine.prefix_cache.peek(toks)
    depth = n // engine.block
    buf = engine.export_pages(entry.handle, depth=depth)
    chains = [bytes([0xEE, d]) * 8 for d in range(depth)]
    handle = engine.import_pages(buf, chains)
    src = arena_gather_pages(engine.arena, list(entry.handle.pages[:depth]))
    dst = arena_gather_pages(engine.arena, list(handle.pages))
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(src), jax.tree.leaves(dst)))
    check(same, f"page export/import round trip of {depth} pages "
                f"({buf['mode']} wire) lands the same bytes")


def one_chip(cfg, seed, device):
    import jax
    import numpy as np
    from repro.models.lm import build_model
    model = build_model(cfg)
    t = time.perf_counter()
    params = init_params(model, seed, device)
    leaves = jax.tree.leaves(params)
    log(f"params: {sum(x.size for x in leaves)} in {leaves[0].dtype}, "
        f"made in {time.perf_counter() - t!r} s")
    node = make_node("m0", cfg, model, params)
    net, sink = overlay([node])
    eng = node.real_engine
    log(f"arena: {eng.num_pages} pages of {eng.block} tokens, "
        f"{eng.page_bytes * eng.num_pages} bytes")
    ps = prompts(np.random.default_rng(seed), cfg.vocab)
    first = {k: v for k, v in ps.items() if k != "share"}
    t = time.perf_counter()
    results = serve(net, node, first)
    shared = serve(net, node, {"share": ps["share"]})
    results += shared
    log(f"served {len(ps)} requests in {time.perf_counter() - t!r} s")
    check(set(sink.got) == set(ps), "every request completed")
    check(sorted(r.output for r in results) == sorted(sink.got.values()),
          "the engine's outputs are the ones delivered")
    for r in results:
        check(len(r.output) == MAX_NEW,
              f"request {r.req_id}: {MAX_NEW} tokens delivered")
        log(f"request {r.req_id}: prompt={r.prompt_tokens} "
            f"cached={r.cached_tokens} queued_s={r.queued_s!r} "
            f"ttft_s={r.ttft!r} wall_s={r.total!r} "
            f"tokens={r.output}")
    check(len(shared) == 1 and shared[0].cached_tokens >= SHARED_LEN,
          f"shared-prefix request reused >= {SHARED_LEN} cached tokens")
    log(f"attention visited {eng.attn_pages_read} of "
        f"{eng.attn_pages_tabled} tabled pages: "
        f"{eng.attn_pages_read / eng.attn_pages_tabled!r}")
    logit_check(eng, model, ps["p4"])
    cached = [ps[k] for k in ("p1", "p2", "p3", "p4")]
    kernel_check(eng, cached)
    prefill_kernel_check(eng, cached[1:], rows=node._real_sched.max_active)
    served_programs_check(eng, node._real_sched.max_active)
    page_roundtrip(eng, ps["p2"][:SHARED_LEN])


def four_chips(cfg, seed, devices):
    """Four one-chip nodes behind the router: the holder of a shared
    prefix is vetoed, so its siblings are routed to replicate, and the
    pages cross chips.  The same siblings served on the holder's own chip
    are the reference."""
    import jax
    import numpy as np
    from repro.models.lm import build_model
    check(len(devices) >= 4, f"{len(devices)} devices (need 4)")
    model = build_model(cfg)
    params0 = init_params(model, seed, devices[0])
    nodes = [make_node(f"m{i}", cfg, model,
                       params0 if i == 0 else jax.device_put(params0, d),
                       replicate=True)
             for i, d in enumerate(devices[:4])]
    net, sink = overlay(nodes)
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab, SHARED_LEN).tolist()
    tails = [rng.integers(1, cfg.vocab, n).tolist() for n in (20, 40, 90, 130)]
    serve(net, nodes[0], {"seed": shared + tails[0]}, forwarded=True)
    for nd in nodes:
        nd.broadcast_state(net)
    net.run_until(net.t + 5)
    # every node sees its peers loaded and full, the holder among them:
    # a sibling stays on the node it entered, which pulls the pages
    for nd in nodes:
        for pid, peer in nd.peers.items():
            if pid != nd.node_id:
                peer.active_requests = 6
                peer.kv_pressure = 0.95
    sibs = {f"sib{i}": shared + tails[i] for i in (1, 2, 3)}
    t = time.perf_counter()
    for i, (mid, toks) in enumerate(sibs.items(), start=1):
        serve(net, nodes[i], {mid: toks})
    log(f"four nodes served {len(sibs)} siblings in "
        f"{time.perf_counter() - t!r} s")
    check(set(sibs) <= set(sink.got), "every sibling completed")

    def total(key):
        return sum(nd.metrics[key] for nd in nodes)

    for key in ("replicate_routes", "kv_fetches", "kv_imported_pages",
                "kv_import_failures", "kv_fallbacks"):
        log(f"{key} = {total(key)}")
    check(total("replicate_routes") > 0, "decide() routed to replicate")
    check(all(nd.metrics["kv_imported_pages"] > 0 for nd in nodes[1:]),
          "pages crossed from chip 0 to every other chip")
    check(total("kv_import_failures") == 0, "no import failed")
    for i, nd in enumerate(nodes):
        eng = nd.real_engine
        held = {d for x in jax.tree.leaves((eng.params, eng.arena))
                for d in x.devices()}
        if nd._real_sched is not None:
            held |= nd._real_sched._logits.devices()
        check(held == {devices[i]}, f"{nd.node_id}: every array on "
                                    f"{devices[i]}")
    ref = {f"ref_{mid}": toks for mid, toks in sibs.items()}
    serve(net, nodes[0], ref, forwarded=True)
    for mid in sibs:
        log(f"{mid}: {sink.got[mid]}")
        check(sink.got[mid] == sink.got[f"ref_{mid}"],
              f"{mid}: same tokens as on one device")


# ---------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-node, four-chip phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU: JAX platform is {platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import ENV, compile_cache_dir
    cache = compile_cache_dir(ROOT)
    if cache is not None:
        jax.config.update("jax_compilation_cache_dir", str(cache))
    log(f"device_kind={devices[0].device_kind!r} count={len(devices)} "
        f"compile cache={cache or '$' + ENV}")

    compile_s = defaultdict(float)

    def on_event(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[kw.get("fun_name", "?")] += secs
    jax.monitoring.register_event_duration_secs_listener(on_event)

    from repro.configs.base import get_config
    cfg = serving_config(get_config(MODEL))
    t = time.perf_counter()
    if args.four_chips:
        four_chips(cfg, args.seed, devices)
        used = 4
    else:
        one_chip(cfg, args.seed, devices[0])
        used = 1
    log(f"phase wall {time.perf_counter() - t!r} s")
    for name, secs in sorted(compile_s.items(), key=lambda kv: -kv[1]):
        log(f"compile_s {name} {secs!r}")
    for d in devices[:used]:
        stats = d.memory_stats() or {}
        log(f"{d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
