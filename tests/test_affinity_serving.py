"""Prefix-affinity overlay forwarding + batched admission prefill.

Multi-node acceptance: with 2+ model nodes on SimNet, affinity routing is
token-identical to load-only routing while doing strictly less duplicate
prefill work, and a whole admission round of co-routed siblings costs ONE
batched ``prefill_paged`` dispatch (shared chunk grid, masked tail rows).

Deliberately hypothesis-free so it runs even without dev extras installed.
"""
import jax
import pytest

from repro.configs import base
from repro.core.forwarding import ForwardingConfig
from repro.models.lm import build_model
from repro.net import messages
from repro.net.simnet import SimNet
from repro.overlay.model_node import ModelNode
from repro.overlay.probe import ResponseSink, direct_payload
from repro.serving.engine import RealEngine, Request
from repro.serving.scheduler import Scheduler


@pytest.fixture(scope="module")
def gt():
    cfg = base.get_config("gentorrent-llama3-8b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


SHARED = [7] * 64                       # two full blocks


# ---------------------------------------------------- batched admission
def test_admission_round_is_single_prefill_dispatch(gt):
    """K co-routed siblings whose divergence suffixes fit one BLOCK cost
    exactly ONE prefill_paged dispatch for the whole admission round."""
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128)
    s = Scheduler(eng, max_active=4)
    s.submit(Request(0, SHARED + [1] * 8, max_new=2))    # seed the cache
    s.run()
    s.done.clear()
    d0 = eng.prefill_dispatches
    for i in range(3):
        s.submit(Request(10 + i, SHARED + [20 + i] * 8, max_new=4))
    s.step()                             # one admission round, 3 siblings
    assert s.metrics["admitted"] == 4
    assert eng.prefill_dispatches - d0 == 1
    out = {r.req_id: r.output for r in s.run()}
    assert all(len(v) == 4 for v in out.values())
    assert all(r.cached_tokens == 64 for r in s.done + [])


def test_batched_admission_matches_per_request(gt):
    """Mixed suffix lengths across the shared chunk grid (masked tail
    rows) must reproduce the per-request admission outputs exactly."""
    cfg, model, params = gt
    lengths = (20, 90, 40, 33)
    prompts = [[(37 * i + j) % cfg.vocab for j in range(n)]
               for i, n in enumerate(lengths)]
    ref_eng = RealEngine(cfg, model, params, max_len=128)
    ref = {i: ref_eng.generate(Request(i, p, max_new=6)).output
           for i, p in enumerate(prompts)}

    eng = RealEngine(cfg, model, params, max_len=128)
    states = eng.prefill_requests(
        [Request(i, p, max_new=6) for i, p in enumerate(prompts)], batch=4)
    s = Scheduler(eng, max_active=4)
    for i, p in enumerate(prompts):
        s.submit(Request(i, p, max_new=6))
    out = {r.req_id: r.output for r in s.run()}
    assert out == ref
    # the direct prefill_requests states agree with per-request admission
    for st, p in zip(states, prompts):
        assert st.pos == len(p)
        eng.release_pages(st.pages)
    # the shared grid compiled once despite per-round occupancy changing
    assert eng.batched_prefill_traces == 1
    eng.allocator.check()


def test_batched_admission_full_hit_replay(gt):
    """A block-aligned fully cached prompt admitted in a batch replays
    query-only (no grid step for it) and still decodes correctly."""
    cfg, model, params = gt
    eng = RealEngine(cfg, model, params, max_len=128)
    s = Scheduler(eng, max_active=2)
    s.submit(Request(0, SHARED, max_new=4))
    ref = {r.req_id: r.output for r in s.run()}[0]
    s.submit(Request(1, SHARED, max_new=4))              # full 64-token hit
    s.submit(Request(2, SHARED + [9] * 4, max_new=4))    # 4-token suffix
    out = {r.req_id: r.output for r in s.run()}
    assert out[1] == ref
    eng.allocator.check()


# -------------------------------------------------- multi-node affinity
def _run_mode(gt, affinity: bool):
    """Two model nodes, seed the prefix on m0, inject siblings at m1.

    m1's (stale) view shows m0 busy-but-under-threshold, so load-only
    routing keeps siblings local while affinity routing follows the
    sketch to the prefix holder."""
    cfg, model, params = gt
    net = SimNet(seed=3)
    fwd = ForwardingConfig(affinity=affinity)
    nodes = [ModelNode(f"m{i}", use_crypto=False, fwd_cfg=fwd,
                       real_engine=RealEngine(cfg, model, params,
                                              max_len=128))
             for i in range(2)]
    for n in nodes:
        net.add_node(n.node_id, n)
    members = [n.node_id for n in nodes]
    for n in nodes:
        n.join_group(members)
    sink = ResponseSink()
    net.add_node("sink", sink)
    nodes[0]._process(net, direct_payload("seed", SHARED + [1] * 8),
                      forwarded=True)
    net.run_until(net.t + 30)
    for n in nodes:
        n.broadcast_state(net)
    net.run_until(net.t + 5)
    # stale busy view of m0: 3 actives on hw 5 = relative load 0.6 — the
    # optimistic forward echo raises it to 1.0 by the third sibling,
    # exactly at the affinity_load_max bound, so ALL siblings co-route
    # while load-only routing (self at 0.0..0.4) keeps them local
    nodes[1].peers["m0"].active_requests = 3
    for i in range(3):
        net.call_after(0.01, nodes[1]._process, net,
                       direct_payload(f"sib{i}", SHARED + [10 + i] * 8))
    net.run_until(net.t + 60)
    assert len(sink.got) == 4
    return nodes, sink


def test_affinity_multinode_parity_and_fewer_prefill_bytes(gt):
    aff_nodes, aff = _run_mode(gt, affinity=True)
    lb_nodes, lb = _run_mode(gt, affinity=False)
    # token-identical outputs regardless of where routing lands
    assert aff.got == lb.got
    # affinity followed the sketch to the holder...
    assert aff_nodes[1].metrics["affinity_hits"] == 3
    assert aff_nodes[1].metrics["forwarded_out"] == 3
    # ...so only the divergence tails were prefilled (seed 72 + 3 x 8),
    # and the whole sibling round was ONE batched dispatch (the 72-token
    # seed's 3 chunks pack into one dispatch of the 4-row grid, the three
    # 8-token sibling suffixes into one more)
    aff_eng = [n.real_engine for n in aff_nodes]
    assert aff_eng[0].prefill_tokens == 72 + 3 * 8
    assert aff_eng[1].prefill_tokens == 0
    assert aff_eng[0].prefill_dispatches == 1 + 1
    # load-only kept siblings on the idle node and re-prefilled the
    # shared prefix from scratch there
    lb_eng = [n.real_engine for n in lb_nodes]
    assert lb_nodes[1].metrics["affinity_hits"] == 0
    assert lb_eng[1].prefill_tokens == 3 * 72
    dup = sum(e.prefill_tokens for e in lb_eng) \
        - sum(e.prefill_tokens for e in aff_eng)
    assert dup >= len(SHARED)            # duplicate-prefill work eliminated


# ------------------------------------------------------- sync plumbing
def test_sync_broadcast_carries_sketch(gt):
    net = SimNet()
    a, b = ModelNode("a", use_crypto=False), ModelNode("b", use_crypto=False)
    for n in (a, b):
        net.add_node(n.node_id, n)
        n.join_group(["a", "b"])
    toks = list(range(64))
    a.engine.prefix_cache.insert(toks, None, 64 * 1024)
    a.broadcast_state(net)
    net.run_until(net.t + 5)
    assert b.peers["a"].prefix_sketch is not None
    from repro.core.forwarding import PrefixSketch
    from repro.serving.prefix_cache import _chain_hashes
    sk = PrefixSketch.from_bytes(b.peers["a"].prefix_sketch)
    assert sk.hit_depth(_chain_hashes(toks)) == 2
    # local self-view refreshed too (decide() sees its own cache)
    assert a.peers["a"].prefix_sketch == b.peers["a"].prefix_sketch


def test_hr_sync_wire_format_accepts_optional_fields():
    ok = {"type": "hr_sync", "from": "m0", "paths": [], "active": 0,
          "hw": 5.0, "kv_pressure": 0.25, "sketch": b"\x00" * 64}
    assert messages.validate(ok)
    assert messages.validate({"type": "hr_sync", "from": "m0",
                              "paths": [], "active": 0, "hw": 5.0})
    bad = dict(ok, sketch="not-bytes")
    assert not messages.validate(bad)
    enc = messages.encode(ok)
    dec = list(messages.Decoder().feed(enc))
    assert dec and dec[0]["sketch"] == b"\x00" * 64
