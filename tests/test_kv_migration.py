"""Cross-node KV page migration: pull-based page replication over the
overlay (kv_fetch / kv_pages) instead of re-prefilling vetoed prefixes.

Multi-node acceptance: with the prefix holder pressured out of affinity
routing, a second node pulls the prefix pages, admits the siblings with
ZERO prefill dispatches for the replicated blocks, and produces outputs
token-identical to prefill-from-scratch.  Plus unit coverage for the wire
codec, the export/import arena round trip, and the message schema.

Deliberately hypothesis-free so it runs even without dev extras installed.
"""
import jax
import numpy as np
import pytest

from repro.configs import base
from repro.core.forwarding import ForwardingConfig
from repro.models.lm import build_model
from repro.net import messages
from repro.net.simnet import SimNet
from repro.overlay.model_node import ModelNode
from repro.overlay.probe import ResponseSink, direct_payload
from repro.overlay.user_node import _decode, _encode
from repro.serving.engine import RealEngine, Request
from repro.serving.prefix_cache import BLOCK, _chain_hashes
from repro.training.compression import (compress_kv_blocks,
                                        decompress_kv_blocks)


@pytest.fixture(scope="module")
def gt():
    cfg = base.get_config("gentorrent-llama3-8b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


SHARED = [7] * 96                       # three full blocks


# ------------------------------------------------------------ wire codec
def test_kv_wire_codec_roundtrip():
    rng = np.random.default_rng(0)
    arr = rng.standard_normal((2, 3, 32, 2, 16)).astype(np.float32)
    raw = decompress_kv_blocks(compress_kv_blocks(arr, "raw"))
    np.testing.assert_array_equal(raw, arr)
    fp16 = decompress_kv_blocks(compress_kv_blocks(arr, "fp16"))
    assert fp16.dtype == np.float32     # cast back to the recorded dtype
    np.testing.assert_allclose(fp16, arr, rtol=1e-3, atol=1e-3)
    q = decompress_kv_blocks(compress_kv_blocks(arr, "int8"))
    # int8 with per-(repeat, page) max-abs scale: error <= scale/2
    scale = np.abs(arr).reshape(2, 3, -1).max(-1) / 127.0
    assert np.all(np.abs(q - arr) <= scale[..., None, None, None] / 2 + 1e-7)
    with pytest.raises(ValueError):
        compress_kv_blocks(arr, "gzip")


def test_kv_messages_schema():
    chains = [b"\x01" * 16, b"\x02" * 16]
    fetch = {"type": "kv_fetch", "from": "m1", "fetch_id": 1,
             "chains": chains, "depth": 2}
    assert messages.validate(fetch)
    pages = {"type": "kv_pages", "from": "m0", "fetch_id": 1, "ok": True,
             "seq": 0, "total": 1, "depth": 2, "data": b"\x00" * 32}
    assert messages.validate(pages)
    refusal = {"type": "kv_pages", "from": "m0", "fetch_id": 1, "ok": False}
    assert messages.validate(refusal)
    assert not messages.validate({"type": "kv_fetch", "from": "m1"})
    assert not messages.validate(dict(pages, data="not-bytes"))
    dec = list(messages.Decoder().feed(messages.encode(fetch)))
    assert dec and [bytes(c) for c in dec[0]["chains"]] == chains


# ------------------------------------------- engine export/import round trip
def test_export_import_pages_roundtrip(gt):
    """Raw-mode export/import lands byte-identical K/V in the importer's
    arena, registered under the same digests, with refcount parity on
    both allocators."""
    cfg, model, params = gt
    src = RealEngine(cfg, model, params, max_len=128)
    src.generate(Request(0, SHARED + [1] * 8, max_new=2))
    _, entry = src.prefix_cache.peek(SHARED)
    assert entry is not None and len(entry.handle.pages) >= 3
    src_free = src.allocator.free_count
    buf = src.export_pages(entry.handle, depth=3, mode="raw")
    assert buf["n_pages"] == 3
    # export is read-only: no refcount or allocator movement at the source
    assert src.allocator.free_count == src_free
    src.allocator.check()

    dst = RealEngine(cfg, model, params, max_len=128)
    chains = _chain_hashes(SHARED)
    handle = dst.import_pages(buf, chains)
    assert handle.length == 3 * BLOCK
    # the digests now resolve locally and the arena bytes match exactly
    matched, got = dst.prefix_cache.peek(SHARED)
    assert matched == 96 and got.handle is handle
    for sl, dl in zip(src.arena, dst.arena):
        for n in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(sl[n][:, list(entry.handle.pages[:3])]),
                np.asarray(dl[n][:, list(handle.pages)]))
    # cache entry owns the imported pages at refcount 1
    assert all(dst.allocator.refcount(p) == 1 for p in handle.pages)
    dst.allocator.check()
    # admission aliases the imported prefix: zero prefill dispatches for
    # the replicated blocks, and releasing everything frees the pages
    d0 = dst.prefill_dispatches
    res = dst.generate(Request(1, SHARED + [9] * 8, max_new=2))
    assert res.cached_tokens == 96
    assert dst.prefill_dispatches - d0 == 1          # the 8-token tail only
    while dst.prefix_cache.pop_lru():
        pass
    assert dst.allocator.free_count == dst.num_pages - 1
    dst.allocator.check()


def test_import_rejects_short_chain(gt):
    cfg, model, params = gt
    src = RealEngine(cfg, model, params, max_len=128)
    src.generate(Request(0, SHARED, max_new=2))
    _, entry = src.prefix_cache.peek(SHARED)
    buf = src.export_pages(entry.handle, depth=3)
    dst = RealEngine(cfg, model, params, max_len=128)
    with pytest.raises(ValueError):
        dst.import_pages(buf, _chain_hashes(SHARED)[:2])
    dst.allocator.check()


# ------------------------------------------------------ multi-node flows
def _build(gt, replicate: bool):
    cfg, model, params = gt
    net = SimNet(seed=5)
    fwd = ForwardingConfig(replicate=replicate)
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
    return net, nodes, sink


def _seed_and_pressure(net, nodes):
    """Seed the shared prefix on m0, sync sketches, then make m0 look
    pressured in m1's (stale) view: load above ``affinity_load_max`` AND
    a nearly-full arena — the regime where PR-3 affinity silently dropped
    the hit and re-prefilled."""
    nodes[0]._process(net, direct_payload("seed", SHARED + [1] * 8, 2),
                      forwarded=True)
    net.run_until(net.t + 30)
    for n in nodes:
        n.broadcast_state(net)
    net.run_until(net.t + 5)
    nodes[1].peers["m0"].active_requests = 6          # rel load 1.2
    nodes[1].peers["m0"].kv_pressure = 0.95


def _run_siblings(gt, replicate: bool):
    net, nodes, sink = _build(gt, replicate)
    _seed_and_pressure(net, nodes)
    eng1 = nodes[1].real_engine
    pre_tok, pre_disp = eng1.prefill_tokens, eng1.prefill_dispatches
    for i in range(3):
        net.call_after(0.01, nodes[1]._process, net,
                       direct_payload(f"sib{i}", SHARED + [10 + i] * 8, 4))
    net.run_until(net.t + 60)
    assert len(sink.got) == 4
    return nodes, sink, eng1.prefill_tokens - pre_tok, \
        eng1.prefill_dispatches - pre_disp


def test_replicated_prefix_admits_with_zero_prefill_and_parity(gt):
    """THE acceptance flow: m1 pulls the vetoed holder's prefix pages
    once, all three siblings admit against the replica with zero prefill
    dispatches for the replicated blocks, and outputs are token-identical
    to serving the same requests by prefill-from-scratch."""
    rep_nodes, rep_sink, rep_tok, rep_disp = _run_siblings(gt, True)
    # one fetch, the other siblings piggybacked on it
    m0, m1 = rep_nodes
    assert m1.metrics["replicate_routes"] == 3
    assert m1.metrics["kv_fetches"] == 1
    assert m1.metrics["kv_fetch_piggybacks"] == 2
    assert m1.metrics["kv_imported_pages"] == 3
    assert m1.metrics["kv_fallbacks"] == 0
    assert m0.metrics["kv_exports"] == 1
    assert m0.metrics["kv_export_refused"] == 0
    # zero prefill dispatches for the replicated blocks: m1 prefilled
    # ONLY the 8-token divergence tails (one batched admission round)
    assert rep_tok == 3 * 8
    assert rep_disp == 1
    # the holder never re-prefilled either (it only exported)
    assert m0.real_engine.kv_exported_pages == 3
    # refcount parity after the burst: nothing leaked on either node
    m0.real_engine.allocator.check()
    m1.real_engine.allocator.check()

    lb_nodes, lb_sink, lb_tok, lb_disp = _run_siblings(gt, False)
    # token-identical outputs vs prefill-from-scratch...
    assert rep_sink.got == lb_sink.got
    # ...which re-prefilled the whole shared prefix on m1
    assert lb_nodes[1].metrics["kv_fetches"] == 0
    assert lb_tok == 3 * (96 + 8)
    assert lb_disp > rep_disp


def test_refusal_falls_back_to_prefill(gt):
    """Holder evicted the entry between the sketch broadcast and the
    kv_fetch: the fetch is refused and the importer serves by plain
    prefill — replication is never a correctness dependency."""
    net, nodes, sink = _build(gt, True)
    _seed_and_pressure(net, nodes)
    m0 = nodes[0].real_engine
    while m0.prefix_cache.pop_lru():      # evict everything post-broadcast
        pass
    eng1 = nodes[1].real_engine
    pre = eng1.prefill_tokens
    net.call_after(0.01, nodes[1]._process, net,
                   direct_payload("sib0", SHARED + [10] * 8, 4))
    net.run_until(net.t + 60)
    assert len(sink.got) == 2
    assert nodes[1].metrics["kv_refusals"] == 1
    assert nodes[1].metrics["kv_fallbacks"] == 1
    assert nodes[0].metrics["kv_export_refused"] == 1
    assert eng1.prefill_tokens - pre == 96 + 8       # full from-scratch
    m0.allocator.check()
    eng1.allocator.check()


def test_garbled_pages_fall_back_without_crashing(gt):
    """A byzantine/version-skewed holder's un-decodable kv_pages payload
    must degrade to plain prefill — never escape into the node's message
    loop."""
    net, nodes, sink = _build(gt, True)
    _seed_and_pressure(net, nodes)
    net.call_after(0.01, nodes[1]._process, net,
                   direct_payload("sib0", SHARED + [10] * 8, 4))
    # corrupt the holder's reply in flight: garble every kv_pages chunk
    real_send = net.send

    def tamper(src, dst, msg, size_bytes=1024):
        if isinstance(msg, dict) and msg.get("type") == "kv_pages":
            msg = dict(msg, data=b"\xde\xad" * 8)
        real_send(src, dst, msg, size_bytes)
    net.send = tamper
    net.run_until(net.t + 60)
    assert "sib0" in sink.got
    assert nodes[1].metrics["kv_import_failures"] == 1
    assert nodes[1].metrics["kv_fallbacks"] == 1
    nodes[1].real_engine.allocator.check()


def _rewrite_records(edit):
    """A tamper that re-encodes a one-chunk kv_pages payload with
    ``edit`` applied to every K/V record: it decodes, but is not an
    export of the arena."""
    def tamper(msg):
        buf = _decode(msg["data"])
        for layer in buf["layers"]:
            for rec in layer.values():
                edit(rec)
        return _encode(buf)
    return tamper


def _infinite_pages(msg):
    buf = _decode(msg["data"])
    buf["n_pages"] = float("inf")
    return _encode(buf)


@pytest.mark.parametrize("tamper", [
    lambda msg: msg["data"][:len(msg["data"]) // 2],    # truncated blob
    _rewrite_records(lambda rec: rec.update(data=rec["data"][:8])),
    _rewrite_records(lambda rec: rec.update(mode="raw", dtype="float99")),
    _rewrite_records(lambda rec: rec.update(mode="int8",
                                            shape=rec["shape"][:1])),
    _infinite_pages,
], ids=["truncated", "short_arrays", "bad_dtype", "int8_1d_shape",
        "inf_pages"])
def test_short_payload_falls_back(gt, tamper):
    """A payload cut short in flight, or one whose fields no export
    carries (short arrays, an unknown dtype, a shape the codec cannot
    read, a non-integral page count), is refused by the importer and the
    request is served by prefill."""
    net, nodes, sink = _build(gt, True)
    _seed_and_pressure(net, nodes)
    net.call_after(0.01, nodes[1]._process, net,
                   direct_payload("sib0", SHARED + [10] * 8, 4))
    real_send = net.send

    def send(src, dst, msg, size_bytes=1024):
        if isinstance(msg, dict) and msg.get("type") == "kv_pages":
            assert msg["total"] == 1
            msg = dict(msg, data=tamper(msg))
        real_send(src, dst, msg, size_bytes)
    net.send = send
    net.run_until(net.t + 60)
    assert "sib0" in sink.got
    assert nodes[1].metrics["kv_import_failures"] == 1
    assert nodes[1].metrics["kv_fallbacks"] == 1
    nodes[1].real_engine.allocator.check()


def test_unexpected_import_error_propagates(gt, monkeypatch):
    """Only a full arena or a bad payload fall back: any other error from
    ``import_pages`` (a device fault, say) escapes the message loop
    instead of vanishing into a counter."""
    net, nodes, sink = _build(gt, True)
    _seed_and_pressure(net, nodes)

    def fault(buf, chains):
        raise RuntimeError("device fault")
    monkeypatch.setattr(nodes[1].real_engine, "import_pages", fault)
    net.call_after(0.01, nodes[1]._process, net,
                   direct_payload("sib0", SHARED + [10] * 8, 4))
    with pytest.raises(RuntimeError, match="device fault"):
        net.run_until(net.t + 60)
    assert nodes[1].metrics["kv_import_failures"] == 0


def test_fetch_timeout_falls_back(gt):
    """A dead holder never answers: the fetch times out and the request
    is still served by plain prefill."""
    net, nodes, sink = _build(gt, True)
    _seed_and_pressure(net, nodes)
    net.remove_node("m0")                 # holder churns out
    net.call_after(0.01, nodes[1]._process, net,
                   direct_payload("sib0", SHARED + [10] * 8, 4))
    net.run_until(net.t + 120)
    assert "sib0" in sink.got
    assert nodes[1].metrics["kv_timeouts"] == 1
    assert nodes[1].metrics["kv_fallbacks"] == 1
    nodes[1].real_engine.allocator.check()


def test_chunked_pages_reassemble(gt):
    """A chunk budget smaller than the payload splits kv_pages into many
    messages; the importer reassembles them in order."""
    cfg, model, params = gt
    net = SimNet(seed=5)
    fwd = ForwardingConfig(replicate=True)
    nodes = [ModelNode(f"m{i}", use_crypto=False, fwd_cfg=fwd,
                       kv_chunk_bytes=1024,
                       real_engine=RealEngine(cfg, model, params,
                                              max_len=128))
             for i in range(2)]
    for n in nodes:
        net.add_node(n.node_id, n)
    for n in nodes:
        n.join_group(["m0", "m1"])
    sink = ResponseSink()
    net.add_node("sink", sink)
    _seed_and_pressure(net, nodes)
    net.call_after(0.01, nodes[1]._process, net,
                   direct_payload("sib0", SHARED + [10] * 8, 4))
    net.run_until(net.t + 60)
    assert "sib0" in sink.got
    assert nodes[1].metrics["kv_imported_pages"] == 3
    # the payload really was chunked (3 fp16 pages >> 1 KiB)
    assert nodes[1].metrics["kv_wire_bytes"] > 1024
