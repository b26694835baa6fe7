"""Unified LM facade over all assigned architecture families.

Params are nested dicts; the repeating block ``cfg.pattern`` is stacked over
``cfg.n_repeats`` and executed with ``lax.scan`` (compact HLO for the
512-device dry-run).  Three entry points per model:

  apply(params, tokens, aux)            full causal logits      (train)
  prefill(params, tokens, aux, max_len) last logits + cache     (serving)
  decode(params, cache, tokens, pos)    next logits + cache     (serving)

Caches are pytrees stacked over repeats (tuple over pattern positions):
  attn      {"k","v"}: (R, B, size, n_kv, d_head); size = window or max_len
  cross     {"k","v"}: (R, B, T_mem, n_kv, d_head)  (static, no update)
  mamba     {"conv","h"}
  mlstm     {"C","n","m","conv"}
  slstm     {"h","c","n","m"}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed import constraints
from repro.models import attention, common, mamba, moe, xlstm

MAX_LEARNED_POS = 65_536  # whisper-style learned positions table


# ==========================================================================
# Per-layer init / forward / prefill / decode
# ==========================================================================

def init_ffn(cfg, key):
    d, ff = cfg.d_model, cfg.d_ff
    pd = cfg.params_dtype
    if cfg.glu:
        kg, ku, kd = jax.random.split(key, 3)
        return {"w_gate": common.dense_init(kg, (d, ff), d, pd),
                "w_up": common.dense_init(ku, (d, ff), d, pd),
                "w_down": common.dense_init(kd, (ff, d), ff, pd)}
    ki, ko = jax.random.split(key, 2)
    return {"w_in": common.dense_init(ki, (d, ff), d, pd),
            "w_out": common.dense_init(ko, (ff, d), ff, pd)}


def ffn_forward(cfg, p, x):
    dt = cfg.compute_dtype
    act = common.act_fn(cfg.act)
    if cfg.glu:
        g = act(jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt)))
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
        return jnp.einsum("bsf,fd->bsd", g * u, p["w_down"].astype(dt))
    h = act(jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(dt)))
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"].astype(dt))


def init_layer(cfg, spec, key):
    km, kf, kn = jax.random.split(key, 3)
    p = {"norm1": common.init_norm(cfg, cfg.d_model)}
    if spec.mixer in ("attn", "cross_attn"):
        p["mixer"] = attention.init_attn(cfg, km, cross=spec.mixer == "cross_attn")
    elif spec.mixer == "mamba":
        p["mixer"] = mamba.init_mamba(cfg, km)
    elif spec.mixer == "mlstm":
        p["mixer"] = xlstm.init_mlstm(cfg, km)
    elif spec.mixer == "slstm":
        p["mixer"] = xlstm.init_slstm(cfg, km)
    if cfg.double_norm:
        p["norm1b"] = common.init_norm(cfg, cfg.d_model)
    if spec.ffn == "dense":
        p["norm2"] = common.init_norm(cfg, cfg.d_model)
        p["ffn"] = init_ffn(cfg, kf)
    elif spec.ffn == "moe":
        p["norm2"] = common.init_norm(cfg, cfg.d_model)
        p["ffn"] = moe.init_moe(cfg, kf)
    if cfg.double_norm and spec.ffn != "none":
        p["norm2b"] = common.init_norm(cfg, cfg.d_model)
    return p


def _ffn_block(cfg, spec, p, x, collect_aux=False):
    aux = 0.0
    if spec.ffn == "none":
        return x, aux
    h = common.apply_norm(cfg, p["norm2"], x)
    if spec.ffn == "dense":
        h = ffn_forward(cfg, p["ffn"], h)
    else:
        if collect_aux:
            h, aux = moe.moe_ffn(cfg, p["ffn"], h, return_aux=True)
        else:
            h = moe.moe_ffn(cfg, p["ffn"], h)
    if cfg.double_norm:
        h = common.apply_norm(cfg, p["norm2b"], h)
    return x + h, aux


def layer_forward_full(cfg, spec, p, x, positions, memory=None,
                       block_q=512, collect_aux=False):
    h = common.apply_norm(cfg, p["norm1"], x)
    if spec.mixer == "attn":
        h = attention.attn_layer_forward(cfg, p["mixer"], h, positions,
                                         window=spec.window, block_q=block_q)
    elif spec.mixer == "cross_attn":
        h = attention.attn_layer_forward(cfg, p["mixer"], h, positions,
                                         memory=memory, block_q=block_q)
    elif spec.mixer == "mamba":
        h = mamba.mamba_forward(cfg, p["mixer"], h)
    elif spec.mixer == "mlstm":
        h = xlstm.mlstm_forward(cfg, p["mixer"], h)
    elif spec.mixer == "slstm":
        h = xlstm.slstm_forward(cfg, p["mixer"], h)
    if cfg.double_norm:
        h = common.apply_norm(cfg, p["norm1b"], h)
    x = x + h
    return _ffn_block(cfg, spec, p, x, collect_aux)


def _attn_prefill(cfg, spec, p, x, positions, max_len, block_q):
    """Self-attention prefill: full forward + cache construction."""
    B, S, _ = x.shape
    q, k, v = attention.project_qkv(cfg, p, x, positions)
    o = attention.full_attention(cfg, q, k, v, positions, positions,
                                 causal=True, window=spec.window,
                                 block_q=block_q)
    out = attention.out_proj(cfg, p, o)
    size = min(spec.window, max_len) if spec.window else max_len
    nkv, dh = cfg.n_kv_heads, cfg.d_head
    kc = jnp.zeros((B, size, nkv, dh), k.dtype)
    vc = jnp.zeros((B, size, nkv, dh), v.dtype)
    tail = min(S, size)
    slots = (positions[-tail:] % size) if spec.window else positions[-tail:]
    kc = kc.at[:, slots].set(k[:, -tail:])
    vc = vc.at[:, slots].set(v[:, -tail:])
    return out, {"k": kc, "v": vc}


def _cross_prefill(cfg, p, x, positions, memory, block_q):
    out = attention.attn_layer_forward(cfg, p, x, positions, memory=memory,
                                       block_q=block_q)
    k, v = attention.project_kv_memory(cfg, p, memory)
    return out, {"k": k, "v": v}


def layer_prefill(cfg, spec, p, x, positions, max_len, memory=None,
                  block_q=512):
    h = common.apply_norm(cfg, p["norm1"], x)
    if spec.mixer == "attn":
        h, cache = _attn_prefill(cfg, spec, p["mixer"], h, positions,
                                 max_len, block_q)
    elif spec.mixer == "cross_attn":
        h, cache = _cross_prefill(cfg, p["mixer"], h, positions, memory,
                                  block_q)
    elif spec.mixer == "mamba":
        h, cache = mamba.mamba_forward(cfg, p["mixer"], h, return_cache=True)
    elif spec.mixer == "mlstm":
        h, cache = xlstm.mlstm_forward(cfg, p["mixer"], h, return_cache=True)
    elif spec.mixer == "slstm":
        h, cache = xlstm.slstm_forward(cfg, p["mixer"], h, return_cache=True)
    if cfg.double_norm:
        h = common.apply_norm(cfg, p["norm1b"], h)
    x = x + h
    x, _ = _ffn_block(cfg, spec, p, x)
    return x, cache


def _ring_kv_positions(pos, size, window):
    """Absolute position held by each ring slot after writing at ``pos``.

    slot j holds p = pos - ((pos - j) mod size); invalid if p < 0."""
    j = jnp.arange(size)
    p = pos[:, None] - ((pos[:, None] - j[None, :]) % size)
    return p  # (B, size); decode_attention masks p<0 and window


def layer_decode(cfg, spec, p, x, pos, cache, memory_unused=None,
                 active=None):
    """x: (B, 1, d); pos: (B,) absolute position of the new token;
    active: optional (B,) bool slot-pool mask (see decode_attention)."""
    B = x.shape[0]
    h = common.apply_norm(cfg, p["norm1"], x)
    if spec.mixer == "attn":
        q, k, v = attention.project_qkv(cfg, p["mixer"], h,
                                        pos[:, None], rope=True)
        size = cache["k"].shape[1]
        slot = (pos % size) if spec.window else pos
        kc, vc, _ = attention.update_cache(cache["k"], cache["v"], None,
                                           k, v, slot)
        if spec.window:
            kv_pos = _ring_kv_positions(pos, size, spec.window)
        else:
            kv_pos = jnp.broadcast_to(jnp.arange(size)[None], (B, size))
        o = attention.decode_attention(cfg, q, kc, vc, kv_pos, pos,
                                       window=spec.window, active=active)
        h = attention.out_proj(cfg, p["mixer"], o)
        cache = {"k": kc, "v": vc}
    elif spec.mixer == "cross_attn":
        dt = cfg.compute_dtype
        wq = attention._pad_heads_w(cfg, p["mixer"]["wq"].astype(dt), 1)
        q = jnp.einsum("bsd,dnh->bsnh", h, wq)
        if cfg.rope_theta > 0:
            q = common.apply_rope(q, pos[:, None], cfg.rope_theta)
        T = cache["k"].shape[1]
        kv_pos = jnp.zeros((B, T), jnp.int32)  # all valid (<= pos)
        o = attention.decode_attention(cfg, q, cache["k"], cache["v"],
                                       kv_pos, pos, active=active)
        h = attention.out_proj(cfg, p["mixer"], o)
    elif spec.mixer == "mamba":
        h, cache = mamba.mamba_decode(cfg, p["mixer"], h, cache)
    elif spec.mixer == "mlstm":
        h, cache = xlstm.mlstm_decode(cfg, p["mixer"], h, cache)
    elif spec.mixer == "slstm":
        h, cache = xlstm.slstm_decode(cfg, p["mixer"], h, cache)
    if cfg.double_norm:
        h = common.apply_norm(cfg, p["norm1b"], h)
    x = x + h
    x, _ = _ffn_block(cfg, spec, p, x)
    return x, cache


def layer_cache_zeros(cfg, spec, B, max_len, T_mem):
    dt = cfg.compute_dtype
    nkv, dh = cfg.n_kv_heads, cfg.d_head
    if spec.mixer == "attn":
        size = min(spec.window, max_len) if spec.window else max_len
        z = jnp.zeros((B, size, nkv, dh), dt)
        return {"k": z, "v": z}
    if spec.mixer == "cross_attn":
        z = jnp.zeros((B, T_mem, nkv, dh), dt)
        return {"k": z, "v": z}
    if spec.mixer == "mamba":
        return mamba.init_cache(cfg, B)
    if spec.mixer == "mlstm":
        return xlstm.empty_mlstm_state(cfg, B)
    if spec.mixer == "slstm":
        return xlstm.empty_slstm_state(cfg, B)
    raise ValueError(spec.mixer)


# ==========================================================================
# Paged KV: per-layer arena scatter/attend (pure-attention patterns only)
# ==========================================================================
#
# The paged pool replaces each request's dense ``max_len`` KV strip with
# block-granular pages in a node-wide (num_pages, BLOCK, nkv * h) arena per
# layer (stacked over repeats: (R, num_pages, BLOCK, nkv * h)).  A request
# is a page table — int32 physical page per logical block — so a prefix-
# cache hit aliases the holder's pages (refcount bump, serving/page_pool)
# instead of copying KV bytes, and pool memory scales with live tokens.
# Recurrent mixers (mamba/xLSTM) summarize the whole stream in O(1) state
# and have nothing to page; those families keep the dense slot pool.

def layer_decode_paged(cfg, spec, p, x, pos, arena, page_table,
                       active=None, write=True, impl="auto"):
    """Paged analogue of ``layer_decode`` for ``attn`` mixers.  arena:
    {"k","v"}: (P, BLOCK, nkv * h); page_table: (B, n_pg); pos: (B,).
    ``write=False`` skips the arena scatter (query-only replay over fully
    cached tokens — never mutate pages another request may alias).
    ``impl`` picks the paged attention (``attention.paged_decode_
    attention``)."""
    with jax.named_scope("qkv"):
        h = common.apply_norm(cfg, p["norm1"], x)
        q, k, v = attention.project_qkv(cfg, p["mixer"], h, pos[:, None],
                                        rope=True)
    ka, va = arena["k"], arena["v"]
    if write:
        with jax.named_scope("kv_write"):
            ka, va = attention.update_paged_cache(ka, va, k, v, page_table,
                                                  pos)
    with jax.named_scope("attention"):
        o = attention.paged_decode_attention(cfg, q, ka, va, page_table,
                                             pos, window=spec.window,
                                             active=active, impl=impl)
    with jax.named_scope("out_proj"):
        h = attention.out_proj(cfg, p["mixer"], o)
        if cfg.double_norm:
            h = common.apply_norm(cfg, p["norm1b"], h)
        x = x + h
    with jax.named_scope("mlp"):
        x, _ = _ffn_block(cfg, spec, p, x)
    return x, {"k": ka, "v": va}


def layer_prefill_paged(cfg, spec, p, x, pos0, arena, page_table,
                        block_q=64, active=None, impl="auto"):
    """One teacher-forced prefill chunk: scatter the chunk's K/V into its
    (freshly allocated) write pages, then attend over all pages.  x:
    (B, C, d) with C == BLOCK and ``pos0`` (B,) block-aligned, so the
    chunk covers exactly logical block ``pos0 // BLOCK`` of every row.

    ``active``: optional (B,) bool mask (batched admission over a shared
    chunk grid) — inactive rows scatter onto the scratch page 0 instead
    of a live page, so empty rows never corrupt the arena.

    Invariant batched admission rests on: ALL rows scatter their K/V
    into the arena before ANY row attends, in every layer.  Rows holding
    consecutive chunks of one request (with the same page table) thus
    read their earlier siblings' K/V of this layer, and the causal and
    window masks hide the later ones — one dispatch computes exactly
    what the chunks would compute one after another."""
    B, C, _ = x.shape
    ka, va = arena["k"], arena["v"]
    blk = ka.shape[1]
    positions = pos0[:, None] + jnp.arange(C)[None]
    with jax.named_scope("qkv"):
        h = common.apply_norm(cfg, p["norm1"], x)
        q, k, v = attention.project_qkv(cfg, p["mixer"], h, positions,
                                        rope=True)
    with jax.named_scope("kv_write"):
        phys = page_table[jnp.arange(B), pos0 // blk]      # (B,)
        if active is not None:
            phys = jnp.where(active, phys, 0)          # dead rows -> scratch
        ka = ka.at[phys].set(k.reshape(B, C, -1))
        va = va.at[phys].set(v.reshape(B, C, -1))
    with jax.named_scope("attention"):
        o = attention.paged_prefill_attention(cfg, q, ka, va, page_table,
                                              pos0, window=spec.window,
                                              block_q=block_q, active=active,
                                              impl=impl)
    with jax.named_scope("out_proj"):
        h = attention.out_proj(cfg, p["mixer"], o)
        if cfg.double_norm:
            h = common.apply_norm(cfg, p["norm1b"], h)
        x = x + h
    with jax.named_scope("mlp"):
        x, _ = _ffn_block(cfg, spec, p, x)
    return x, {"k": ka, "v": va}


def layer_verify_paged(cfg, spec, p, x, pos, arena, page_table, n_tok=None,
                       impl="auto"):
    """One speculation-window layer step: scatter the window's K/V by
    token position (may straddle a block boundary), then attend causally
    *inside* the window.  x: (B, W, d) with W == spec_k + 1 — row b's
    window is [committed token, draft_1 .. draft_k] starting at absolute
    position ``pos[b]``; ``n_tok`` (B,) counts the real tokens per row
    (ragged drafts; 0 = dead slot) — pad/dead tokens scatter onto the
    scratch page and their outputs are zeroed.

    Teacher-forced verification: token m's hidden state attends exactly
    the KV a sequential decode at position pos+m would see (committed
    pages plus the window's own earlier tokens, just scattered), so the
    returned logits are the sequential greedy logits for every window
    position — acceptance is decided on the host by comparing drafts
    against argmax, and rejected tail KV needs no device rollback: pages
    are append-only per row and the position mask hides anything beyond
    the committed position until it is overwritten."""
    B, W, _ = x.shape
    positions = pos[:, None] + jnp.arange(W)[None]
    with jax.named_scope("qkv"):
        h = common.apply_norm(cfg, p["norm1"], x)
        q, k, v = attention.project_qkv(cfg, p["mixer"], h, positions,
                                        rope=True)
    with jax.named_scope("kv_write"):
        ka, va = attention.update_paged_cache_window(
            arena["k"], arena["v"], k, v, page_table, pos, n_tok=n_tok)
    active = None if n_tok is None else n_tok > 0
    with jax.named_scope("attention"):
        o = attention.paged_prefill_attention(cfg, q, ka, va, page_table,
                                              pos, window=spec.window,
                                              block_q=64, active=active,
                                              impl=impl)
    with jax.named_scope("out_proj"):
        h = attention.out_proj(cfg, p["mixer"], o)
        if cfg.double_norm:
            h = common.apply_norm(cfg, p["norm1b"], h)
        x = x + h
    with jax.named_scope("mlp"):
        x, _ = _ffn_block(cfg, spec, p, x)
    return x, {"k": ka, "v": va}


def arena_gather_pages(arena, pages):
    """Gather physical pages out of a paged arena pytree: every
    {"k","v"} leaf (R, num_pages, BLOCK, nkv * h) -> (R, n, BLOCK, nkv * h)
    for the n requested pages, in order.

    The overlay's cross-node page migration uses this to lift a prefix
    entry's pages into a wire buffer (serving/engine.export_pages) — the
    same physical-page indexing ``attention.gather_pages`` applies per
    request, minus the logical-block reshape (wire pages stay
    block-granular)."""
    idx = jnp.asarray(pages, jnp.int32)
    return jax.tree.map(lambda a: a[:, idx], arena)


def arena_scatter_pages(arena, pages, blocks):
    """Inverse of ``arena_gather_pages``: write (R, n, BLOCK, nkv * h)
    block payloads into freshly allocated physical pages of every arena
    leaf (cast to the arena dtype — wire payloads may arrive fp16/int8-
    dequantized).  The caller owns the target pages (refcount 1); aliased
    pages are never scattered into.  Jit-friendly (``pages`` may be a
    traced index array): the serving engine wraps it with the arena
    donated so an import updates pages in place instead of copying the
    whole node-wide arena."""
    idx = jnp.asarray(pages, jnp.int32)

    def one(a, b):
        return a.at[:, idx].set(jnp.asarray(b, a.dtype))

    return jax.tree.map(one, arena, blocks)


# ==========================================================================
# Slot-pool cache helpers (continuous batching)
# ==========================================================================
#
# A slot pool is an ordinary decode cache built with ``cache_zeros(B=max_
# active, ...)``: leaves are (R, max_active, ...) with the batch on axis 1.
# A scheduler scatters each admitted request's single-request cache (batch
# dim 1, as produced by ``prefill``) into a free batch row, decodes the
# whole pool with ONE ``decode(..., active=mask)`` dispatch per round, and
# gathers the row back out on completion for prefix-cache insertion.  Both
# helpers accept a traced ``slot`` so a jitted wrapper compiles once.

def cache_slot_write(pool, single, slot):
    """Scatter a batch-1 cache pytree into batch row ``slot`` of ``pool``."""
    return jax.tree.map(lambda b, s: b.at[:, slot].set(s[:, 0]),
                        pool, single)


def cache_slot_read(pool, slot):
    """Gather batch row ``slot`` of ``pool`` as a batch-1 cache pytree."""
    return jax.tree.map(
        lambda b: jax.lax.dynamic_slice_in_dim(b, slot, 1, axis=1), pool)


# ==========================================================================
# Whisper-style encoder (bidirectional)
# ==========================================================================

_ENC_SPEC = None  # lazily built per call; encoder layers: attn + dense ffn


def _enc_spec():
    from repro.configs.base import LayerSpec
    return LayerSpec(mixer="attn", ffn="dense")


def init_encoder(cfg, key):
    spec = _enc_spec()
    keys = jax.random.split(key, cfg.n_enc_layers)
    layers = jax.vmap(lambda k: init_layer(cfg, spec, k))(keys)
    return {"layers": layers, "final_norm": common.init_norm(cfg, cfg.d_model)}


def encode(cfg, p, frames, block_q=512):
    """frames: (B, T, d) stub conv-frontend output -> (B, T, d)."""
    T = frames.shape[1]
    x = frames + common.sinusoidal_positions(T, cfg.d_model).astype(frames.dtype)
    positions = jnp.arange(T)
    spec = _enc_spec()

    def body(x, lp):
        h = common.apply_norm(cfg, lp["norm1"], x)
        q, k, v = attention.project_qkv(cfg, lp["mixer"], h, positions,
                                        rope=False)
        o = attention.full_attention(cfg, q, k, v, positions, positions,
                                     causal=False, block_q=block_q)
        x = x + attention.out_proj(cfg, lp["mixer"], o)
        x, _ = _ffn_block(cfg, spec, lp, x)
        return x, None

    x, _ = jax.lax.scan(body, x, p["layers"])
    return common.apply_norm(cfg, p["final_norm"], x)


# ==========================================================================
# Model facade
# ==========================================================================

class LM:
    def __init__(self, cfg):
        self.cfg = cfg

    # ---------------- params ----------------
    def init(self, key):
        cfg = self.cfg
        ke, kb, kh, kenc, kpos = jax.random.split(key, 5)
        params = {
            "embed": common.embed_init(ke, (cfg.padded_vocab, cfg.d_model),
                                       cfg.params_dtype),
            "final_norm": common.init_norm(cfg, cfg.d_model),
        }
        R = cfg.n_repeats
        keys = jax.random.split(kb, R)

        def one_repeat(k):
            ks = jax.random.split(k, len(cfg.pattern))
            return tuple(init_layer(cfg, spec, ks[i])
                         for i, spec in enumerate(cfg.pattern))

        params["blocks"] = jax.vmap(one_repeat)(keys)
        if not cfg.tie_embeddings:
            params["lm_head"] = common.embed_init(
                kh, (cfg.padded_vocab, cfg.d_model), cfg.params_dtype)
        if cfg.is_encdec:
            params["encoder"] = init_encoder(cfg, kenc)
        if cfg.rope_theta <= 0:
            params["pos_embed"] = common.embed_init(
                kpos, (MAX_LEARNED_POS, cfg.d_model), cfg.params_dtype)
        return params

    def param_specs(self):
        key = jax.random.PRNGKey(0)
        return jax.eval_shape(self.init, key)

    # ---------------- helpers ----------------
    def _embed(self, params, tokens, positions):
        cfg = self.cfg
        x = common.take_embedding(params["embed"].astype(cfg.compute_dtype),
                                  tokens, cfg.embed_scale)
        if cfg.rope_theta <= 0:
            pe = jnp.take(params["pos_embed"].astype(cfg.compute_dtype),
                          jnp.minimum(positions, MAX_LEARNED_POS - 1), axis=0)
            x = x + pe
        # re-pin batch sharding: the embed table's FSDP sharding otherwise
        # propagates into activations (see distributed/constraints.py)
        return constraints.constrain_batch(x)

    def _logits(self, params, x):
        cfg = self.cfg
        head = (params["embed"] if cfg.tie_embeddings
                else params["lm_head"]).astype(cfg.compute_dtype)
        logits = jnp.einsum("...d,vd->...v", x, head)
        logits = common.softcap(logits.astype(jnp.float32),
                                cfg.final_softcap)
        if cfg.padded_vocab != cfg.vocab:
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
            logits = jnp.where(pad_mask, -1e30, logits)
        return logits

    def _memory(self, params, aux, block_q=512):
        cfg = self.cfg
        if cfg.is_encdec:
            return encode(cfg, params["encoder"], aux["frames"], block_q)
        if cfg.n_image_tokens:
            return aux["image_embeds"]
        return None

    # ---------------- full forward (train) ----------------
    def apply(self, params, tokens, aux=None, remat=False, block_q=512,
              collect_aux=False):
        cfg = self.cfg
        B, S = tokens.shape
        positions = jnp.arange(S)
        x = self._embed(params, tokens, positions)
        memory = self._memory(params, aux or {}, block_q)

        def body(x, bp):
            aux_sum = 0.0
            for i, spec in enumerate(cfg.pattern):
                x, a = layer_forward_full(cfg, spec, bp[i], x, positions,
                                          memory=memory, block_q=block_q,
                                          collect_aux=collect_aux)
                aux_sum = aux_sum + a
            return constraints.constrain_batch(x), aux_sum

        if remat:
            from repro.distributed.remat import wrap
            body = wrap(body, "full" if remat is True else remat)
        x, auxs = jax.lax.scan(body, x, params["blocks"])
        x = common.apply_norm(cfg, params["final_norm"], x)
        logits = self._logits(params, x)
        if collect_aux:
            return logits, jnp.sum(auxs)
        return logits

    # ---------------- prefill ----------------
    def prefill(self, params, tokens, aux=None, max_len=None, block_q=512):
        cfg = self.cfg
        B, S = tokens.shape
        max_len = max_len or S
        positions = jnp.arange(S)
        x = self._embed(params, tokens, positions)
        memory = self._memory(params, aux or {}, block_q)

        def body(x, bp):
            caches = []
            for i, spec in enumerate(cfg.pattern):
                x, c = layer_prefill(cfg, spec, bp[i], x, positions, max_len,
                                     memory=memory, block_q=block_q)
                caches.append(c)
            return constraints.constrain_batch(x), tuple(caches)

        x, cache = jax.lax.scan(body, x, params["blocks"])
        x = common.apply_norm(cfg, params["final_norm"], x)
        logits = self._logits(params, x[:, -1])
        return logits, cache

    # ---------------- decode ----------------
    def decode(self, params, cache, tokens, pos, active=None):
        """tokens: (B, 1); pos: (B,) absolute position of the new token.

        ``active`` is an optional (B,) bool slot-pool mask: with a fixed
        max-batch cache, a partially occupied pool decodes with dead rows
        masked instead of recompiling for every occupancy level."""
        cfg = self.cfg
        x = self._embed(params, tokens, pos[:, None])

        def body(x, xs):
            bp, cr = xs
            new = []
            for i, spec in enumerate(cfg.pattern):
                x, c = layer_decode(cfg, spec, bp[i], x, pos, cr[i],
                                    active=active)
                new.append(c)
            return constraints.constrain_batch(x), tuple(new)

        x, cache = jax.lax.scan(body, x, (params["blocks"], cache))
        x = common.apply_norm(cfg, params["final_norm"], x)
        logits = self._logits(params, x[:, -1])
        return logits, cache

    # ---------------- paged serving (pure-attention patterns) ----------
    def supports_paging(self) -> bool:
        """Only attention KV has per-position state to page; recurrent and
        cross-attention mixers keep the dense slot pool."""
        return all(s.mixer == "attn" for s in self.cfg.pattern)

    def paged_arena_zeros(self, num_pages, block):
        """Node-wide paged KV arena: per pattern position {"k","v"} leaves
        of shape (R, num_pages, BLOCK, nkv * d_head), each page one
        contiguous run that the paged kernel reads in place (a separate
        minor d_head of 80 would pad to 128 lanes, and the TPU compiler
        would lay the pages out along the minor dimension instead).  Page
        0 is the scratch page (serving/page_pool.NULL_PAGE)."""
        cfg = self.cfg
        assert self.supports_paging(), cfg.name
        shape = (cfg.n_repeats, num_pages, block,
                 cfg.n_kv_heads * cfg.d_head)
        # one buffer per leaf: the engine donates the arena, and a buffer
        # may be donated only once per call
        return tuple({n: jnp.zeros(shape, cfg.compute_dtype)
                      for n in ("k", "v")} for _ in cfg.pattern)

    def prefill_paged(self, params, arena, page_tables, tokens, pos0,
                      active=None, impl="auto"):
        """One teacher-forced chunk of prompt prefill over the paged pool.

        tokens: (B, C) with C == BLOCK; pos0: (B,) block-aligned chunk
        start.  Scatters the chunk's K/V into each row's write page and
        returns logits for EVERY chunk position ((B, C, V) — the caller
        picks the last real token's row; pad tail K/V is overwritten by
        later writes before any mask exposes it), plus the updated arena.

        ``active`` is an optional (B,) bool mask for batched admission:
        the admitted requests' uncached chunks are packed one per row of
        ONE shared chunk grid (several rows may hold consecutive chunks
        of one request, each row with the request's whole page table), and
        rows the last dispatch of an admission leaves empty are masked
        (scratch-page writes, zeroed output) — K co-routed siblings cost
        ceil(sum(chunks) / B) dispatches instead of sum(chunks).

        ``impl`` picks the paged attention of every layer (``attention.
        paged_prefill_attention``): the kernel on a TPU by default."""
        cfg = self.cfg
        B, C = tokens.shape
        positions = pos0[:, None] + jnp.arange(C)[None]
        with jax.named_scope("embed"):
            x = self._embed(params, tokens, positions)

        def body(x, xs):
            bp, ar = xs
            new = []
            for i, spec in enumerate(cfg.pattern):
                x, a = layer_prefill_paged(cfg, spec, bp[i], x, pos0,
                                           ar[i], page_tables,
                                           active=active, impl=impl)
                new.append(a)
            return constraints.constrain_batch(x), tuple(new)

        x, arena = jax.lax.scan(body, x, (params["blocks"], arena))
        with jax.named_scope("logits"):
            x = common.apply_norm(cfg, params["final_norm"], x)
            return self._logits(params, x), arena

    def decode_paged(self, params, arena, page_tables, tokens, pos,
                     active=None, write=True, impl="auto"):
        """Paged analogue of ``decode``: tokens (B, 1), pos (B,),
        page_tables (B, n_pg) physical page per logical block.  With
        ``write=False`` the arena is returned untouched (query-only replay
        for full prefix hits — aliased pages are never mutated).  ``impl``
        picks the paged attention, as in ``prefill_paged``."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = self._embed(params, tokens, pos[:, None])

        def body(x, xs):
            bp, ar = xs
            new = []
            for i, spec in enumerate(cfg.pattern):
                x, a = layer_decode_paged(cfg, spec, bp[i], x, pos, ar[i],
                                          page_tables, active=active,
                                          write=write, impl=impl)
                new.append(a)
            return constraints.constrain_batch(x), tuple(new)

        x, arena = jax.lax.scan(body, x, (params["blocks"], arena))
        with jax.named_scope("logits"):
            x = common.apply_norm(cfg, params["final_norm"], x)
            logits = self._logits(params, x[:, -1])
        return logits, arena

    def verify_paged(self, params, arena, page_tables, tokens, pos,
                     n_tok=None, impl="auto"):
        """Speculative multi-token verify over the paged pool.

        tokens: (B, W) — per row, the committed next token followed by up
        to W-1 draft tokens; pos: (B,) absolute position of the window's
        first token; n_tok: (B,) real tokens per row (ragged drafts, 0 =
        masked slot).  Extends ``decode_paged`` to a W-token window in ONE
        dispatch: the window's K/V is scattered by token position (pad and
        dead rows go to the scratch page) and attention is causal inside
        the window, so the returned (B, W, V) logits equal W sequential
        single-token decodes — the scheduler accepts the longest draft
        prefix matching greedy argmax and rolls back rejected tail KV by
        simply not advancing the row position (append-only pages).
        ``impl`` picks the paged attention, as in ``prefill_paged``."""
        cfg = self.cfg
        B, W = tokens.shape
        positions = pos[:, None] + jnp.arange(W)[None]
        with jax.named_scope("embed"):
            x = self._embed(params, tokens, positions)

        def body(x, xs):
            bp, ar = xs
            new = []
            for i, spec in enumerate(cfg.pattern):
                x, a = layer_verify_paged(cfg, spec, bp[i], x, pos, ar[i],
                                          page_tables, n_tok=n_tok,
                                          impl=impl)
                new.append(a)
            return constraints.constrain_batch(x), tuple(new)

        x, arena = jax.lax.scan(body, x, (params["blocks"], arena))
        with jax.named_scope("logits"):
            x = common.apply_norm(cfg, params["final_norm"], x)
            return self._logits(params, x), arena

    # ---------------- cache scaffolding ----------------
    def cache_zeros(self, B, max_len, T_mem=0):
        cfg = self.cfg
        R = cfg.n_repeats

        def stack(c):
            return jax.tree.map(lambda a: jnp.broadcast_to(
                a[None], (R,) + a.shape), c)

        return tuple(stack(layer_cache_zeros(cfg, spec, B, max_len, T_mem))
                     for spec in cfg.pattern)

    def cache_specs(self, B, max_len, T_mem=0):
        return jax.eval_shape(lambda: self.cache_zeros(B, max_len, T_mem))


def build_model(cfg) -> LM:
    return LM(cfg)


# ==========================================================================
# Loss
# ==========================================================================

def lm_loss(cfg, model: LM, params, tokens, labels, aux=None, remat=True,
            block_q=512):
    """Mean next-token cross-entropy; labels < 0 are masked.

    Returns (loss, metrics).  MoE archs add the Switch load-balance aux."""
    collect = cfg.moe is not None
    out = model.apply(params, tokens, aux=aux, remat=remat, block_q=block_q,
                      collect_aux=collect)
    logits, moe_aux = out if collect else (out, 0.0)
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    safe_labels = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe_labels[..., None],
                             axis=-1)[..., 0] - logz
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = -(ll * mask).sum() / denom
    total = loss + 0.01 * moe_aux
    return total, {"ce": loss, "moe_aux": moe_aux,
                   "tokens": mask.sum()}
