"""The Llama/Mistral decoder without biases: every layer the same block,
RMSNorm, grouped-query causal attention with rotary positions
(rotate-half) and an optional sliding window, then a SwiGLU MLP.

Leaves of one layer: ``attn_norm`` (d), ``wq`` (d, nq, dh), ``wk``/``wv``
(d, nkv, dh), ``wo`` (nq, dh, d), ``mlp_norm`` (d), ``w_gate``/``w_up``
(d, ff), ``w_down`` (ff, d).  The program runs it as a ``dense`` family
``ArchConfig`` whose every layer is ``attn`` + ``dense``.

The counts follow ``ArchConfig.param_counts`` of the program (attention
and a gated MLP per layer, an untied head), copied here so that the
yardstick stays fixed.  Attention is counted at the published head
count, whatever padding the program adds.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from arith import BF16
from reference import QUERY_BLOCK, _mm, _rms
from weights import NORM_STD

KIND = "block"
LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
          "w_up", "w_down")
# configuration-file key -> ArchConfig field, for the widths that must
# equal the program's registered configuration
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
          "vocab_size": "vocab", "num_hidden_layers": "n_layers",
          "rope_theta": "rope_theta"}


def dims(spec: dict) -> dict:
    """Model sizes of a configuration file, by short name."""
    return {"d": spec["hidden_size"], "ff": spec["intermediate_size"],
            "nq": spec["num_attention_heads"],
            "nkv": spec["num_key_value_heads"], "dh": spec["head_dim"],
            "V": spec["vocab_size"], "L": spec["num_hidden_layers"]}


def groups(spec: dict) -> list:
    return [(KIND, spec["num_hidden_layers"])]


def leaves(spec: dict, kind: str) -> dict:
    """``name -> (shape, std, is_norm)`` of the leaves of one layer."""
    m = dims(spec)
    d, ff, nq, nkv, dh = (m[k] for k in ("d", "ff", "nq", "nkv", "dh"))
    fan = 1.0 / math.sqrt(d)
    return {
        "attn_norm": ((d,), NORM_STD, True),
        "wq": ((d, nq, dh), fan, False),
        "wk": ((d, nkv, dh), fan, False),
        "wv": ((d, nkv, dh), fan, False),
        "wo": ((nq, dh, d), 1.0 / math.sqrt(nq * dh), False),
        "mlp_norm": ((d,), NORM_STD, True),
        "w_gate": ((d, ff), fan, False),
        "w_up": ((d, ff), fan, False),
        "w_down": ((ff, d), 1.0 / math.sqrt(ff), False),
    }


# -- the program's configuration and parameter tree -------------------------

def arch_config(spec: dict, check: bool = True):
    """The program's ``ArchConfig`` for ``spec``.  With ``check``, every
    width and the window must equal the registered configuration, apart
    from the keys ``spec`` lists as reduced."""
    from repro.configs.base import get_config
    reg = get_config(spec["program_config"])
    if reg.family != "dense" or any(s.mixer != "attn" or s.ffn != "dense"
                                    for s in reg.pattern):
        raise ValueError(f"{reg.name}: not a dense attention decoder")
    if (spec["hidden_act"], reg.act, reg.glu, reg.norm) != \
            ("silu", "silu", True, "rms") or \
            spec["tie_word_embeddings"] != reg.tie_embeddings:
        raise ValueError(f"{reg.name}: not a SwiGLU/RMSNorm decoder with "
                         f"the configuration's head")
    window = spec.get("sliding_window")
    if check:
        diff = {k: (spec[k], getattr(reg, f)) for k, f in WIDTHS.items()
                if k not in spec["reduced"] and spec[k] != getattr(reg, f)}
        if {s.window for s in reg.pattern} != {window}:
            diff["sliding_window"] = (window, reg.pattern[0].window)
        if diff:
            raise ValueError(f"{spec['name']} differs from the program's "
                             f"{reg.name}: {diff}")
    dtype = spec["torch_dtype"]
    return dataclasses.replace(
        reg, pattern=tuple(dataclasses.replace(s, window=window)
                           for s in reg.pattern),
        dtype=dtype, param_dtype=dtype,
        **{f: spec[k] for k, f in WIDTHS.items()})


def program_params(canon: dict, model):
    """The benchmark's weights in the program's parameter tree; the tree
    and every shape must be what ``model.init`` makes."""
    lay, = canon["layers"]
    params = {
        "embed": canon["embed"],
        "final_norm": {"scale": canon["final_norm"]},
        "lm_head": canon["lm_head"],
        "blocks": ({"norm1": {"scale": lay["attn_norm"]},
                    "mixer": {n: lay[n] for n in ("wq", "wk", "wv", "wo")},
                    "norm2": {"scale": lay["mlp_norm"]},
                    "ffn": {n: lay[n] for n in ("w_gate", "w_up",
                                                "w_down")}},),
    }
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if got != want:
        raise ValueError(f"the program's parameter tree changed:\n"
                         f"want {want}\ngot {got}")
    return params


# -- the float32 forward of one layer ----------------------------------------

def _rope(x, pos, theta):
    """x: (S, H, dh); rotate-half convention."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # (S, dh/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, window):
    """q: (S, nq, dh); k, v: (S, nkv, dh); causal, query blocks of 512."""
    S, nq, dh = q.shape
    nkv = k.shape[1]
    g = nq // nkv
    qb = q.reshape(S // QUERY_BLOCK, QUERY_BLOCK, nkv, g, dh)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) / math.sqrt(dh)
        ok = kpos[None] <= qpos[:, None]
        if window is not None:
            ok &= qpos[:, None] - kpos[None] < window
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    o = jax.lax.map(block, (jnp.arange(S // QUERY_BLOCK), qb))
    return o.reshape(S, nq, dh)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "window",
                                              "quant"))
def _layer(w, x, *, eps, theta, window, quant):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    pos = jnp.arange(x.shape[0])
    h = _rms(x, w["attn_norm"], eps)
    q = _rope(_mm(h, w["wq"], (0,), quant), pos, theta)
    k = _rope(_mm(h, w["wk"], (0,), quant), pos, theta)
    v = _mm(h, w["wv"], (0,), quant)
    o = _attention(q, k, v, window)
    x = x + _mm(o.reshape(o.shape[0], -1),
                w["wo"].reshape(-1, w["wo"].shape[-1]), (0,), quant)
    h = _rms(x, w["mlp_norm"], eps)
    a = jax.nn.silu(_mm(h, w["w_gate"], (0,), quant))
    a = a * _mm(h, w["w_up"], (0,), quant)
    return x + _mm(a, w["w_down"], (0,), quant)


def layer(kind, w, x, spec, quant):
    return _layer(w, x, eps=float(spec["rms_norm_eps"]),
                  theta=float(spec["rope_theta"]),
                  window=spec.get("sliding_window"), quant=quant)


# -- counts -----------------------------------------------------------------

def layer_matmul_params(spec: dict) -> int:
    d, ff = spec["hidden_size"], spec["intermediate_size"]
    nq, nkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    dh = spec["head_dim"]
    return d * nq * dh + 2 * d * nkv * dh + nq * dh * d + 3 * d * ff


def matmul_params(spec: dict) -> int:
    """Parameters every token multiplies: the layers and the head."""
    return (spec["num_hidden_layers"] * layer_matmul_params(spec)
            + spec["vocab_size"] * spec["hidden_size"])


def weight_bytes(spec: dict, rows_looked_up: int = 0) -> int:
    """Bytes of bfloat16 weights a step reads once: every layer (norms
    too), the final norm, the head, and ``rows_looked_up`` embedding
    rows."""
    d = spec["hidden_size"]
    L = spec["num_hidden_layers"]
    per_layer = layer_matmul_params(spec) + 2 * d
    return BF16 * (L * per_layer + d + spec["vocab_size"] * d
                   + rows_looked_up * d)


def kv_bytes_per_token(spec: dict) -> int:
    return (BF16 * 2 * spec["num_hidden_layers"]
            * spec["num_key_value_heads"] * spec["head_dim"])


def attn_context(spec: dict, pos: int) -> int:
    """Keys a query at position ``pos`` attends to (the window, if any)."""
    w = spec.get("sliding_window")
    return min(pos + 1, w) if w else pos + 1


def token_flops(spec: dict, pos: int) -> float:
    """Model operations of one token at position ``pos``: two per matmul
    parameter, and QK plus PV over its context at the published heads."""
    attn = (4 * spec["num_hidden_layers"] * spec["num_attention_heads"]
            * spec["head_dim"] * attn_context(spec, pos))
    return 2.0 * matmul_params(spec) + attn


def prefill_flops(spec: dict, cached: int, prompt: int) -> float:
    """Operations of prefilling positions ``cached .. prompt-1``."""
    n = prompt - cached
    if n <= 0:
        return 0.0
    w = spec.get("sliding_window")
    if w:
        return sum(token_flops(spec, p) for p in range(cached, prompt))
    # sum of (p + 1) over p in [cached, prompt)
    ctx = (prompt * (prompt + 1) - cached * (cached + 1)) // 2
    return (2.0 * matmul_params(spec) * n
            + 4.0 * spec["num_hidden_layers"]
            * spec["num_attention_heads"] * spec["head_dim"] * ctx)


def decode_least_bytes(spec: dict, contexts: list) -> int:
    """Least bytes one decode step over rows with these context lengths
    must move: every weight once, the embedding rows of the batch, and
    the K/V of every live token of the active rows."""
    return (weight_bytes(spec, rows_looked_up=len(contexts))
            + kv_bytes_per_token(spec) * sum(contexts))


def decode_flops(spec: dict, contexts: list) -> float:
    """Operations of one decode step: a token at position ``c - 1`` per
    active row of context ``c``."""
    return sum(token_flops(spec, c - 1) for c in contexts)
