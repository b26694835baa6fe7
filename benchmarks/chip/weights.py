"""Random weights of a benchmark configuration, made from the run's seed.

The served weights and the reference's weights both come from here, so
the reference takes nothing the program made.  Every value is an integer
from the threefry bits of its own key, times one float32 constant, cast
to bfloat16: integer work and a single IEEE multiply, so a leaf made
inside the one jitted call that builds the whole model and the same leaf
made alone for one layer of the reference are the same bits.

Layout (a Llama/Mistral decoder without biases): per layer ``attn_norm``
(d), ``wq`` (d, nq, dh), ``wk``/``wv`` (d, nkv, dh), ``wo`` (nq, dh, d),
``mlp_norm`` (d), ``w_gate``/``w_up`` (d, ff), ``w_down`` (ff, d); then
``embed`` (V, d), ``final_norm`` (d), ``lm_head`` (V, d).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                "w_up", "w_down")
TOP_LEAVES = ("embed", "final_norm", "lm_head")
NORM_STD = 0.1      # norm scales: 1 + uniform noise of this std
EMBED_STD = 1.0     # rows of unit RMS, so the norm's epsilon is immaterial
_HALF = 1 << 15


def dims(spec: dict) -> dict:
    """Model sizes of a configuration file, by short name."""
    return {"d": spec["hidden_size"], "ff": spec["intermediate_size"],
            "nq": spec["num_attention_heads"],
            "nkv": spec["num_key_value_heads"], "dh": spec["head_dim"],
            "V": spec["vocab_size"], "L": spec["num_hidden_layers"]}


def leaf_shapes(spec: dict) -> dict:
    """``name -> (shape, std, is_norm)`` of every leaf; layer leaves are
    the shapes of one layer."""
    m = dims(spec)
    d, ff, nq, nkv, dh, V = (m[k] for k in ("d", "ff", "nq", "nkv", "dh",
                                            "V"))
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
        "embed": ((V, d), EMBED_STD, False),
        "final_norm": ((d,), NORM_STD, True),
        "lm_head": ((V, d), fan, False),
    }


def base_key(seed: int):
    """A key for any whole number up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not a 64-bit whole number")
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def _leaf(key, shape, std, is_norm):
    """Uniform values of the given std (around 1 for a norm scale)."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    ints = (bits >> 16).astype(jnp.int32) - _HALF
    step = std * math.sqrt(3.0) / _HALF
    if is_norm:
        ints = ints + round(1.0 / step)      # centre 1, still one multiply
    return (ints.astype(jnp.float32) * jnp.float32(step)).astype(jnp.bfloat16)


def _leaf_key(key, name, layer=0):
    ix = (LAYER_LEAVES + TOP_LEAVES).index(name)
    return jax.random.fold_in(jax.random.fold_in(key, ix), layer)


def _layer(spec, key, layer):
    shapes = leaf_shapes(spec)
    return {n: _leaf(_leaf_key(key, n, layer), *shapes[n])
            for n in LAYER_LEAVES}


def _top(spec, key, name):
    return _leaf(_leaf_key(key, name), *leaf_shapes(spec)[name])


def make_params(spec: dict, seed: int, device) -> dict:
    """Every weight of the model in bfloat16 on ``device``, in one jitted
    call: ``{"layers": {name: (L, ...)}, "embed": ..., "final_norm": ...,
    "lm_head": ...}``.  Layers are made one after another (``lax.map``),
    so the call's temporaries are one layer's."""
    L = dims(spec)["L"]

    def gen(key):
        out = {n: _top(spec, key, n) for n in TOP_LEAVES}
        out["layers"] = jax.lax.map(lambda i: _layer(spec, key, i),
                                    jnp.arange(L))
        return out

    fn = jax.jit(gen, out_shardings=SingleDeviceSharding(device))
    return jax.block_until_ready(fn(base_key(seed)))


class LeafMaker:
    """One layer's weights, or one top-level leaf, at a time: what the
    reference loads while it walks the model layer by layer."""

    def __init__(self, spec: dict, seed: int):
        self.key = base_key(seed)
        self._layer = jax.jit(lambda k, i: _layer(spec, k, i))
        self._top = {n: jax.jit(lambda k, n=n: _top(spec, k, n))
                     for n in TOP_LEAVES}

    def layer(self, i: int) -> dict:
        return self._layer(self.key, jnp.int32(i))

    def top(self, name: str):
        return self._top[name](self.key)
