"""Random weights of a benchmark configuration, made from the run's seed.

The served weights and the reference's weights both come from here, so
the reference takes nothing the program made.  Every value is an integer
from the threefry bits of its own key, times one float32 constant, cast
to bfloat16: integer work and a single IEEE multiply, so a leaf made
inside the one jitted call that builds the whole model and the same leaf
made alone for one layer of the reference are the same bits.

Layout: the configuration's family (``families/<family>.py``) gives the
ordered layer groups and the leaves of each kind of layer; every family
has the top leaves ``embed`` (V, d), ``final_norm`` (d) and ``lm_head``
(V, d).  A leaf's key is ``fold_in(fold_in(base, leaf index), layer)``:
the leaf index in the family's ``LEAVES`` followed by the top leaves, the
layer numbered over every group in order (0 for a top leaf).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import cells

TOP_LEAVES = ("embed", "final_norm", "lm_head")
NORM_STD = 0.1      # norm scales: 1 + uniform noise of this std
EMBED_STD = 1.0     # rows of unit RMS, so the norm's epsilon is immaterial
_HALF = 1 << 15


def top_leaves(spec: dict) -> dict:
    """``name -> (shape, std, is_norm)`` of the leaves outside the
    layers."""
    d, V = spec["hidden_size"], spec["vocab_size"]
    return {"embed": ((V, d), EMBED_STD, False),
            "final_norm": ((d,), NORM_STD, True),
            "lm_head": ((V, d), 1.0 / math.sqrt(d), False)}


def layer_groups(spec: dict) -> list:
    """``[(kind, range of global layer numbers), ...]`` in order."""
    out, start = [], 0
    for kind, count in cells.family(spec["family"]).groups(spec):
        out.append((kind, range(start, start + count)))
        start += count
    return out


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


def _leaf_key(spec, key, name, layer=0):
    order = cells.family(spec["family"]).LEAVES + TOP_LEAVES
    ix = order.index(name)
    return jax.random.fold_in(jax.random.fold_in(key, ix), layer)


def _layer(spec, kind, key, layer):
    shapes = cells.family(spec["family"]).leaves(spec, kind)
    return {n: _leaf(_leaf_key(spec, key, n, layer), *shape)
            for n, shape in shapes.items()}


def _top(spec, key, name):
    return _leaf(_leaf_key(spec, key, name), *top_leaves(spec)[name])


def make_params(spec: dict, seed: int, device) -> dict:
    """Every weight of the model in bfloat16 on ``device``, in one jitted
    call: ``{"layers": [{name: (count, ...)} per layer group],
    "embed": ..., "final_norm": ..., "lm_head": ...}``.  Each group's
    layers are made one after another (``lax.map``), so the call's
    temporaries are one layer's."""
    def gen(key):
        out = {n: _top(spec, key, n) for n in TOP_LEAVES}
        out["layers"] = [
            jax.lax.map(lambda i, kind=kind: _layer(spec, kind, key, i),
                        jnp.arange(layers.start, layers.stop))
            for kind, layers in layer_groups(spec)]
        return out

    fn = jax.jit(gen, out_shardings=SingleDeviceSharding(device))
    return jax.block_until_ready(fn(base_key(seed)))


class LeafMaker:
    """One layer's weights, or one top-level leaf, at a time: what the
    reference loads while it walks the model layer by layer."""

    def __init__(self, spec: dict, seed: int):
        self.key = base_key(seed)
        self._layer = {kind: jax.jit(lambda k, i, kind=kind:
                                     _layer(spec, kind, k, i))
                       for kind, _ in layer_groups(spec)}
        self._top = {n: jax.jit(lambda k, n=n: _top(spec, k, n))
                     for n in TOP_LEAVES}

    def layer(self, kind: str, i: int) -> dict:
        """Layer ``i`` (its global number), of kind ``kind``."""
        return self._layer[kind](self.key, jnp.int32(i))

    def top(self, name: str):
        return self._top[name](self.key)
