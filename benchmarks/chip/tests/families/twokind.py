"""A family of two kinds of layer, for the tests: one ``wide`` layer (an
MLP) first, then ``mix`` layers (a causal running mean of the normed
states, projected), each kind with its own leaves."""
import functools

import jax
import jax.numpy as jnp

from reference import _mm, _rms

LEAVES = ("norm", "w_in", "w_out", "w_mix")


def groups(spec):
    return [("wide", 1), ("mix", spec["num_hidden_layers"] - 1)]


def leaves(spec, kind):
    d, ff = spec["hidden_size"], spec["intermediate_size"]
    if kind == "wide":
        return {"norm": ((d,), 0.1, True), "w_in": ((d, ff), d ** -0.5, False),
                "w_out": ((ff, d), ff ** -0.5, False)}
    return {"norm": ((d,), 0.1, True), "w_mix": ((d, d), d ** -0.5, False)}


@functools.partial(jax.jit, static_argnames=("kind", "eps", "quant"))
def _layer(w, x, *, kind, eps, quant):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    h = _rms(x, w["norm"], eps)
    if kind == "wide":
        return x + _mm(jax.nn.silu(_mm(h, w["w_in"], (0,), quant)),
                       w["w_out"], (0,), quant)
    mean = jnp.cumsum(h, 0) / jnp.arange(1, x.shape[0] + 1)[:, None]
    return x + _mm(mean, w["w_mix"], (0,), quant)


def layer(kind, w, x, spec, quant):
    return _layer(w, x, kind=kind, eps=float(spec["rms_norm_eps"]),
                  quant=quant)


def matmul_params(spec):
    d, ff = spec["hidden_size"], spec["intermediate_size"]
    return (2 * d * ff + (spec["num_hidden_layers"] - 1) * d * d
            + spec["vocab_size"] * d)
