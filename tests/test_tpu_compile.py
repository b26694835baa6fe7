"""Compile the served path for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode accepts: Pallas blocks that
break the (8, 128) tiling rule, and programs that do not fit the chip's
memory.  These tests compile the paged decode kernel at real head widths
and the engine's full-width h2o-danube-1.8b steps (bf16 params beside the
default KV arena) against a described v5e:2x2 topology.  Nothing runs; a
pass says the chip's compiler accepts the programs and that each fits.

The topology is described inside a fixture, never at import, so that
every pytest-xdist worker collects the same tests.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import base
from repro.kernels.decode_attention.paged import \
    paged_decode_attention_pallas
from repro.models.lm import build_model
from repro.serving.prefix_cache import BLOCK

V5E_HBM_BYTES = 15.75 * 2 ** 30     # what a v5e chip offers a program
MAX_LEN = 4096                      # danube's attention window
NUM_PAGES = 1 + 16 * (MAX_LEN // BLOCK)   # RealEngine's default arena
BATCH = 4                           # ModelNode's scheduler slots


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits(compiled):
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert need <= V5E_HBM_BYTES, (need / 2 ** 30, ma)


@pytest.mark.parametrize("d_head", [80, 128])
def test_paged_kernel_compiles(one_chip, d_head):
    B, H, Hkv, P, n_pg = BATCH, 32, 8, NUM_PAGES, MAX_LEN // BLOCK
    bf16 = jnp.bfloat16
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((B, H, d_head), bf16),
        jax.ShapeDtypeStruct((P, BLOCK, Hkv, d_head), bf16),
        jax.ShapeDtypeStruct((P, BLOCK, Hkv, d_head), bf16),
        jax.ShapeDtypeStruct((B, n_pg), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32)))
    fn = functools.partial(paged_decode_attention_pallas, window=MAX_LEN)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def danube(one_chip):
    cfg = base.get_config("h2o-danube-1.8b")
    serve = dataclasses.replace(cfg, param_dtype=cfg.dtype)
    model = build_model(serve)
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    arena = _on(one_chip, jax.eval_shape(
        functools.partial(model.paged_arena_zeros, NUM_PAGES, BLOCK)))
    return model, params, arena


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["jnp", "kernel"])
def test_danube_decode_step_fits(one_chip, danube, use_kernels,
                                 monkeypatch):
    model, params, arena = danube
    # the attention picks the kernel only on a TPU backend, and this
    # process's backend is the CPU: steer it for this compile
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = build_model(dataclasses.replace(model.cfg,
                                            use_kernels=use_kernels))
    i32 = jnp.int32
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((BATCH, MAX_LEN // BLOCK), i32),
        jax.ShapeDtypeStruct((BATCH, 1), i32),
        jax.ShapeDtypeStruct((BATCH,), i32),
        jax.ShapeDtypeStruct((BATCH,), jnp.bool_)))

    def step(params, arena, pt, tok, pos, active):
        return model.decode_paged(params, arena, pt, tok, pos, active=active)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, arena, *args).compile()
    _fits(compiled)
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernels


def test_danube_prefill_step_fits(one_chip, danube):
    model, params, arena = danube
    i32 = jnp.int32
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((BATCH, MAX_LEN // BLOCK), i32),
        jax.ShapeDtypeStruct((BATCH, BLOCK), i32),
        jax.ShapeDtypeStruct((BATCH,), i32),
        jax.ShapeDtypeStruct((BATCH,), jnp.bool_)))

    def step(params, arena, pt, tok, pos0, active):
        return model.prefill_paged(params, arena, pt, tok, pos0,
                                   active=active)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, arena, *args).compile()
    _fits(compiled)
