"""Sharding rules must cover every parameter/cache leaf of every assigned
architecture with rank-correct, divisibility-safe PartitionSpecs."""
import jax
import pytest
from jax.sharding import AbstractMesh

from repro.configs import base
from repro.distributed import sharding
from repro.models.lm import build_model

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


@pytest.mark.parametrize("arch", base.ASSIGNED)
@pytest.mark.parametrize("mesh", [MESH, MESH_MP], ids=["1pod", "2pod"])
@pytest.mark.parametrize("train", [True, False], ids=["train", "serve"])
def test_param_rules_cover_all_leaves(arch, mesh, train):
    cfg = base.get_config(arch)
    model = build_model(cfg)
    specs = model.param_specs()

    def check(path, leaf):
        spec = sharding.param_pspec(cfg, path, leaf.shape, mesh, train)
        assert len(spec) <= len(leaf.shape)
        # divisibility: every sharded dim divides
        for dim, axes in zip(leaf.shape, tuple(spec) + (None,) * 10):
            if axes is None:
                continue
            for ax in ([axes] if isinstance(axes, str) else axes):
                assert dim % mesh.shape[ax] == 0, (path, leaf.shape, spec)
        return spec

    jax.tree_util.tree_map_with_path(check, specs)


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "jamba-v0.1-52b",
                                  "xlstm-1.3b", "gemma2-9b", "whisper-base"])
def test_cache_rules_cover_all_leaves(arch):
    cfg = base.get_config(arch)
    model = build_model(cfg)
    shape = base.SHAPES["decode_32k"]
    T_mem = shape.seq_len // 2 if cfg.is_encdec else cfg.n_image_tokens
    specs = model.cache_specs(shape.global_batch, shape.seq_len, T_mem)

    def check(path, leaf):
        for long_ctx in (False, True):
            spec = sharding.cache_pspec(cfg, path, leaf.shape, MESH, long_ctx)
            assert len(spec) <= len(leaf.shape), (path, leaf.shape, spec)
            for dim, axes in zip(leaf.shape, tuple(spec) + (None,) * 10):
                if axes is None:
                    continue
                for ax in ([axes] if isinstance(axes, str) else axes):
                    assert dim % MESH.shape[ax] == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(check, specs)


def test_kv_axis_divisibility_policy():
    gemma = base.get_config("gemma2-9b")      # kv=8 < 16 -> None
    assert sharding.kv_axis(gemma, MESH) is None
    moonshot = base.get_config("moonshot-v1-16b-a3b")  # kv=16 -> model
    assert sharding.kv_axis(moonshot, MESH) == "model"


def test_batch_axes():
    assert sharding.batch_axes(MESH, 256) == ("data",)
    assert sharding.batch_axes(MESH_MP, 256) == ("pod", "data")
    assert sharding.batch_axes(MESH, 1) == ()
