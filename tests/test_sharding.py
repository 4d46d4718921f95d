"""Sharding-rule unit tests (AbstractMesh — no devices needed)."""

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs
from repro.distributed import sharding as SH
from repro.nn import model as MD

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_spec_divisibility_drops_axis():
    rules = SH.rules_for("train")
    # 130 not divisible by 16 -> replicated
    assert SH.spec_for((130,), ("embed",), rules, MESH1) == P()
    assert SH.spec_for((128,), ("embed",), rules, MESH1) == P("data")


def test_spec_multi_axis_batch():
    rules = SH.rules_for("train")
    s = SH.spec_for((256, 4096), ("batch", None), rules, MESH2)
    assert s == P(("pod", "data"))
    # batch=1 (long_500k): replicate
    s = SH.spec_for((1, 1), ("batch", None), rules, MESH2)
    assert s == P()


def test_no_axis_reuse_within_tensor():
    rules = {"a": ("model",), "b": ("model",)}
    s = SH.spec_for((32, 32), ("a", "b"), rules, MESH1)
    # second dim can't reuse "model"
    assert s == P("model")


def test_param_shardings_cover_all_archs():
    for arch in configs.ARCH_IDS:
        cfg = configs.get(arch)
        specs = MD.param_specs(cfg)
        for mesh in (MESH1, MESH2):
            for mode in ("train", "serve"):
                sh = SH.shardings_for_specs(specs, SH.rules_for(mode), mesh)
                for path, s in sh.items():
                    spec = s.spec
                    shape = specs[path].shape
                    # every sharded dim divides
                    for dim, entry in zip(shape, tuple(spec)):
                        if entry is None:
                            continue
                        axes = entry if isinstance(entry, tuple) else (entry,)
                        size = int(np.prod([mesh.shape[a] for a in axes]))
                        assert dim % size == 0, (arch, path, spec)


def test_train_embed_fully_sharded():
    cfg = configs.get("llama3-8b")
    specs = MD.param_specs(cfg)
    sh = SH.shardings_for_specs(specs, SH.rules_for("train"), MESH1)
    assert sh["embed/tok"].spec == P("model", "data")


def test_serve_params_not_zero3():
    """Serve mode avoids per-layer gathers: embed dim replicated.
    (wq is stacked [L, d, H*hd] — layers axis replicated too.)"""
    cfg = configs.get("llama3-8b")
    specs = MD.param_specs(cfg)
    sh = SH.shardings_for_specs(specs, SH.rules_for("serve"), MESH1)
    assert sh["blocks/attn/wq"].spec == P(None, None, "model")
    tr = SH.shardings_for_specs(specs, SH.rules_for("train"), MESH1)
    assert tr["blocks/attn/wq"].spec == P(None, "data", "model")


def test_cache_shardings_mla_latent():
    """Stacked MLA latent caches must shard batch + latent (the 253GB
    replication bug this rule system exists to prevent)."""
    cfg = configs.get("deepseek-v2-236b")
    caches = jax.eval_shape(lambda: MD.init_cache(cfg, 128, 32768))
    sh = SH.cache_shardings(cfg, caches, MESH1)
    spec = sh["blocks"].c_kv.spec
    assert "data" in str(spec) and "model" in str(spec)


def test_cache_shardings_all_archs_valid():
    for arch in configs.ARCH_IDS:
        cfg = configs.get(arch)
        B = 8 if arch != "deepseek-v2-236b" else 128
        caches = jax.eval_shape(lambda: MD.init_cache(cfg, 128, 4096))
        for mesh in (MESH1, MESH2):
            sh = SH.cache_shardings(cfg, caches, mesh)
            flat_c = jax.tree_util.tree_leaves(caches)
            flat_s = jax.tree_util.tree_leaves(
                sh, is_leaf=lambda x: hasattr(x, "spec"))
            for c, s in zip(flat_c, flat_s):
                for dim, entry in zip(c.shape, tuple(s.spec)):
                    if entry is None:
                        continue
                    axes = entry if isinstance(entry, tuple) else (entry,)
                    size = int(np.prod([mesh.shape[a] for a in axes]))
                    assert dim % size == 0, (arch, c.shape, s.spec)
