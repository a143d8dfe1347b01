"""Sharding rules validated structurally on AbstractMesh — covers every
param leaf of every assigned arch on the production mesh shapes without
needing 256 real devices (the AOT proof lives in artifacts/dryrun)."""

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.distributed.sharding import batch_pspecs, cache_pspecs, param_pspecs
from repro.models import init_cache, init_params, tp_pad


MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axis_prod(mesh, entry):
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    return int(np.prod([dict(zip(mesh.axis_names, mesh.axis_sizes))[a] for a in axes]))


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD], ids=["1pod", "2pod"])
def test_param_specs_cover_and_divide(arch, mesh):
    cfg = tp_pad(get_config(arch).reduced(), 4)  # reduced tree, same structure
    _ = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    # full-size config for the divisibility check on real dims
    cfg_full = tp_pad(get_config(arch), 16)
    params_full = jax.eval_shape(lambda k: init_params(cfg_full, k), jax.random.PRNGKey(0))
    specs = param_pspecs(params_full, cfg_full, mesh)  # raises if uncovered
    big_sharded = 0
    for leaf, spec in zip(jax.tree.leaves(params_full), jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        for dim, entry in zip(leaf.shape, spec):
            size = _axis_prod(mesh, entry)
            assert dim % size == 0, (arch, leaf.shape, spec)
        if np.prod(leaf.shape) > 1e6:
            # every big tensor must be sharded on at least one axis
            assert any(e is not None for e in spec), (arch, leaf.shape, spec)
            big_sharded += 1
    assert big_sharded > 0


@pytest.mark.parametrize("arch", ["qwen2-7b", "jamba-v0.1-52b", "rwkv6-7b", "whisper-base"])
def test_cache_specs_shard_sequence(arch):
    cfg = tp_pad(get_config(arch), 16)
    cache = jax.eval_shape(lambda: init_cache(cfg, 128, 32768))
    specs = cache_pspecs(cache, cfg, MESH_1POD)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    for path, spec in flat:
        keys = [str(getattr(k, "key", "")) for k in path]
        if keys[-1] in ("k", "v") and "blocks" in keys[0]:
            assert "model" in spec, (keys, spec)  # split-K decode: seq over model


def test_batch_specs_fallback_batch1():
    batch = {"tokens": jax.ShapeDtypeStruct((1, 1), np.int32)}
    specs = batch_pspecs(batch, MESH_1POD)
    assert specs["tokens"] == P(None, None)  # long_500k: replicate batch


def test_tp_pad():
    cfg = get_config("qwen2-7b")
    padded = tp_pad(cfg, 16)
    assert padded.n_heads == 32 and padded.n_kv_heads == 4
    cfg2 = get_config("qwen1.5-4b")
    padded2 = tp_pad(cfg2, 16)
    assert padded2.n_heads == 32 and padded2.n_kv_heads == 32  # MHA stays MHA
    assert tp_pad(get_config("qwen3-1.7b"), 16).n_heads == 16  # already divides
