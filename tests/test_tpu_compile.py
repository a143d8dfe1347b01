"""Compile the main-path kernels for a TPU v5e core, with no chip attached.

Interpret-mode tests cannot see what the TPU compiler refuses: blocks
that are not tiled to (8, 128), primitives Mosaic does not lower, more
VMEM than a core's scoped limit. Each test here compiles one kernel at
a bucket shape the pipeline launches and checks that the compiled
program holds the Mosaic kernel. ``wildcard_match`` launches one token
bucket's distinct lines of a chunk against every template: up to 8,192
lines (a chunk) and 1,024 templates, with templates padded to the token
bucket.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.colcodec import colcodec_transform
from repro.kernels.scan import distinct_counts
from repro.kernels.tokenize import tokenize_hash
from repro.kernels.wildcard_match import wildcard_match


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip can be written to the
        # persistent cache but not read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, one_chip, *shapes, dtypes=()) -> str:
    dtypes = dtypes or (jnp.int32,) * len(shapes)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in zip(shapes, dtypes)]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n,t,k,tt", [
    (256, 32, 16, 16),
    (256, 32, 64, 16),
    (4096, 32, 256, 16),
    (8192, 128, 256, 128),  # widest one-line token buckets
    (1024, 256, 64, 256),   # multi-line records (stack traces)
    (1024, 512, 64, 512),   # the token budget: the largest VMEM tile (BK=8)
    (1024, 32, 128, 32),    # the floor of a dense launch
    (8192, 64, 1024, 64),   # a chunk's distinct lines against a large store
    (8192, 128, 1024, 128),
])
def test_wildcard_match_compiles(one_chip, n, t, k, tt):
    txt = _compiled_text(
        lambda a, b, c, d: wildcard_match(a, b, c, d, interpret=False),
        one_chip, (n, t), (n,), (k, tt), (k,))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("r,width", [(8, 128), (8, 256), (8, 1 << 20)])
def test_colcodec_transform_compiles(one_chip, r, width):
    txt = _compiled_text(
        lambda v, ln, m, rf: colcodec_transform(v, ln, m, rf, interpret=False),
        one_chip, (r, width), (r,), (r,), (r,))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n,bins", [(256, 128), (1 << 16, 1 << 16)])
def test_distinct_counts_compiles(one_chip, n, bins):
    txt = _compiled_text(
        lambda i, w: distinct_counts(i, w, n_bins=bins, interpret=False),
        one_chip, (n,), (n,))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n,width", [(256, 64), (256, 1024)])
def test_tokenize_hash_compiles(one_chip, n, width):
    txt = _compiled_text(
        lambda b, ln, p1, p2: tokenize_hash(b, ln, p1, p2, delims=(32, 9, 44),
                                            interpret=False),
        one_chip, (n, width), (n,), (width,), (width,),
        dtypes=(jnp.uint8, jnp.int32, jnp.uint32, jnp.uint32))
    assert "tpu_custom_call" in txt
