"""Compile the main-path Pallas kernels for a TPU v5e at real sizes.

No chip is needed: the TPU compiler compiles for a described v5e. This is
what interpret mode cannot show — block shapes the tiling refuses, more
VMEM than a kernel may use, primitives Mosaic cannot lower. Each test
asserts that the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


# the paper's light-source frame: 360 angles x 1448 detector columns,
# reconstructed at n = 1448 (miniapps/mass.py LightsourceTemplateSource)
N_ANGLES, N_DET, N = 360, 1448, 1448


def test_tomo_backproject_compiles_at_paper_size(one_chip, no_persistent_cache):
    from repro.kernels.tomo.kernel import backproject_pallas

    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda s, c, si: backproject_pallas(s, c, si, n=N),
        f32((N_ANGLES, N_DET)), f32((N_ANGLES,)), f32((N_ANGLES,)))
    assert "tpu_custom_call" in text


def test_tomo_backproject_stack_compiles_at_paper_size(one_chip, no_persistent_cache):
    """The reconstruction stage's stack program vmaps the kernel over frames."""
    from repro.kernels.tomo.kernel import backproject_pallas

    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    stack = lambda s, c, si: jax.vmap(lambda x: backproject_pallas(x, c, si, n=N))(s)
    text = _compiled_text(
        stack, f32((2, N_ANGLES, N_DET)), f32((N_ANGLES,)), f32((N_ANGLES,)))
    assert "tpu_custom_call" in text


def test_tomo_project_compiles_at_paper_size(one_chip, no_persistent_cache):
    from repro.kernels.tomo.kernel import project_pallas

    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda x, c, si: project_pallas(x, c, si, n_det=N_DET),
        f32((N, N)), f32((N_ANGLES,)), f32((N_ANGLES,)))
    assert "tpu_custom_call" in text


def test_kmeans_assign_compiles_at_the_cluster_message_bucket(one_chip, no_persistent_cache):
    from repro.kernels.kmeans import assign
    from repro.streaming import ShapeBuckets

    # a 5000 x 3 `cluster` message pads to StreamingKMeans' row bucket
    rows = ShapeBuckets(min_size=512, max_size=65536).fit(5000)
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda p, c: assign(p, c, use_kernel=True, interpret=False),
        f32((rows, 3)), f32((10, 3)))
    assert "tpu_custom_call" in text


def test_paged_decode_compiles_at_smollm_heads(one_chip, no_persistent_cache):
    from repro.configs.registry import get_arch
    from repro.kernels.attention.decode_kernel import decode_attention_pallas

    cfg = get_arch("smollm-135m")
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    assert (H, KV, hd) == (9, 3, 64)
    page, B, S = 16, 8, 256  # the serving page size; 16 pages of context
    dt = jnp.dtype(cfg.compute_dtype)  # the published config serves in bf16
    sds = lambda shape, dt=dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v, pos: decode_attention_pallas(q, k, v, pos, block_kv=page),
        sds((B, 1, H, hd)), sds((B, S, KV, hd)), sds((B, S, KV, hd)), sds((B,), jnp.int32))
    assert "tpu_custom_call" in text
