"""Main-path Pallas kernels compile for the real chip (no chip attached).

The TPU compiler is installed next to the CPU backend and compiles for a
*described* ``v5e:2x2`` topology; what it refuses here the chip refuses
too (block shapes off the (8, 128) tiling, too much VMEM). Interpret-mode
parity tests cannot see either. Nothing runs: a compile that passes says
the lowering and Mosaic accept the kernel, ``chip_smoke.py`` says it is
right. The topology is described inside a fixture (never at import) so
only the process that runs this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bigdl_tpu.ops.flash_attention import _flash_fwd, paged_flash_attention

SLOTS, HEADS, PAGE_SIZE, MAX_LEN = 16, 8, 16, 256   # GenerationEngine defaults


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile writes cache entries nobody can read back
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("pages", ["float32", "bfloat16", "int8"])
def test_paged_decode_kernel_compiles(one_chip, pages, head_dim):
    ppn = MAX_LEN // PAGE_SIZE
    n_pages = SLOTS * ppn + 1
    q_dtype = jnp.float32 if pages == "int8" else jnp.dtype(pages)
    pool = ((n_pages, HEADS, PAGE_SIZE, head_dim), jnp.dtype(pages))
    shapes = [((SLOTS, HEADS, head_dim), q_dtype), pool, pool,
              ((SLOTS, ppn), jnp.int32), ((SLOTS,), jnp.int32)]
    if pages == "int8":
        scale = ((n_pages, PAGE_SIZE), jnp.float32)
        shapes += [scale, scale]

        def fn(q, k, v, pm, pos, ks, vs):
            return paged_flash_attention(q, k, v, pm, pos,
                                         k_scales=ks, v_scales=vs)
    else:
        fn = paged_flash_attention
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def test_dense_flash_forward_compiles(one_chip):
    qkv = ((8, 8, 256, 64), jnp.bfloat16)

    def fn(q, k, v):
        return _flash_fwd(q, k, v, None, 64 ** -0.5, True, 128, 128, False)

    assert "tpu_custom_call" in _compiled_text(fn, one_chip, qkv, qkv, qkv)
