"""Compile the Pallas GEMM for a described TPU v5e at starcoder2-3b widths.

Nothing runs: the TPU compiler (installed with jax) compiles for a chip
that is described, not attached, and refuses what the chip would refuse —
more VMEM than a kernel may use, a block not aligned to the tiling. The
topology is described inside a fixture (never at import), so every test
worker collects the same tests and only the worker running this file
loads the TPU library.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.tiled_matmul import BlockConfig, DEFAULT_CONFIG, tiled_matmul

# (m, n, k, config, transpose_a): starcoder2-3b d_model 3072, d_ff 12288,
# vocab 49152; 8 decode rows, 512-row chunk calls (8 lanes x 64 tokens)
CASES = {
    "decode_qproj": (8, 3072, 3072, BlockConfig(8, 512, 1024), False),
    "chunk_qproj_default": (512, 3072, 3072, DEFAULT_CONFIG, False),
    "chunk_ffn_down": (512, 3072, 12288, BlockConfig(512, 1024, 1024), False),
    "decode_lm_head": (8, 49152, 3072, BlockConfig(8, 1024, 2048), False),
    # the tuner grid's largest block: needs a raised VMEM limit
    "largest_candidate": (2048, 12288, 3072, BlockConfig(1024, 1024, 2048),
                          False),
    # a transposed A block is (bk, bm): bm must be lane-aligned
    "transposed_a_small_bm": (512, 3072, 3072, BlockConfig(8, 128, 128),
                              True),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("case", sorted(CASES))
def test_tiled_matmul_compiles_for_v5e(one_chip, case):
    m, n, k, cfg, ta = CASES[case]
    a = jax.ShapeDtypeStruct((k, m) if ta else (m, k), jnp.bfloat16,
                             sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b: tiled_matmul(a, b, config=cfg, transpose_a=ta)
    ).lower(a, b).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= m * n * 2
