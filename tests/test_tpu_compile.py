"""Compile the Pallas GEMM for a described TPU v5e at starcoder2-3b widths.

Nothing runs: the TPU compiler (installed with jax) compiles for a chip
that is described, not attached, and refuses what the chip would refuse —
more VMEM than a kernel may use, a block not aligned to the tiling. The
topology is described inside a fixture (never at import), so every test
worker collects the same tests and only the worker running this file
loads the TPU library.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.tiled_matmul import BlockConfig, DEFAULT_CONFIG, tiled_matmul

# (m, n, k, config, transpose_a, transpose_b, layers): starcoder2-3b
# d_model 3072, d_ff 12288, qkv 3584, vocab 49152; 8 decode rows, 512-row
# chunk calls (8 lanes x 64 tokens). `layers` set: b is a stack of that
# many weights, read in place at a traced layer index.
CASES = {
    "decode_qproj": (8, 3072, 3072, BlockConfig(8, 512, 1024), False,
                     False, None),
    "chunk_qproj_default": (512, 3072, 3072, DEFAULT_CONFIG, False, False,
                            None),
    "chunk_ffn_down": (512, 3072, 12288, BlockConfig(512, 1024, 1024),
                       False, False, None),
    # K 3072 past the last 2048 block: masked, not padded
    "decode_lm_head": (8, 49152, 3072, BlockConfig(8, 1024, 2048), False,
                       False, None),
    # the tuner grid's largest block: needs a raised VMEM limit
    "largest_candidate": (2048, 12288, 3072, BlockConfig(1024, 1024, 2048),
                          False, False, None),
    # a transposed A block is (bk, bm): bm must be lane-aligned
    "transposed_a_small_bm": (512, 3072, 3072, BlockConfig(8, 128, 128),
                              True, False, None),
    # the chunk program's up projection: a layer of the stack, ragged K
    "chunk_up_stacked_ragged_k": (256, 12288, 3072,
                                  BlockConfig(256, 1024, 2048), False,
                                  False, 4),
    # qkv: ragged N and K
    "chunk_qkv_stacked_ragged_n": (256, 3584, 3072,
                                   BlockConfig(256, 1024, 2048), False,
                                   False, 4),
    "decode_stacked_transposed_b": (8, 3072, 3072,
                                    BlockConfig(8, 1024, 2048), False, True,
                                    2),
    # K and N not lane-aligned: the last K block is masked by iota
    "unaligned_ragged_mask": (200, 300, 200, BlockConfig(64, 128, 128),
                              False, False, None),
    # fewer rows than a bf16 tile, masked: A's rows go up to 8
    "three_rows_unaligned_stacked": (3, 200, 130, BlockConfig(8, 128, 128),
                                     False, False, 2),
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
    m, n, k, cfg, ta, tb, layers = CASES[case]
    a = jax.ShapeDtypeStruct((k, m) if ta else (m, k), jnp.bfloat16,
                             sharding=one_chip)
    b_mat = (n, k) if tb else (k, n)
    b = jax.ShapeDtypeStruct(b_mat if layers is None else (layers, *b_mat),
                             jnp.bfloat16, sharding=one_chip)
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b, i: tiled_matmul(
            a, b, layer=None if layers is None else i, config=cfg,
            transpose_a=ta, transpose_b=tb)
    ).lower(a, b, i).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _staged_operands(text)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= m * n * 2


HLO_LINE = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+?)\[([0-9,]*)\]\S* "
                      r"([a-z-]+)\((.*?)\)")
OPERAND = re.compile(r"%(\S+?)(?:[,)]|$)")


def _staged_operands(hlo: str) -> list[str]:
    """Weight operands of the Pallas calls that a pad or a dynamic slice
    made: the kernel's second >=2-D bf16 operand (B, after A), when the
    compiler copied it before the kernel where the kernel should read it
    where it lives."""
    made, calls = {}, []
    for line in hlo.splitlines():
        got = HLO_LINE.match(line)
        if got is None:
            continue
        name, dtype, dims, opcode, args = got.groups()
        made[name] = (dtype, dims.count(",") + 1 if dims else 0, opcode,
                      args)
        if opcode == "custom-call" and "tpu_custom_call" in line:
            calls.append(args)

    def source(arg):
        while made.get(arg, ("",) * 4)[2] == "bitcast":
            arg = OPERAND.findall(made[arg][3] + ")")[0]
        return arg

    staged = []
    for args in calls:
        mats = [a for a in map(source, OPERAND.findall(args + ")"))
                if a in made and made[a][0] == "bf16" and made[a][1] >= 2]
        weight = mats[1]
        opcode = made[weight][2]
        if (opcode in ("pad", "dynamic-slice")
                or weight.startswith(("pad", "dynamic-slice"))):
            staged.append(f"{weight} = {opcode}")
    return staged


def test_dense_serving_programs_read_weights_in_place(one_chip, monkeypatch):
    """Two starcoder2-3b layers at full width, compiled for a v5e in the
    Pallas mode: the decode step and a 256-row prefill chunk hand every
    Pallas call its weight as it lives, with no dynamic slice out of the
    stacked layers and no padded copy ahead of it (K 3072 at a 2048
    block, the tuned block of these programs, leaves a ragged edge)."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import transformer as tfm

    cfg = dataclasses.replace(get_config("starcoder2-3b"), n_layers=2)
    monkeypatch.setattr(
        ops, "_tuned_config",
        lambda m, n, k, *_: BlockConfig(min(m, 256), 1024, 2048))
    ops.force_mode("pallas")
    try:
        def spec(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip), tree)

        params = spec(jax.eval_shape(
            lambda: tfm.lm_init(jax.random.PRNGKey(0), cfg)))
        rows, max_len, chunk = 8, 512, 32
        state = spec({"kv": jax.eval_shape(
            lambda: tfm.init_kv_cache(cfg, rows, max_len)),
            "index": jax.ShapeDtypeStruct((rows,), jnp.int32)})
        ids = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
        tokens = jax.ShapeDtypeStruct((rows, chunk), jnp.int32,
                                      sharding=one_chip)
        decode = jax.jit(lambda p, t, s: tfm.lm_decode_step(p, t, s, cfg))
        prefill = jax.jit(lambda p, t, ln, s: tfm.lm_prefill_chunk(
            p, t, ln, s, cfg))
        texts = [decode.lower(params, ids, state).compile().as_text(),
                 prefill.lower(params, tokens, ids, state).compile().as_text()]
    finally:
        ops.force_mode("auto")
    for text in texts:
        assert text.count("tpu_custom_call") >= 5
        assert not _staged_operands(text)
