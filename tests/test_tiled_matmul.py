"""Pallas tiled GEMM vs pure-jnp oracle: shape/dtype sweeps + properties.

All kernel executions use interpret=True (CPU container; TPU is the target).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property-based kernel tests need the 'test' extra "
           "(pip install -e '.[test]')")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.ref import matmul_ref
from repro.kernels.tiled_matmul import BlockConfig, tiled_matmul

jax.config.update("jax_enable_x64", False)

SMALL = BlockConfig(block_m=16, block_n=128, block_k=128)


def _rand(shape, dtype, seed):
    x = np.random.default_rng(seed).normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,n,k",
    [
        (16, 128, 128),     # single block
        (32, 256, 256),     # multi-block even
        (40, 200, 300),     # ragged in every dim (padding path)
        (1, 128, 512),      # degenerate row (decode-style GEMV)
        (128, 1, 64),       # degenerate col
        (17, 129, 257),     # all-prime-ish
    ],
)
def test_matches_oracle_shapes(m, n, k, dtype):
    a, b = _rand((m, k), dtype, 0), _rand((k, n), dtype, 1)
    got = tiled_matmul(a, b, config=SMALL, interpret=True)
    want = matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("ta,tb", [(False, False), (False, True),
                                   (True, False), (True, True)])
def test_layouts(ta, tb):
    m, n, k = 48, 160, 96
    a = _rand((k, m) if ta else (m, k), jnp.float32, 2)
    b = _rand((n, k) if tb else (k, n), jnp.float32, 3)
    got = tiled_matmul(a, b, config=SMALL, transpose_a=ta, transpose_b=tb,
                       interpret=True)
    want = matmul_ref(a, b, transpose_a=ta, transpose_b=tb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("alpha,beta", [(1.0, 0.0), (2.0, 0.0), (0.5, 0.5),
                                        (1.0, 1.0)])
def test_alpha_beta(alpha, beta):
    m, n, k = 32, 128, 64
    a, b = _rand((m, k), jnp.float32, 4), _rand((k, n), jnp.float32, 5)
    c = _rand((m, n), jnp.float32, 6)
    got = tiled_matmul(a, b, c, config=SMALL, alpha=alpha, beta=beta,
                       interpret=True)
    want = matmul_ref(a, b, c, alpha=alpha, beta=beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bf16_in_f32_out():
    a, b = _rand((32, 64), jnp.bfloat16, 7), _rand((64, 128), jnp.bfloat16, 8)
    got = tiled_matmul(a, b, config=SMALL, out_dtype=jnp.float32, interpret=True)
    assert got.dtype == jnp.float32
    want = matmul_ref(a, b, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_fp32_accumulation_not_bf16():
    """K large enough that bf16 accumulation would visibly drift."""
    k = 4096
    a = jnp.full((8, k), 0.01, jnp.bfloat16)
    b = jnp.full((k, 128), 0.01, jnp.bfloat16)
    got = tiled_matmul(a, b, config=BlockConfig(8, 128, 512),
                       out_dtype=jnp.float32, interpret=True)
    # matching bf16 inputs: each product is (0.01 rounded to bf16)^2
    x = np.float32(np.asarray(jnp.bfloat16(0.01), np.float32))
    np.testing.assert_allclose(np.asarray(got), np.full((8, 128), k * x * x),
                               rtol=1e-3)


@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 70),
    n=st.integers(1, 160),
    k=st.integers(1, 200),
    bm=st.sampled_from([8, 16, 32]),
    bn=st.sampled_from([128]),
    bk=st.sampled_from([128]),
)
def test_property_any_shape_any_block(m, n, k, bm, bn, bk):
    a = _rand((m, k), jnp.float32, m * 7 + n)
    b = _rand((k, n), jnp.float32, k * 3 + 1)
    got = tiled_matmul(a, b, config=BlockConfig(bm, bn, bk), interpret=True)
    want = matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_config_invariance():
    """Different valid block configs give identical math."""
    a, b = _rand((64, 256), jnp.float32, 9), _rand((256, 256), jnp.float32, 10)
    outs = [
        np.asarray(tiled_matmul(a, b, config=BlockConfig(bm, bn, bk),
                                interpret=True))
        for bm, bn, bk in [(16, 128, 128), (32, 256, 256), (64, 128, 256)]
    ]
    for o in outs[1:]:
        # different bk => different fp32 summation order: allow ulp drift
        np.testing.assert_allclose(outs[0], o, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tb", [False, True])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_stacked_weight_read_in_place(layer, tb):
    """A layer of a stacked weight, named by a traced index, gives what
    the sliced layer gives, at ragged K and N."""
    m, n, k = 24, 200, 300
    a = _rand((m, k), jnp.float32, 21)
    w = _rand((3, n, k) if tb else (3, k, n), jnp.float32, 22)
    run = jax.jit(lambda a, w, i: tiled_matmul(
        a, w, layer=i, config=SMALL, transpose_b=tb, interpret=True))
    got = run(a, w, jnp.int32(layer))
    sliced = tiled_matmul(a, w[layer], config=SMALL, transpose_b=tb,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(sliced))
    want = matmul_ref(a, w[layer], transpose_b=tb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ragged edges at the served widths: starcoder2-3b d_model 3072 (qkv N
# 3584), OLMoE vocab 50304, each at a block that does not divide it
@pytest.mark.parametrize("m,n,k,cfg", [
    (8, 1024, 3072, BlockConfig(8, 1024, 2048)),     # K past the last block
    (8, 3584, 3072, BlockConfig(8, 1024, 2048)),     # K and N
    (8, 50304, 256, BlockConfig(8, 1024, 256)),      # N past the last block
], ids=["k3072_bk2048", "n3584_bn1024", "n50304_bn1024"])
def test_ragged_edges_at_served_widths(m, n, k, cfg):
    a = _rand((m, k), jnp.bfloat16, 23)
    b = _rand((k, n), jnp.bfloat16, 24)
    got = tiled_matmul(a, b, config=cfg, out_dtype=jnp.float32,
                       interpret=True)
    want = matmul_ref(a, b, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


class TestOpsDispatch:
    def test_matmul_batched_lead_dims(self):
        from repro.kernels import ops

        ops.force_mode("xla")
        try:
            x = _rand((2, 3, 64), jnp.float32, 11)
            w = _rand((64, 32), jnp.float32, 12)
            y = ops.matmul(x, w)
            assert y.shape == (2, 3, 32)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(x) @ np.asarray(w), rtol=1e-5,
                atol=1e-5)
        finally:
            ops.force_mode("auto")

    def test_linear_bias(self):
        from repro.kernels import ops

        x = _rand((4, 16), jnp.float32, 13)
        w = _rand((16, 8), jnp.float32, 14)
        b = _rand((8,), jnp.float32, 15)
        y = ops.linear(x, w, b)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(x) @ np.asarray(w) + np.asarray(b),
            rtol=1e-5, atol=1e-5)

    def test_pallas_interpret_mode_routes_kernel(self):
        from repro.kernels import ops

        ops.force_mode("pallas_interpret")
        try:
            x = _rand((8, 64), jnp.float32, 16)
            w = _rand((64, 128), jnp.float32, 17)
            y = ops.matmul(x, w, config=SMALL)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(x) @ np.asarray(w), rtol=1e-4,
                atol=1e-4)
        finally:
            ops.force_mode("auto")

    @pytest.mark.parametrize("mode", ["xla", "pallas_interpret"])
    def test_weight_ref_reads_its_layer(self, mode):
        from repro.kernels import ops

        ops.force_mode(mode)
        try:
            x = _rand((2, 4, 64), jnp.float32, 25)
            w = _rand((3, 64, 128), jnp.float32, 26)
            counts = {}
            with ops.weight_reads(counts):
                y = jax.jit(lambda x, w, i: ops.matmul(
                    x, ops.WeightRef(w, i), config=SMALL))(x, w, 2)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(x) @ np.asarray(w[2]), rtol=1e-4,
                atol=1e-4)
            reads = 1 if mode != "xla" else 0
            assert counts == {"in_place": reads, "staged": 0}
        finally:
            ops.force_mode("auto")

    def test_gemm_xla_path_matches_ref(self):
        from repro.kernels import ops

        a, b = _rand((16, 32), jnp.float32, 18), _rand((32, 8), jnp.float32, 19)
        c = _rand((16, 8), jnp.float32, 20)
        y = ops.gemm(a, b, c, alpha=0.5, beta=0.5)
        want = matmul_ref(a, b, c, alpha=0.5, beta=0.5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5)
