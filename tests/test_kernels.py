"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py),
with hypothesis property tests where invariants exist."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("shape", [(8,), (1000, 7), (3, 5, 64), (4096,)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_fused_add(rng, shape, dtype):
    x = jnp.asarray(rng.normal(size=shape), dtype)
    y = jnp.asarray(rng.normal(size=shape), dtype)
    out = ops.fused_add(x, y, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref.fused_combine(x, y), np.float32), atol=1e-2)


@pytest.mark.parametrize("op", ["add", "max", "min", "mul"])
def test_fused_combine_ops(rng, op):
    x = jnp.asarray(rng.normal(size=(257, 3)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(257, 3)), jnp.float32)
    out = ops.fused_combine(x, y, op=op, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.fused_combine(x, y, op)),
                               atol=1e-5)


@pytest.mark.parametrize("n", [256, 1000, 100_000])
def test_quantize_roundtrip(rng, n):
    flat = jnp.asarray(rng.normal(size=(n,)) * 13, jnp.float32)
    q, s = ops.quantize_int8(flat, interpret=True)
    assert q.dtype == jnp.int8
    back = np.asarray(ops.dequantize_int8(q, s, interpret=True))[:n]
    rel = np.abs(back - np.asarray(flat)).max() / (
        np.abs(np.asarray(flat)).max() + 1e-9)
    assert rel < 0.01


@given(scale=st.floats(1e-3, 1e3), seed=st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_quantize_scale_invariance(scale, seed):
    """Quantization is (nearly) scale-equivariant: codes may shift by at
    most one step (fp32 division rounding moves .5 boundaries), scales
    scale exactly."""
    r = np.random.default_rng(seed)
    flat = jnp.asarray(r.normal(size=(512,)), jnp.float32)
    q1, s1 = ops.quantize_int8(flat, interpret=True)
    q2, s2 = ops.quantize_int8(flat * scale, interpret=True)
    diff = np.abs(np.asarray(q1, np.int32)[:512]
                  - np.asarray(q2, np.int32)[:512])
    assert diff.max() <= 1, diff.max()
    real_blocks = 512 // 256  # beyond these, scales are the clamp floor
    np.testing.assert_allclose(np.asarray(s2)[:real_blocks],
                               np.asarray(s1)[:real_blocks] * scale,
                               rtol=1e-4)


@pytest.mark.parametrize("m,k,n", [(300, 200, 100), (512, 512, 512),
                                   (64, 384, 128), (1, 128, 1),
                                   (257, 129, 65)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_matmul(rng, m, k, n, dtype):
    a = jnp.asarray(rng.normal(size=(m, k)), dtype)
    b = jnp.asarray(rng.normal(size=(k, n)), dtype)
    out = ops.matmul(a, b, interpret=True)
    expect = ref.matmul(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=2e-2 if dtype != np.float32 else 1e-3,
                               rtol=2e-2)


@pytest.mark.parametrize("v,d,b", [(100, 32, 16), (1000, 96, 64),
                                   (37, 128, 5)])
def test_embedding_gather(rng, v, d, b):
    table = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, v, size=(b,)), jnp.int32)
    out = ops.embedding_gather(table, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.gather_rows(table, idx)))


def test_vmem_block_alignment():
    """Kernel block shapes stay MXU/VPU aligned and within VMEM budget."""
    from repro.core.hw_spec import TPU_V5E
    from repro.kernels import fused_reduce as fr
    from repro.kernels import matmul as mm
    assert fr.LANES % 128 == 0
    # matmul working set: x-tile + y-tile + fp32 acc must fit VMEM
    ws = (mm.DEFAULT_BM * mm.DEFAULT_BK * 2 + mm.DEFAULT_BK * mm.DEFAULT_BN
          * 2 + mm.DEFAULT_BM * mm.DEFAULT_BN * 4)
    assert ws < TPU_V5E.vmem_bytes
    for d in (mm.DEFAULT_BM, mm.DEFAULT_BN, mm.DEFAULT_BK):
        assert d % 128 == 0


@pytest.mark.parametrize("t,v,d,b", [(3, 256, 32, 300), (2, 1000, 32, 128),
                                     (4, 384, 16, 7)])
def test_embedding_gather_stack(rng, t, v, d, b):
    """(T, V, D) table stacks, one id list per table — the DLRM lookup."""
    tables = jnp.asarray(rng.normal(size=(t, v, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, v, size=(t, b)), jnp.int32)
    out = ops.embedding_gather(tables, idx, interpret=True)
    want = np.stack([np.asarray(ref.gather_rows(tables[i], idx[i]))
                     for i in range(t)])
    np.testing.assert_array_equal(np.asarray(out), want)
