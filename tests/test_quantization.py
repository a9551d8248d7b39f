"""Kernel tier of the quantized comm fabric: blockwise int8 round-trip
error bounds, Pallas/XLA parity, stochastic rounding and pytree
behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.parallel import quantization as qz


def _rand(shape, seed=0, scale=3.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32) * scale


# ---------------------------------------------------------------------------
# round-trip error contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(8, 1024), (5, 1000), (64, 333), (7,), (1, 4096)])
def test_roundtrip_within_halfstep_bound(shape):
    x = _rand(shape)
    q = qz.quantize_blockwise(x, block=256)
    assert q.values.shape == x.shape and q.values.dtype == jnp.int8
    err = np.abs(np.asarray(q.dequantize() - x))
    bound = np.asarray(qz.quantization_error_bound(x, block=256))
    assert (err <= bound * 1.0001 + 1e-7).all(), (err.max(), bound.max())


def test_scales_shape_and_zero_blocks():
    x = jnp.zeros((4, 512))
    q = qz.quantize_blockwise(x, block=128)
    assert q.scales.shape == (4, 4)
    # all-zero blocks get scale 1 so dequantization is exact zero
    np.testing.assert_array_equal(np.asarray(q.scales), 1.0)
    np.testing.assert_array_equal(np.asarray(q.dequantize()), 0.0)


def test_partial_trailing_block():
    x = _rand((3, 300), seed=1)
    q = qz.quantize_blockwise(x, block=256)
    assert q.scales.shape == (3, 2)  # 256 + short 44-wide block
    err = np.abs(np.asarray(q.dequantize() - x))
    bound = np.asarray(qz.quantization_error_bound(x, block=256))
    assert (err <= bound * 1.0001 + 1e-7).all()


def test_empty_and_preserves_dtype():
    e = qz.quantize_blockwise(jnp.zeros((3, 0)))
    assert e.values.shape == (3, 0) and e.dequantize().shape == (3, 0)
    xb = _rand((4, 512)).astype(jnp.bfloat16)
    q = qz.quantize_blockwise(xb)
    assert q.dequantize().dtype == jnp.bfloat16


def test_nonfinite_rows_cannot_poison_blocks():
    """An adversarial inf/NaN coordinate must not NaN its block: scale
    comes from the finite values, inf clips to +/-127*scale, NaN encodes
    as 0 — the robust fabrics feed attacker-controlled rows through the
    codec and the decoded matrix must stay finite."""
    x = _rand((4, 512), seed=9)
    x = x.at[1, 3].set(jnp.inf).at[2, 300].set(-jnp.inf).at[3, 7].set(jnp.nan)
    for use_pallas in (False, True):
        q = qz.quantize_blockwise(
            x, block=256, use_pallas=use_pallas, interpret=True
        )
        deq = np.asarray(q.dequantize())
        assert np.isfinite(deq).all(), "non-finite leaked through the codec"
        assert np.isfinite(np.asarray(q.scales)).all()
        # the finite neighbors of the poisoned coordinates stay accurate
        finite_mask = np.isfinite(np.asarray(x))
        err = np.abs(deq - np.asarray(x))[finite_mask]
        ref_bound = np.abs(np.asarray(x))[finite_mask].max() / 127 + 1e-6
        assert err.max() <= ref_bound
        # inf hits the codomain edge, NaN encodes as zero
        assert np.asarray(q.values)[1, 3] == 127
        assert np.asarray(q.values)[2, 300] == -127
        assert np.asarray(q.values)[3, 7] == 0


# ---------------------------------------------------------------------------
# Pallas kernel parity (interpret mode on the CPU suite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,block,tile", [
    ((8, 1024), 256, 512),
    ((3, 700), 256, 256),
    ((16, 2048), 128, 1024),
])
def test_pallas_matches_xla(shape, block, tile):
    x = _rand(shape, seed=2)
    ref = qz.quantize_blockwise(x, block=block, use_pallas=False)
    got = qz.quantize_blockwise(
        x, block=block, tile=tile, use_pallas=True, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(ref.values), np.asarray(got.values))
    np.testing.assert_allclose(
        np.asarray(ref.scales), np.asarray(got.scales), rtol=1e-7
    )
    deq_ref = qz.dequantize_blockwise(ref, use_pallas=False)
    deq_got = qz.dequantize_blockwise(
        got, tile=tile, use_pallas=True, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(deq_ref), np.asarray(deq_got), rtol=1e-6, atol=1e-7
    )


# ---------------------------------------------------------------------------
# stochastic rounding
# ---------------------------------------------------------------------------


def test_stochastic_requires_key():
    with pytest.raises(ValueError, match="key"):
        qz.quantize_blockwise(_rand((2, 256)), stochastic=True)


def test_stochastic_rounding_unbiased():
    # a value landing strictly between two int8 steps must average out
    x = jnp.full((1, 256), 0.30117, jnp.float32)  # absmax fixes the scale
    x = x.at[0, 0].set(1.0)
    key = jax.random.PRNGKey(3)
    deqs = [
        np.asarray(
            qz.quantize_blockwise(
                x, stochastic=True, key=jax.random.fold_in(key, i)
            ).dequantize()
        )[0, 1]
        for i in range(300)
    ]
    step = 1.0 / 127.0
    assert np.asarray(deqs).std() > 0  # it actually dithers
    assert abs(np.mean(deqs) - 0.30117) < step / 8


# ---------------------------------------------------------------------------
# pytree + dispatch
# ---------------------------------------------------------------------------


def test_quantized_blocks_is_pytree():
    q = qz.quantize_blockwise(_rand((4, 512)))
    leaves = jax.tree_util.tree_leaves(q)
    assert len(leaves) == 2
    # jit boundaries keep static metadata intact
    out = jax.jit(lambda t: t.dequantize())(q)
    assert out.shape == (4, 512)


def test_comm_precision_coercion_and_validation():
    assert qz.as_comm_precision(None).mode == "off"
    assert qz.as_comm_precision("int8").mode == "int8"
    p = qz.CommPrecision(mode="int8", block=128)
    assert qz.as_comm_precision(p) is p
    with pytest.raises(ValueError):
        qz.CommPrecision(mode="fp4")
    with pytest.raises(TypeError):
        qz.as_comm_precision(3)
    assert qz.CommPrecision(mode="int8", block=256).wire_bytes_per_value() == \
        pytest.approx(1.0 + 4.0 / 256)
    assert qz.CommPrecision().wire_bytes_per_value() == 4.0
