"""Core BFP numerics: unit + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional test dep (pyproject `test` extra); unit tests run without
    from hypothesis import given, settings, strategies as st
except ImportError:
    given = None

from repro.core import bfp


def test_quantize_dequantize_roundtrip_matches_fake_quant():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    fq = bfp.bfp_fake_quant(x, 32, 8)
    m, e = bfp.bfp_quantize(x, 32, 8)
    deq = bfp.bfp_dequantize(m, e, 128, 32, 8, axis=-1, ndim=2)
    assert jnp.allclose(deq, fq)


def test_error_bound():
    """|x - q(x)| <= 2^(E - m + 2) per group (truncation step size)."""
    rng = np.random.default_rng(1)
    for m_bits in (4, 6, 8):
        x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32)) * 10
        mant, exp = bfp.bfp_quantize(x, 32, m_bits)
        deq = bfp.bfp_dequantize(mant, exp, 64, 32, m_bits, axis=-1, ndim=2)
        step = np.exp2(np.asarray(exp, np.float32) - (m_bits - 2))
        err = np.abs(np.asarray(x - deq)).reshape(4, 2, 32)
        assert np.all(err <= step[..., None] + 1e-7)


def test_zero_group():
    x = jnp.zeros((2, 32))
    fq = bfp.bfp_fake_quant(x, 32, 8)
    assert jnp.all(fq == 0)


def test_monotone_in_mantissa_bits():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(16, 96)).astype(np.float32))
    errs = []
    for m in (2, 4, 8):
        errs.append(float(jnp.abs(
            x - bfp.bfp_fake_quant(x, 32, m)).mean()))
    assert errs[0] > errs[1] > errs[2]


def test_power_of_two_scale_covariance():
    """BFP with pow-2 scaling: q(2^k x) == 2^k q(x) (shared exp shifts)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    a = bfp.bfp_fake_quant(x * 4.0, 32, 8)
    b = bfp.bfp_fake_quant(x, 32, 8) * 4.0
    assert jnp.allclose(a, b)


def test_int4_pack_roundtrip():
    rng = np.random.default_rng(4)
    m = jnp.asarray(rng.integers(-8, 8, size=(6, 64)), jnp.int8)
    for axis in (0, 1, -1):
        rt = bfp.unpack_int4(bfp.pack_int4(m, axis), axis)
        assert jnp.all(rt == m)


def test_grouping_axis():
    """Quantizing along different axes quantizes different groups."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(32, 32)).astype(np.float32))
    x = x.at[0, 0].set(1000.0)  # outlier
    row = bfp.bfp_fake_quant(x, 32, 4, axis=-1)
    col = bfp.bfp_fake_quant(x, 32, 4, axis=0)
    # the outlier flattens its row group in one case, column in the other
    assert float(jnp.abs(x[0, 1:] - row[0, 1:]).mean()) > \
        float(jnp.abs(x[0, 1:] - col[0, 1:]).mean())


def test_padding_of_ragged_axis():
    x = jnp.ones((2, 40))  # 40 % 32 != 0
    fq = bfp.bfp_fake_quant(x, 32, 8)
    assert fq.shape == (2, 40)
    assert jnp.allclose(fq, x, atol=1e-2)


# ---------------------------------------------------------------------------
# Quantize/dequantize invariants.  Each property lives in a plain checker
# exercised by an always-run seeded test; when hypothesis is installed the
# same checkers also run under generated inputs (pyproject `test` extra).
# ---------------------------------------------------------------------------

def _exact_exponent(absmax: float) -> int:
    """floor(log2(absmax)) clipped, exact: frexp gives absmax = m * 2^e
    with m in [0.5, 1), so floor(log2) = e - 1 even at powers of two and
    just below them, where a floating-point log2 can round across."""
    return int(np.clip(np.frexp(np.float32(absmax))[1] - 1, bfp.EXP_MIN,
                       bfp.EXP_MAX))


def _check_roundtrip_error_bound(x: np.ndarray, m_bits: int):
    """|x - q(x)| <= truncation step derived from the group absmax, and
    the bound tightens with mantissa width."""
    xj = jnp.asarray(x)[None, :]
    fq = bfp.bfp_fake_quant(xj, 32, m_bits)
    absmax = float(jnp.max(jnp.abs(xj)))
    if absmax == 0:
        assert jnp.all(fq == 0)
        return
    E = _exact_exponent(absmax)
    step = 2.0 ** (float(E) - (m_bits - 2))
    assert float(jnp.max(jnp.abs(xj - fq))) <= step * (1 + 1e-5) + 1e-6


def _check_shared_exponent_dominance(x: np.ndarray):
    """The group absmax dictates everyone's scale: the stored exponent is
    floor(log2(absmax)) (clipped), and any element smaller than the
    implied step truncates to exactly zero — the 'outlier flattens its
    group' behaviour the smoothing machinery exists to fight."""
    xj = jnp.asarray(x)[None, :]
    mant, exp = bfp.bfp_quantize(xj, 32, 8)
    absmax = float(np.max(np.abs(x)))
    if absmax == 0:
        assert int(exp.reshape(-1)[0]) == bfp.EXP_MIN
        return
    expect = _exact_exponent(absmax)
    assert int(exp.reshape(-1)[0]) == expect
    step = 2.0 ** (expect - 6)               # 8-bit mantissa step
    fq = np.asarray(bfp.bfp_fake_quant(xj, 32, 8))[0]
    assert np.all(fq[np.abs(x) < step] == 0)


def _check_sign_preservation(x: np.ndarray, m_bits: int):
    """Truncation toward zero never flips a sign: q(x) is 0 or has the
    sign of x, elementwise."""
    fq = np.asarray(bfp.bfp_fake_quant(jnp.asarray(x)[None, :], 32,
                                       m_bits))[0]
    assert np.all((fq == 0) | (np.sign(fq) == np.sign(x)))


def _check_idempotence(x: np.ndarray, m_bits: int):
    """Quantizing an already-quantized block is the identity: q(x) stays
    on the BFP grid (truncation cannot drop the group absmax below the
    shared-exponent bucket floor, so the grid is unchanged)."""
    xj = jnp.asarray(x)[None, :]
    q1 = bfp.bfp_fake_quant(xj, 32, m_bits)
    q2 = bfp.bfp_fake_quant(q1, 32, m_bits)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


_BITS = (2, 4, 6, 8)


def test_shared_exponent_exact_at_powers_of_two():
    """The exponent is floor(log2) exactly at 2^e and just below it, and
    the quantizer's steps are exact powers of two, so a block whose
    absmax is a power of two is a fixed point (the saved Hypothesis
    example: m=3 with an 8193 outlier)."""
    e = np.arange(bfp.EXP_MIN, bfp.EXP_MAX + 1)
    at = np.float32(2.0) ** e.astype(np.float32)
    below = np.nextafter(at, np.float32(0))
    np.testing.assert_array_equal(
        np.asarray(bfp.shared_exponent(jnp.asarray(at))), e)
    np.testing.assert_array_equal(
        np.asarray(bfp.shared_exponent(jnp.asarray(below))),
        np.maximum(e - 1, bfp.EXP_MIN))
    k = np.arange(-40, 40)
    np.testing.assert_array_equal(np.asarray(bfp.pow2(jnp.asarray(k))),
                                  np.float32(2.0) ** k.astype(np.float32))
    x = np.zeros(32, np.float32)
    x[0], x[1] = 8193.0, -3.0
    _check_idempotence(x, 3)


def test_property_roundtrip_error_bound_seeded():
    rng = np.random.default_rng(10)
    for m_bits in _BITS:
        for scale in (1e-3, 1.0, 1e4):
            _check_roundtrip_error_bound(
                (rng.normal(size=32) * scale).astype(np.float32), m_bits)
    _check_roundtrip_error_bound(np.zeros(32, np.float32), 4)


def test_property_shared_exponent_dominance_seeded():
    rng = np.random.default_rng(11)
    for _ in range(8):
        x = rng.normal(size=32).astype(np.float32)
        x[int(rng.integers(32))] *= 1e3      # planted outlier
        _check_shared_exponent_dominance(x)
    _check_shared_exponent_dominance(np.zeros(32, np.float32))


def test_property_sign_preservation_seeded():
    rng = np.random.default_rng(12)
    for m_bits in _BITS:
        _check_sign_preservation(
            (rng.normal(size=32) * 100).astype(np.float32), m_bits)


def test_property_idempotence_seeded():
    rng = np.random.default_rng(13)
    for m_bits in _BITS:
        for scale in (1e-4, 1.0, 1e4):
            _check_idempotence(
                (rng.normal(size=32) * scale).astype(np.float32), m_bits)


if given is not None:
    _vals = st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32),
                     min_size=32, max_size=32)

    @settings(max_examples=30, deadline=None)
    @given(st.integers(2, 10), _vals)
    def test_hypothesis_error_bound(m_bits, vals):
        _check_roundtrip_error_bound(np.array(vals, np.float32), m_bits)

    @settings(max_examples=30, deadline=None)
    @given(_vals)
    def test_hypothesis_shared_exponent_dominance(vals):
        _check_shared_exponent_dominance(np.array(vals, np.float32))

    @settings(max_examples=30, deadline=None)
    @given(st.integers(2, 10), _vals)
    def test_hypothesis_sign_preservation(m_bits, vals):
        _check_sign_preservation(np.array(vals, np.float32), m_bits)

    @settings(max_examples=30, deadline=None)
    @given(st.integers(2, 10), _vals)
    def test_hypothesis_idempotence(m_bits, vals):
        _check_idempotence(np.array(vals, np.float32), m_bits)

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_hypothesis_pack_roundtrip(seed):
        rng = np.random.default_rng(seed)
        m = jnp.asarray(rng.integers(-8, 8, size=(2, 32)), jnp.int8)
        assert jnp.all(bfp.unpack_int4(bfp.pack_int4(m, -1), -1) == m)
else:
    def test_hypothesis_error_bound():
        pytest.importorskip("hypothesis")

    def test_hypothesis_shared_exponent_dominance():
        pytest.importorskip("hypothesis")

    def test_hypothesis_sign_preservation():
        pytest.importorskip("hypothesis")

    def test_hypothesis_idempotence():
        pytest.importorskip("hypothesis")

    def test_hypothesis_pack_roundtrip():
        pytest.importorskip("hypothesis")


def test_storage_accounting():
    assert bfp.kv_cache_reduction(8) == pytest.approx(0.4375)
    assert bfp.kv_cache_reduction(4) == pytest.approx(0.6875)
