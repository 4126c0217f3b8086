"""Bit-plane decomposition (repro.quant.bitplanes): exhaustive
deterministic roundtrips plus hypothesis property tests (pack→unpack
identity over random shapes/bit-widths; bitserial B=8 == digital at
zero noise over random data and ADC windows)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import dima
from repro.core.params import DimaParams
from repro.quant import bitplanes as bp

P = DimaParams()


# ---------------------------------------------------------------------------
# deterministic: exhaustive over the full 8-b alphabet
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_planes", bp.PLANE_COUNTS)
def test_split_merge_roundtrip_all_words(n_planes):
    words = np.arange(256, dtype=np.uint8)
    planes = bp.split_planes(words, n_planes)
    assert planes.shape == (n_planes, 256) and planes.dtype == np.uint8
    assert int(planes.max()) <= (1 << bp.plane_width(n_planes)) - 1
    np.testing.assert_array_equal(bp.merge_planes(planes, n_planes), words)
    # LSB-first: plane 0 holds the low bits
    np.testing.assert_array_equal(
        planes[0], words & ((1 << bp.plane_width(n_planes)) - 1))


def test_plane_width_and_scale():
    assert [bp.plane_width(b) for b in bp.PLANE_COUNTS] == [8, 4, 2, 1]
    assert bp.plane_scale(1) == 1.0
    assert bp.plane_scale(2) == pytest.approx(15.0 / 255.0)
    assert bp.plane_scale(8) == pytest.approx(1.0 / 255.0)
    for bad in (0, 3, 5, 16):
        with pytest.raises(ValueError):
            bp.plane_width(bad)


def test_merge_infers_plane_count():
    words = np.arange(256, dtype=np.uint8)
    planes = bp.split_planes(words, 4)
    np.testing.assert_array_equal(bp.merge_planes(planes), words)


def test_sign_split_roundtrip_and_validation():
    vals = np.arange(-255, 256, dtype=np.int32)
    pos, neg = bp.sign_split(vals)
    assert pos.dtype == np.uint8 and neg.dtype == np.uint8
    assert not np.logical_and(pos > 0, neg > 0).any()
    np.testing.assert_array_equal(bp.sign_merge(pos, neg), vals)
    with pytest.raises(ValueError):
        bp.sign_split(np.asarray([256]))
    with pytest.raises(ValueError):
        bp.sign_split(np.asarray([-256]))


def test_signed_planes_compose():
    """sign-split magnitudes bit-plane cleanly: merge∘split on each rail
    then sign_merge reconstructs the signed value."""
    rng = np.random.default_rng(5)
    vals = rng.integers(-255, 256, (64,), dtype=np.int32)
    pos, neg = bp.sign_split(vals)
    for n_planes in bp.PLANE_COUNTS:
        rp = bp.merge_planes(bp.split_planes(pos, n_planes), n_planes)
        rn = bp.merge_planes(bp.split_planes(neg, n_planes), n_planes)
        np.testing.assert_array_equal(
            bp.sign_merge(rp.astype(np.uint8), rn.astype(np.uint8)), vals)


# ---------------------------------------------------------------------------
# property-based
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([1, 2, 4, 8]),
       st.integers(1, 3), st.integers(1, 40))
def test_roundtrip_identity_random_shapes(seed, n_planes, ndim, dim0):
    rng = np.random.default_rng(seed)
    shape = (dim0,) + tuple(int(x) for x in rng.integers(1, 9, ndim - 1))
    words = rng.integers(0, 256, shape, dtype=np.uint8)
    planes = bp.split_planes(words, n_planes)
    assert planes.shape == (n_planes,) + shape
    np.testing.assert_array_equal(bp.merge_planes(planes, n_planes), words)
    # merged shifted weights telescope: sum_k plane_k << (k*w) == word
    w = bp.plane_width(n_planes)
    acc = sum(planes[k].astype(np.int64) << (k * w)
              for k in range(n_planes))
    np.testing.assert_array_equal(acc, words.astype(np.int64))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 24), st.integers(8, 256),
       st.booleans())
def test_bitserial_b8_equals_digital_zero_noise(seed, m, n, custom_range):
    """Full serialization (B=8, 1-b planes) at zero noise / ideal chip
    is bitwise the digital backend, for arbitrary shapes and windows."""
    rng = np.random.default_rng(seed)
    d = rng.integers(0, 256, (m, n), dtype=np.uint8)
    q = rng.integers(0, 256, (n,), dtype=np.uint8)
    vr = None
    if custom_range:
        hi = float(rng.integers(1000, 65026)) * 255.0 * dima.dp_gain(P)
        vr = (0.0, hi)
    dig = dima.get_backend("digital", P)
    bs = dima.get_backend("bitserial", P, None, n_planes=8)
    a = dig.matvec(d, q, mode="dp", v_range=vr)
    b = bs.matvec(d, q, mode="dp", v_range=vr)
    np.testing.assert_array_equal(np.asarray(a.code), np.asarray(b.code))
    np.testing.assert_array_equal(np.asarray(a.volts), np.asarray(b.volts))


# canary: records whether property bodies actually execute
_RUNS = []


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10))
def test_property_canary(x):
    _RUNS.append(x)


def test_canary_ran_iff_hypothesis_installed():
    """Relies on pytest's file-order execution: the canary above has
    already run by the time this asserts."""
    assert _RUNS, "hypothesis property body never ran"
