"""MR-FR: PWM transfer linearity, sub-ranged merge, bit-cell layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import mapping
from repro.core.functional_read import (mr_fr, pwm_transfer, split_words,
                                        subrange_merge, word_gain)
from repro.core.params import DimaParams

P = DimaParams()


def test_inl_matches_paper():
    """Fig. 3: max INL of the merged 8-b read = 0.03 LSB (best-fit line)."""
    codes = jnp.arange(256)
    m, l = (codes >> 4) & 15, codes & 15
    v = (16 * pwm_transfer(m.astype(jnp.float32), P)
         + pwm_transfer(l.astype(jnp.float32), P)) / 17
    A = jnp.stack([codes.astype(jnp.float32), jnp.ones(256)], 1)
    coef, *_ = jnp.linalg.lstsq(A, v)
    inl = float(jnp.max(jnp.abs(v - A @ coef)) / (P.delta_v_lsb / 17))
    assert 0.02 <= inl <= 0.04, inl


def test_transfer_monotone_and_bounded():
    c = jnp.arange(31.0)
    v = pwm_transfer(c, P, replica=True)
    assert bool(jnp.all(jnp.diff(v) > 0)), "transfer must stay monotone"
    assert float(v[0]) == 0.0


def test_subrange_merge_ratio():
    vm, vl = jnp.asarray(0.3), jnp.asarray(0.1)
    out = subrange_merge(vm, vl, P)
    assert np.isclose(float(out), (16 * 0.3 + 0.1) / 17)


def test_word_gain_identity():
    """Noiseless read of word w gives exactly w·δ/17 when INL is off."""
    import dataclasses
    p0 = dataclasses.replace(P, inl_beta=0.0)
    words = jnp.arange(0, 256, 17, dtype=jnp.int32)
    m, l = split_words(words)
    v = mr_fr(m, l, p0)
    np.testing.assert_allclose(np.asarray(v),
                               np.asarray(words) * word_gain(p0), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_pack_unpack_roundtrip(seed):
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 256, (P.word_rows, P.words_per_access), np.uint8)
    bits = mapping.pack(words, P)
    assert bits.shape == (P.n_rows, P.n_cols)
    back = np.asarray(mapping.unpack(bits, P))
    np.testing.assert_array_equal(back, words)


def test_subwords_matches_layout():
    rng = np.random.default_rng(3)
    words = rng.integers(0, 256, (P.word_rows, P.words_per_access), np.uint8)
    bits = mapping.pack(words, P)
    for r in (0, 5, 127):
        m, l = mapping.subwords(bits, r, P)
        np.testing.assert_array_equal(np.asarray(m), words[r] >> 4)
        np.testing.assert_array_equal(np.asarray(l), words[r] & 15)


def test_vectors_to_banks_capacity():
    mat = np.random.default_rng(0).integers(0, 256, (64, 256), np.uint8)
    banks, layout = mapping.vectors_to_banks(mat, P)
    assert banks.shape == (1, 512, 256)       # 64×256 dims fill one bank
    assert len(layout) == 64
    # unpack and verify a stored vector
    words = np.asarray(mapping.unpack(banks[0], P))
    b, r0, nr = layout[7]
    np.testing.assert_array_equal(words[r0:r0 + nr].reshape(-1), mat[7])


def test_banks_for_matrix():
    assert mapping.banks_for_matrix((512, 256), bits=8) == 8  # 128KB / 16KB
