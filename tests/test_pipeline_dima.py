"""The 4-stage pipeline: ideal == digital within ADC quantization; the
Fig. 4 measured error envelopes; property-based invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import noise as noise_mod
from repro.core import pipeline as pl
from repro.core.params import DimaParams

P = DimaParams()
KEY = jax.random.PRNGKey(0)
FULL_DP = 255 * 255 * 256
FULL_MD = 255 * 256


def test_ideal_dp_close_to_digital():
    rng = np.random.default_rng(0)
    D = rng.integers(0, 256, (8, 256))
    Q = rng.integers(0, 256, (256,))
    out = pl.dima_dot(D, Q, P)
    dec = np.asarray(pl.code_to_dot(out.code, P))
    exact = np.asarray(pl.digital_dot(D, Q))
    # ideal chain: only ADC quantization (1/255) + calibrated INL + mult bow
    assert np.max(np.abs(dec - exact)) / FULL_DP < 0.045


def test_ideal_md_close_to_digital():
    rng = np.random.default_rng(1)
    D = rng.integers(0, 256, (8, 256))
    Q = rng.integers(0, 256, (256,))
    out = pl.dima_manhattan(D, Q, P)
    dec = np.asarray(pl.code_to_md(out.code, P))
    exact = np.asarray(pl.digital_manhattan(D, Q))
    assert np.max(np.abs(dec - exact)) / FULL_MD < 0.06


def test_fig4_dp_error_envelope():
    """Measured max error 5.8 % of dynamic range on the D=P=const sweep."""
    chip = noise_mod.sample_chip(jax.random.PRNGKey(42), P)
    errs = []
    for val in range(0, 256, 8):
        D = np.full((256,), val)
        out = pl.dima_dot(D, D, P, chip, jax.random.fold_in(KEY, val))
        dec = float(pl.code_to_dot(out.code, P))
        errs.append(abs(dec - val * val * 256) / FULL_DP * 100)
    assert 4.0 < max(errs) < 7.5, max(errs)   # paper: 5.8 %


def test_fig4_md_error_envelope():
    chip = noise_mod.sample_chip(jax.random.PRNGKey(7), P)
    errs = []
    for val in range(0, 256, 8):
        D = np.full((256,), val)
        Q = np.full((256,), 255 - val)
        out = pl.dima_manhattan(D, Q, P, chip, jax.random.fold_in(KEY, val))
        dec = float(pl.code_to_md(out.code, P))
        errs.append(abs(dec - abs(2 * val - 255) * 256) / FULL_MD * 100)
    assert 6.5 < max(errs) < 11.0, max(errs)  # paper: 8.6 %


def test_cycles_and_conversions_accounting():
    D = np.zeros((256,), np.uint8)
    out = pl.dima_dot(D, D, P)
    assert out.n_cycles == 2 and out.n_conversions == 1
    out = pl.dima_dot(np.zeros((100,)), np.zeros((100,)), P)
    assert out.n_cycles == 2                   # padded to one conversion


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_dp_scaling_invariant(seed):
    """Noiseless DP decode error is bounded for random data (property)."""
    rng = np.random.default_rng(seed)
    D = rng.integers(0, 256, (256,))
    Q = rng.integers(0, 256, (256,))
    out = pl.dima_dot(D, Q, P)
    dec = float(pl.code_to_dot(out.code, P))
    exact = float(pl.digital_dot(D, Q))
    assert abs(dec - exact) / FULL_DP < 0.045


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_md_symmetry(seed):
    """|D−P| must be symmetric under swapping D and P (dual-rail mux)."""
    rng = np.random.default_rng(seed)
    D = rng.integers(0, 256, (256,))
    Q = rng.integers(0, 256, (256,))
    a = pl.dima_manhattan(D, Q, P).volts
    b = pl.dima_manhattan(Q, D, P).volts
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-9)


def test_md_zero_distance():
    D = np.random.default_rng(2).integers(0, 256, (256,))
    out = pl.dima_manhattan(D, D, P)
    assert float(out.volts) < 1e-3 * 255 * pl.md_gain(P)


def test_delta_v_sweep_degrades_snr():
    """Fig. 5: lower ΔV_BL -> the fixed mV-scale noise floors grow relative
    to the signal.  Isolate the *random* component (the systematic betas
    are scale-free) by measuring shot-to-shot reproducibility."""
    rng = np.random.default_rng(3)
    D = rng.integers(0, 256, (32, 256))
    Q = rng.integers(0, 256, (256,))

    def rand_err(delta_v):
        p = P.with_delta_v(delta_v)
        chip = noise_mod.sample_chip(jax.random.PRNGKey(1), p)
        v1 = np.asarray(pl.dima_dot(D, Q, p, chip, KEY).volts, np.float64)
        v2 = np.asarray(pl.dima_dot(D, Q, p, chip,
                                    jax.random.PRNGKey(99)).volts, np.float64)
        fs = 255 * 255 * pl.dp_gain(p)
        return np.mean(np.abs(v1 - v2)) / fs

    assert rand_err(0.002) > rand_err(0.025) * 5.0
