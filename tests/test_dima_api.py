"""Unified backend API: zero-noise parity across every registered
substrate (the tests/_parity.py matrix), bit-for-bit
vectorized-vs-looped matvec, calibration, dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _parity import assert_bitwise_parity, make_pair, parametrize_backends
from repro import dima
from repro.core import noise as noise_mod
from repro.core import pipeline as pl
from repro.core.params import DimaParams

P = DimaParams()
FULL = {"dp": 255 * 255 * 256, "md": 255 * 256}
rng = np.random.default_rng(0)
D = jnp.asarray(rng.integers(0, 256, (200, 256)))
Q = jnp.asarray(rng.integers(0, 256, (256,)))
QS = jnp.asarray(rng.integers(0, 256, (3, 256)))
CHIP = noise_mod.sample_chip(jax.random.PRNGKey(3), P)
KEY = jax.random.PRNGKey(9)


# ---------------------------------------------------------------------------
# zero-noise parity: the standing backend matrix (tests/_parity.py)
# ---------------------------------------------------------------------------

@parametrize_backends()
@pytest.mark.parametrize("mode", ["dp", "md"])
def test_backend_parity_zero_noise(case, mode):
    """Every registered substrate must agree with its oracle exactly
    when no noise is drawn: same codes, bitwise-or-atol volts."""
    if mode not in case.modes:
        pytest.skip(f"{case.id} parity pinned for {case.modes} only")
    ref, ut = make_pair(case, P, CHIP if case.chip else None)
    assert_bitwise_parity("matvec", ref, ut, D, Q, mode=mode,
                          volts_atol=case.volts_atol)


@pytest.mark.parametrize("mode", ["dp", "md"])
def test_digital_within_systematic_envelope(mode):
    """Digital (exact, ideal-linear volts) vs the analog chain: the gap is
    only the calibrated systematic nonlinearity + ADC quantization —
    bounded by the Fig. 3/4 error envelopes."""
    ref = dima.get_backend("reference", P)
    dig = dima.get_backend("digital", P)
    a = ref.matvec(D, Q, mode=mode)
    d = dig.matvec(D, Q, mode=mode)
    dec_gap = np.abs(np.asarray(ref.decode(a.code, mode=mode))
                     - np.asarray(dig.decode(d.code, mode=mode)))
    assert np.max(dec_gap) / FULL[mode] < (0.045 if mode == "dp" else 0.06)
    v_gap = np.max(np.abs(np.asarray(a.volts) - np.asarray(d.volts)))
    fs = (255 * 255 * pl.dp_gain(P) if mode == "dp" else 255 * pl.md_gain(P))
    assert v_gap / fs < (0.045 if mode == "dp" else 0.06)


@parametrize_backends()
@pytest.mark.parametrize("mode", ["dp", "md"])
def test_matmat_parity_zero_noise(case, mode):
    if mode not in case.modes:
        pytest.skip(f"{case.id} parity pinned for {case.modes} only")
    ref, ut = make_pair(case, P, CHIP if case.chip else None)
    a = ut.matmat(D[:32], QS, mode=mode)
    assert a.code.shape == (3, 32)
    assert_bitwise_parity("matmat", ref, ut, D[:32], QS, mode=mode,
                          volts_atol=case.volts_atol)


def test_chip_record_expansion_inside_pallas_backend():
    """Callers hand the pallas backend a chip record + key, never the
    kernels' explicit noise arrays; zero-key results with a chip still
    match the reference exactly (fixed-pattern mismatch is static)."""
    ref = dima.get_backend("reference", P, CHIP)
    pal = dima.get_backend("pallas", P, CHIP)
    a = ref.matvec(D, Q)
    b = pal.matvec(D, Q)
    np.testing.assert_array_equal(np.asarray(a.code), np.asarray(b.code))
    # noisy path runs (statistically equivalent; key layouts differ)
    n = pal.matvec(D, Q, key=KEY)
    assert n.code.shape == (200,)


# ---------------------------------------------------------------------------
# vectorized matvec == the seed's per-row Python loop, bit-for-bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["dp", "md"])
@pytest.mark.parametrize("use_chip,use_key", [(False, False), (True, True)])
def test_vectorized_matvec_matches_seed_loop(mode, use_chip, use_key):
    chip = CHIP if use_chip else None
    key = KEY if use_key else None
    m = 24
    vec = pl.dima_matvec(D[:m], Q, P, chip, key, mode)
    loop = pl.dima_matvec_loop(D[:m], Q, P, chip, key, mode)
    np.testing.assert_array_equal(np.asarray(vec.volts),
                                  np.asarray(loop.volts))
    np.testing.assert_array_equal(np.asarray(vec.code),
                                  np.asarray(loop.code))
    assert vec.n_cycles == loop.n_cycles
    assert vec.n_conversions == loop.n_conversions


def test_backend_matvec_matches_seed_loop():
    """Through the jitted backend entry point: codes identical; volts may
    drift by XLA-fusion float reassociation (≤ 1 ulp observed)."""
    be = dima.get_backend("reference", P, CHIP)
    vec = be.matvec(D[:16], Q, key=KEY)
    loop = pl.dima_matvec_loop(D[:16], Q, P, CHIP, KEY)
    np.testing.assert_allclose(np.asarray(vec.volts),
                               np.asarray(loop.volts), atol=1e-7)
    np.testing.assert_array_equal(np.asarray(vec.code),
                                  np.asarray(loop.code))


# ---------------------------------------------------------------------------
# factory / dispatch / registry
# ---------------------------------------------------------------------------

def test_get_backend_factory():
    for name in ("digital", "reference", "pallas", "auto"):
        be = dima.get_backend(name, P)
        assert type(be).name == name and be.p is P
    be = dima.get_backend("reference", P, CHIP)
    assert dima.get_backend(be) is be            # pass-through
    assert be.ideal().chip is None and be.ideal().p is P
    with pytest.raises(KeyError, match="unknown backend"):
        dima.get_backend("fpga")


def test_auto_dispatch():
    # min_rows pinned: the dispatch logic under test must not depend on
    # whatever measured crossover a local bench run left in
    # BENCH_dima_api.json (covered by test_multibank)
    auto = dima.get_backend("auto", P, CHIP, min_rows=128)
    assert type(auto.pick(D, Q)).name == "pallas"          # large bank
    assert type(auto.pick(D[:4], Q)).name == "reference"   # small batch
    assert type(auto.pick(D[0], Q)).name == "reference"    # single op
    long = jnp.zeros((300, 512), jnp.int32)
    assert type(auto.pick(long, jnp.zeros(512, jnp.int32))).name == "reference"
    out = auto.matvec(D, Q, mode="md")
    ref = dima.get_backend("reference", P, CHIP).matvec(D, Q, mode="md")
    np.testing.assert_array_equal(np.asarray(out.code), np.asarray(ref.code))


def test_register_backend_plugin():
    @dima.register_backend("_test_sub")
    class Sub(dima.DigitalBackend):
        pass
    try:
        assert type(dima.get_backend("_test_sub", P)).name == "_test_sub"
    finally:
        del dima.BACKENDS["_test_sub"]


def test_mode_and_shape_validation():
    with pytest.raises(ValueError, match="unknown mode"):
        dima.get_backend("reference", P).dot(D[0], Q, mode="xor")
    # >1-conversion misuse fails loudly and identically on every backend
    # (instead of silently saturating the programmed ADC range)
    for name in ("digital", "reference", "pallas"):
        be = dima.get_backend(name, P)
        with pytest.raises(ValueError, match="chunked_dot"):
            be.matvec(jnp.zeros((8, 512), jnp.int32),
                      jnp.zeros(512, jnp.int32))
        with pytest.raises(ValueError, match="chunked_dot"):
            be.dot(jnp.zeros(512, jnp.int32), jnp.zeros(512, jnp.int32))


# ---------------------------------------------------------------------------
# shared calibration
# ---------------------------------------------------------------------------

def test_calibration_range_and_trim():
    be = dima.get_backend("reference", P, CHIP)
    stored = D[:1]                                  # one stored row
    target = np.asarray(pl.digital_dot(stored, QS), np.float64)
    cal = dima.calibrate(be, stored, QS, mode="dp", target=target, key=KEY)
    lo, hi = cal.v_range
    assert lo < hi and cal.coef is not None and cal.coef.shape == (3,)
    scores = dima.trimmed_scores(cal, be, stored, QS, key=KEY)
    # trim fitted on these queries reconstructs the digital score closely
    assert np.max(np.abs(scores - target)) / FULL["dp"] < 0.02


def test_calibration_range_only():
    be = dima.get_backend("reference", P)
    cal = dima.calibrate(be, D[None, :32, :], QS[:, None, :], mode="md")
    assert cal.coef is None
    out = be.manhattan(D[None, :32, :], QS[:, None, :], v_range=cal.v_range)
    codes = np.asarray(out.code)
    assert codes.shape == (3, 32) and codes.max() <= 255 and codes.min() >= 0


def test_chunked_dot_long_vectors():
    """506-dim SVM-style op: chunked conversions, decoded sum ≈ exact."""
    w = jnp.asarray(rng.integers(0, 256, (506,)))
    X = jnp.asarray(rng.integers(0, 256, (10, 506)))
    for name in ("digital", "reference"):
        be = dima.get_backend(name, P)
        dec = np.asarray(dima.chunked_dot(be, w[None, :], X))
        exact = np.asarray(pl.digital_dot(w[None, :], X))
        assert np.max(np.abs(dec - exact)) / (2 * FULL["dp"]) < 0.045


def test_chunked_dot_fused_matches_loop():
    """The vectorized chunked_dot (chunks stacked on a leading axis,
    fold_in(key, chunk) via _fold_each, ONE jitted dispatch) is bitwise
    identical to the seed's per-chunk loop — ragged last chunk (506 =
    256 + 250) and a >2-chunk shape (1030) alike, noisy and noise-free,
    on digital and reference substrates."""
    # own stream: the shared module rng's draw order depends on which
    # tests ran first, and this parity must hold for fixed data
    r = np.random.default_rng(7)
    w = jnp.asarray(r.integers(0, 256, (506,)))
    X = jnp.asarray(r.integers(0, 256, (10, 506)))
    w3 = jnp.asarray(r.integers(0, 256, (1030,)))
    X3 = jnp.asarray(r.integers(0, 256, (6, 1030)))
    for name in ("digital", "reference"):
        be = dima.get_backend(name, P, CHIP if name == "reference" else None)
        for key in (None, KEY):
            for s, q in ((w[None, :], X), (w3[None, :], X3)):
                fused = np.asarray(dima.chunked_dot(be, s, q, key=key))
                loop = np.asarray(dima.chunked_dot_loop(be, s, q, key=key))
                np.testing.assert_array_equal(fused, loop)
    be = dima.get_backend("reference", P)
    dima.chunked_dot(be, w[None, :], X, key=KEY)            # warm up
    with dima.count_dispatches() as c:
        dima.chunked_dot(be, w[None, :], X, key=KEY)
    assert c.n == 1                  # one dispatch, not one per chunk


def test_count_dispatches_skips_launches_traced_into_a_jit():
    """On its first call ``chunked_dot`` traces the backend's own jitted
    dot into its jit: those inner launches return tracers and are not
    dispatches; only the outer launch counts."""
    r = np.random.default_rng(5)
    w = jnp.asarray(r.integers(0, 256, (506,)))
    X = jnp.asarray(r.integers(0, 256, (10, 506)))
    be = dima.get_backend("reference", P)        # nothing traced yet
    with dima.count_dispatches() as c:
        dima.chunked_dot(be, w[None, :], X, key=KEY)
    assert c.n == 1


def test_stable_crossover_rule_tolerates_non_monotonic_timings():
    """The persisted auto_crossover_rows rule (docs/benchmarks.md): an
    isolated noisy loss above the threshold doesn't void the
    measurement; a lucky small-size win can't drag the threshold down;
    losing at the largest count means no crossover."""
    from benchmarks.bench_dima import stable_crossover
    row = lambda m, ref, pal: {"rows": m, "reference_us": ref,
                               "pallas_us": pal}
    assert stable_crossover([]) is None        # not measured at all
    # clean monotonic crossover at 128
    assert stable_crossover([row(64, 1, 2), row(128, 3, 2),
                             row(256, 6, 3)]) == 128
    # isolated loss at 256 no longer voids the 128 threshold
    assert stable_crossover([row(64, 1, 2), row(128, 3, 2), row(256, 3, 4),
                             row(512, 9, 4), row(1024, 20, 8)]) == 128
    # a lucky win at 16 can't drag the threshold below the rule
    assert stable_crossover([row(16, 3, 2), row(64, 2, 4), row(128, 2, 4),
                             row(256, 6, 3), row(512, 9, 4)]) == 256
    # pallas losing at the largest measured count -> MEASURED "never"
    # (distinct from None so AutoBackend doesn't fall back to 128 and
    # route large matvecs onto the path the sweep just measured slower)
    assert stable_crossover([row(64, 2, 1), row(128, 3, 2),
                             row(256, 3, 4)]) == "never"
    from repro.core.api import _MIN_ROWS_NEVER
    assert _MIN_ROWS_NEVER > 10 ** 9


def test_applications_run_on_pallas_backend():
    """The apps' backend parameter accepts any registered substrate: the
    broadcast layouts they use decompose onto the banked kernels."""
    from repro.core.applications import run_tm
    r = run_tm(P, CHIP, KEY, backend="pallas")
    assert r.acc_digital == 1.0
    assert abs(r.acc_dima - r.acc_digital) <= 0.02 + 1e-9


def test_auto_matmat_uses_picked_backend():
    auto = dima.get_backend("auto", P)
    out = auto.matmat(D[:8], QS)                    # below min_rows
    ref = dima.get_backend("reference", P).matmat(D[:8], QS)
    np.testing.assert_array_equal(np.asarray(out.code), np.asarray(ref.code))
    assert out.code.shape == (3, 8)


# ---------------------------------------------------------------------------
# serving-layer integration
# ---------------------------------------------------------------------------

def test_weights_energy_per_token_backends():
    n_active = 1_000_000
    pj_dima, banks = dima.weights_energy_per_token(
        n_active, dima.get_backend("reference", P))
    pj_conv, _ = dima.weights_energy_per_token(
        n_active, dima.get_backend("digital", P))
    assert banks == int(np.ceil(n_active * 8 / (P.n_rows * P.n_cols)))
    assert pj_conv > 4 * pj_dima            # the paper's savings ordering
