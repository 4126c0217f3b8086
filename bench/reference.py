"""Plain reference of the DIMA chain that decides a run's ``correct``.

Written from the paper's transfer functions (arXiv:1610.07501, Fig. 2-4)
in straightforward ``jax.numpy``, importing nothing of the program under
test.  It takes the circuit constants from the configuration file, the
silicon instance and the data from the benchmark's seed, and the noise
from the call's key with the documented stream layout:

* bank ``b`` of a call with key ``k`` draws from ``fold_in(k, b)``;
* a call of ``B`` queries (``matmat``) gives query ``j`` the key
  ``split(fold_in(k, b), B)[j]``; a one-query call (``matvec``) uses the
  bank key itself;
* one query's key splits in four; DP draws the read noise ``(R, 2, 128)``
  and the CBLP rail noise ``(R, 2, 2)`` from the first two, MD the
  comparator offset, BL and BLB read noise ``(R, 2, 128)`` and the CBLP
  noise ``(R, 2)`` from the four, each a unit normal times its sigma;
* ``R`` is the bank's row count rounded up to a whole 128-row block,
  and a bank's rows take the first of those draws (a counter-based
  draw is not prefix-stable, so the block size is part of the stream).

So the program's codes, for the same stored data, queries and keys, must
equal these bit for bit but for float32 round-off at an ADC step.
``dtype`` computes the chain in another precision: bfloat16 is the
control that a correct comparison has to reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

WORDS = 128              # words per access cycle; two cycles per 256-dim op
DIMS = 2 * WORDS
BLOCK = 128              # noise is drawn per bank for whole blocks of rows


def drawn_rows(rows):
    """Rows of noise a bank of ``rows`` stored rows draws per query."""
    return -(-rows // BLOCK) * BLOCK


def sample_chip(key, prm):
    """Fixed-pattern mismatch of one silicon instance: per-column-pair
    read gain and 16:1 merge-cap error, per-rail multiplier gain and
    offset."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "col_gain": 1.0 + prm["sigma_gain_col"]
        * jax.random.normal(k1, (WORDS,)),
        "cap_ratio_err": prm["sigma_cap_ratio"]
        * jax.random.normal(k2, (WORDS,)),
        "mult_gain": 1.0 + prm["sigma_mult_gain"]
        * jax.random.normal(k3, (2, WORDS)),
        "mult_off": prm["sigma_mult_off_mv"] * 1e-3
        * jax.random.normal(k4, (2, WORDS)),
    }


def ideal_chip():
    return {"col_gain": jnp.ones((WORDS,)),
            "cap_ratio_err": jnp.zeros((WORDS,)),
            "mult_gain": jnp.ones((2, WORDS)),
            "mult_off": jnp.zeros((2, WORDS))}


def query_noise(key, prm, mode, rows):
    """One query's dynamic noise against ``rows`` stored rows (volts)."""
    ks = jax.random.split(key, 4)
    rd = prm["sigma_read_mv"] * 1e-3
    cb = prm["sigma_cblp_mv"] * 1e-3
    if mode == "dp":
        return (rd * jax.random.normal(ks[0], (rows, 2, WORDS)),
                cb * jax.random.normal(ks[1], (rows, 2, 2)))
    cm = prm["sigma_cmp_off_mv"] * 1e-3
    return (cm * jax.random.normal(ks[0], (rows, 2, WORDS)),
            rd * jax.random.normal(ks[1], (rows, 2, WORDS)),
            rd * jax.random.normal(ks[2], (rows, 2, WORDS)),
            cb * jax.random.normal(ks[3], (rows, 2)))


def zero_noise(mode, rows):
    z = lambda *s: jnp.zeros(s, jnp.float32)
    if mode == "dp":
        return z(rows, 2, WORDS), z(rows, 2, 2)
    return (z(rows, 2, WORDS), z(rows, 2, WORDS), z(rows, 2, WORDS),
            z(rows, 2))


def dp_gain(prm):
    """Ideal volts per unit of mean(D*P)."""
    return prm["delta_v_lsb"] / 17.0 / (16.0 * 17.0)


def md_gain(prm):
    """Ideal volts per unit of mean(|D-P|)."""
    return prm["delta_v_lsb"] / 17.0


def _transfer(c, prm, beta):
    """PWM functional-read swing of a (summed) 4-b sub-word code."""
    return prm["delta_v_lsb"] * c * (1.0 - beta * c)


def _read(words, rep, chip, noise, prm, beta, dt):
    """Functional read of 128-word rows: both sub-words, plus the
    replica words in MD, merged 16:1 through the trimmed caps."""
    m = ((words >> 4) & 0xF).astype(dt)
    l = (words & 0xF).astype(dt)
    if rep is not None:
        m = m + ((rep >> 4) & 0xF).astype(dt)
        l = l + (rep & 0xF).astype(dt)
    r = 16.0 * (1.0 + chip["cap_ratio_err"].astype(dt))
    v = (r * _transfer(m, prm, beta) + _transfer(l, prm, beta)) / (r + 1.0)
    return v * chip["col_gain"].astype(dt) + noise.astype(dt)


def _adc(v, v_range, prm, dt):
    full = float(2 ** prm["adc_bits"] - 1)
    lo, hi = v_range[0].astype(dt), v_range[1].astype(dt)
    x = (v - lo) / jnp.maximum(hi - lo, 1e-9)
    return jnp.clip(jnp.round(x * full), 0, full).astype(jnp.int32)


def chain(mode, d, q, chip, noise, v_range, prm, dt=jnp.float32):
    """One query q (256,) uint8 against rows d (R, 256) uint8 through
    functional read, BLP, CBLP and the 8-b ADC.  Returns (codes (R,)
    int32, volts (R,))."""
    R = d.shape[0]
    d2 = d.astype(jnp.int32).reshape(R, 2, WORDS)
    q2 = q.astype(jnp.int32).reshape(2, WORDS)
    if mode == "dp":
        rn, cn = noise
        v_word = _read(d2, None, chip, rn, prm, prm["inl_beta"], dt)
        pm = ((q2 >> 4) & 0xF).astype(dt)
        pl = (q2 & 0xF).astype(dt)
        mg = chip["mult_gain"].astype(dt)
        mo = chip["mult_off"].astype(dt)
        mb = prm["mult_beta"]
        rail_m = v_word * (pm / 16.0) * (1.0 - mb * pm) * mg[0] \
            + mo[0] * (pm > 0)
        rail_l = v_word * (pl / 16.0) * (1.0 - mb * pl) * mg[1] \
            + mo[1] * (pl > 0)
        cn = cn.astype(dt)
        vm = jnp.mean(rail_m, axis=2) + cn[:, :, 0]
        vl = jnp.mean(rail_l, axis=2) + cn[:, :, 1]
        v = (16.0 * jnp.mean(vm, axis=1) + jnp.mean(vl, axis=1)) / 17.0
    else:
        cmp_n, rn, rnb, cn = noise
        beta = prm["md_inl_beta"]
        v_bl = _read(d2, 255 - q2, chip, rn, prm, beta, dt)     # D + P-bar
        v_blb = _read(255 - d2, q2, chip, rnb, prm, beta, dt)   # D-bar + P
        c15 = jnp.asarray(15.0, dt)
        vref = (16.0 * _transfer(c15, prm, beta)
                + _transfer(c15, prm, beta)) / 17.0
        pick = (v_bl + cmp_n.astype(dt)) >= v_blb
        v_abs = jnp.maximum(jnp.where(pick, v_bl, v_blb) - vref, 0.0)
        v = jnp.mean(jnp.mean(v_abs, axis=2) + cn.astype(dt), axis=1)
    return _adc(v, v_range, prm, dt), v


def trim(mode, code, q_sum, coef, v_range, prm, dt=jnp.float32):
    """Decode an ADC code to dot units and apply the affine trim
    ``c0 * dot_hat + c1 * sum(q) + c2``."""
    full = float(2 ** prm["adc_bits"] - 1)
    lo, hi = v_range[0].astype(dt), v_range[1].astype(dt)
    vd = lo + code.astype(dt) / full * (hi - lo)
    gain = dp_gain(prm) if mode == "dp" else md_gain(prm)
    dot_hat = vd / gain * DIMS
    c = coef.astype(dt)
    return (c[0] * dot_hat + c[1] * q_sum.astype(dt)) + c[2]


@functools.partial(jax.jit, static_argnames=("mode", "prm_items", "one",
                                             "noisy", "dtype", "n_banks"))
def _call(stored, queries, chip, key, v_range, coef, *, mode, prm_items,
          one, noisy, dtype, n_banks):
    prm = dict(prm_items)
    dt = jnp.dtype(dtype)
    M = stored.shape[0]
    B = queries.shape[0]
    rows = M // n_banks
    R = drawn_rows(rows)
    banks = stored.reshape(n_banks, rows, DIMS)
    q_sum = queries.astype(jnp.float32).sum(-1)

    def per_bank(args):
        b, d = args
        if not noisy:
            keys = None
        elif one:
            keys = jax.random.fold_in(key, b)[None]
        else:
            keys = jax.random.split(jax.random.fold_in(key, b), B)

        def per_query(q, k):
            noise = (query_noise(k, prm, mode, R) if noisy
                     else zero_noise(mode, R))
            noise = tuple(a[:rows] for a in noise)
            return chain(mode, d, q, chip, noise, v_range, prm, dt)

        if noisy:
            return jax.vmap(per_query)(queries, keys)
        return jax.vmap(lambda q: per_query(q, None))(queries)

    code, volts = jax.lax.map(per_bank, (jnp.arange(n_banks), banks))
    code = code.transpose(1, 0, 2).reshape(B, M)          # (B, M)
    volts = volts.transpose(1, 0, 2).reshape(B, M).astype(jnp.float32)
    trimmed = trim(mode, code, q_sum[:, None], coef, v_range, prm,
                   dt).astype(jnp.float32)
    return code, volts, trimmed


def run(mode, stored, queries, chip, key, v_range, coef, prm, *,
        n_banks, one=False, dtype="float32"):
    """The chain over every stored row, split into ``n_banks`` equal
    banks, for a call of ``queries`` (B, 256) with one key: ``one`` is
    the one-query (matvec) noise layout, which needs B == 1.  ``key=None``
    is the noise-free chain.  Returns (codes (B, M), volts (B, M),
    trimmed (B, M)); ``coef`` (3,) may be zeros where the cell applies no
    trim."""
    if one and queries.shape[0] != 1:
        raise ValueError("the matvec layout is one query per call")
    if stored.shape[0] % n_banks:
        raise ValueError(f"{stored.shape[0]} rows do not fill {n_banks} "
                         "equal banks")
    return _call(stored, queries, chip,
                 jnp.zeros((2,), jnp.uint32) if key is None else key,
                 jnp.asarray(v_range, jnp.float32),
                 jnp.asarray(coef, jnp.float32), mode=mode,
                 prm_items=tuple(sorted(prm.items())), one=one,
                 noisy=key is not None, dtype=dtype, n_banks=n_banks)


def run_many(mode, stored, queries, chip, keys, v_range, coef, prm, *,
             n_banks, dtype="float32"):
    """Several one-query calls at once: queries (n, 256) with keys (n, 2),
    each in the matvec noise layout.  Returns (codes, volts, trimmed),
    each (n, M)."""
    f = jax.vmap(lambda q, k: _call(
        stored, q[None], chip, k, jnp.asarray(v_range, jnp.float32),
        jnp.asarray(coef, jnp.float32), mode=mode,
        prm_items=tuple(sorted(prm.items())), one=True, noisy=True,
        dtype=dtype, n_banks=n_banks))
    code, volts, trimmed = f(queries, keys)
    return code[:, 0], volts[:, 0], trimmed[:, 0]


def digital_scores(mode, stored, queries):
    """Exact 8-b scores (int32, (B, M)): the offset-binary signed
    correlation in DP, the Manhattan distance in MD."""
    d = jnp.asarray(stored, jnp.int32)
    q = jnp.asarray(queries, jnp.int32)
    if mode == "dp":
        dot = q @ d.T
        return (dot - 128 * q.sum(-1)[:, None] - 128 * d.sum(-1)[None, :]
                + DIMS * 128 * 128)
    return jnp.abs(q[:, None, :] - d[None, :, :]).sum(-1)


def affine_trim(dot_hat, q_sum, target):
    """Least-squares trim [dot_hat, sum(q), 1] -> target, in float64."""
    A = np.stack([np.ravel(dot_hat), np.ravel(q_sum),
                  np.ones(np.size(dot_hat))], axis=1)
    coef, *_ = np.linalg.lstsq(A, np.ravel(target).astype(np.float64),
                               rcond=None)
    return coef
