"""The full 4-stage deep in-memory pipeline (Fig. 1/2):

    MR-FR  →  BLP  →  CBLP  →  ADC & slice

``dima_dot`` / ``dima_manhattan`` process one ≤256-dim operation per ADC
conversion (two access cycles of 128 words charge-shared, exactly the
prototype's dataflow).  Everything is vectorized over leading batch dims
(queries × stored vectors × banks) — the massively-parallel multi-bank
scenario is a vmap.

A parallel exact *digital reference* implements the conventional
architecture's arithmetic for the ≤1 %-accuracy-gap experiments.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adc as adc_mod
from repro.core import blp as blp_mod
from repro.core import cblp as cblp_mod
from repro.core import functional_read as fr
from repro.core.params import DimaParams


class DimaOut(NamedTuple):
    code: jnp.ndarray        # ADC output (int32)
    volts: jnp.ndarray       # pre-ADC analog value
    n_cycles: int            # access cycles consumed (energy/timing model)
    n_conversions: int
    # trimmed scores when the op ran with a fused calibration epilogue
    # (``trim=coef``); None on the plain code/volts path
    trimmed: Optional[jnp.ndarray] = None


def _pad_to_conversion(x, p: DimaParams):
    n = x.shape[-1]
    full = p.dims_per_conversion
    if n < full:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, full - n)]
        x = jnp.pad(x, pad)
    return x


def dp_gain(p: DimaParams) -> float:
    """Ideal volts per unit of mean(D·P):  V = mean_j(D_j P_j) · G.

    Two 17s: D's sub-range merge and P's rail merge; 16: the 4-b
    capacitive multiplier's charge division."""
    return fr.word_gain(p) / (16.0 * 17.0)


def md_gain(p: DimaParams) -> float:
    """Ideal volts per unit of mean(|D−P|)."""
    return fr.word_gain(p)


def _cycle_split(x, n_cycles, w):
    """(..., n_cycles·w) -> (..., n_cycles, w); slice [..., c, :] equals the
    seed's per-cycle slice [..., c·w:(c+1)·w]."""
    return x.reshape(x.shape[:-1] + (n_cycles, w))


def _merge_banked(*blocks):
    """The matmat digital merge: per-bank (n_banks, B, rows) blocks ->
    (B, m) in bank-contiguous row order.  Defined ONCE for the host
    fused, pallas and mesh paths — the bitwise host==pallas==mesh parity
    depends on all three merging in the same order."""
    return tuple(x.transpose(1, 0, 2).reshape(x.shape[1], -1)
                 for x in blocks)


def _fold_each(key, idx):
    """fold_in over an index vector -> stacked keys (vmap-invariant, so
    each row equals the seed loop's ``fold_in(key, i)``)."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)


def dima_dot(d_words, p_words, p: DimaParams, chip=None, key=None,
             v_range=None) -> DimaOut:
    """Dot product mode. d_words/p_words: (..., n≤256) ints in [0,255].

    Returns ADC code ≈ mean_j(D_j·P_j)·G mapped onto (v_min, v_max).
    The per-cycle work (two pipelined accesses) is a vmap over the cycle
    axis — one XLA dispatch regardless of batch and cycle count.
    """
    d = _pad_to_conversion(jnp.asarray(d_words, jnp.int32), p)
    q = _pad_to_conversion(jnp.asarray(p_words, jnp.int32), p)
    w = p.words_per_access
    n_cycles = d.shape[-1] // w
    d_c = _cycle_split(d, n_cycles, w)
    q_c = _cycle_split(q, n_cycles, w)

    def cycle(dc, qc, k_read, k_blp, k_col_m, k_col_l):
        msb, lsb = fr.split_words(dc)
        v_word = fr.mr_fr(msb, lsb, p, chip, k_read)
        rm, rl = blp_mod.blp_dp(v_word, qc, p, chip, k_blp)
        return (cblp_mod.column_share(rm, p, k_col_m),
                cblp_mod.column_share(rl, p, k_col_l))

    if key is None:
        rails_m, rails_l = jax.vmap(
            lambda dc, qc: cycle(dc, qc, None, None, None, None),
            in_axes=(-2, -2), out_axes=-1)(d_c, q_c)
    else:
        k0, k1, k2 = _keys(key, 3)
        c = jnp.arange(n_cycles)
        rails_m, rails_l = jax.vmap(
            cycle, in_axes=(-2, -2, 0, 0, 0, 0), out_axes=-1)(
                d_c, q_c, _fold_each(k0, c), _fold_each(k1, c),
                _fold_each(k2, 2 * c), _fold_each(k2, 2 * c + 1))

    v_m = cblp_mod.cycle_share(rails_m, p)
    v_l = cblp_mod.cycle_share(rails_l, p)
    v = cblp_mod.rail_merge(v_m, v_l, p)

    if v_range is None:
        v_range = (0.0, 255.0 * 255.0 * dp_gain(p))
    code = adc_mod.adc(v, v_range[0], v_range[1], p)
    return DimaOut(code, v, n_cycles, 1)


def dima_manhattan(d_words, p_words, p: DimaParams, chip=None, key=None,
                   v_range=None) -> DimaOut:
    """Manhattan-distance mode: replica read develops D + (255−P); the
    comparator/mux takes |·−ref|; CBLP averages."""
    d = _pad_to_conversion(jnp.asarray(d_words, jnp.int32), p)
    q = _pad_to_conversion(jnp.asarray(p_words, jnp.int32), p)
    w = p.words_per_access
    n_cycles = d.shape[-1] // w

    # the comparator reference: both rails at D = P (word value 255 summed)
    v_ref = fr.mr_fr(jnp.full((1,), 15), jnp.full((1,), 15), p, None, None,
                     rep_msb=jnp.zeros((1,), jnp.int32),
                     rep_lsb=jnp.zeros((1,), jnp.int32))[0]
    d_c = _cycle_split(d, n_cycles, w)
    q_c = _cycle_split(q, n_cycles, w)

    def cycle(dc, qc, k_bl, k_blb, k_cmp, k_col):
        msb, lsb = fr.split_words(dc)
        pm, plw = fr.split_words(255 - qc)          # replica stores P̄
        v_bl = fr.mr_fr(msb, lsb, p, chip, k_bl, rep_msb=pm, rep_lsb=plw)
        dm, dl = fr.split_words(255 - dc)           # BLB: complementary cell
        qm, ql = fr.split_words(qc)
        v_blb = fr.mr_fr(dm, dl, p, chip, k_blb, rep_msb=qm, rep_lsb=ql)
        v_abs = blp_mod.blp_md(v_bl, v_blb, v_ref, p, chip, k_cmp)
        return cblp_mod.column_share(v_abs, p, k_col)

    if key is None:
        outs = jax.vmap(lambda dc, qc: cycle(dc, qc, None, None, None, None),
                        in_axes=(-2, -2), out_axes=-1)(d_c, q_c)
    else:
        k0, k1, k2, k3 = _keys(key, 4)
        c = jnp.arange(n_cycles)
        outs = jax.vmap(cycle, in_axes=(-2, -2, 0, 0, 0, 0), out_axes=-1)(
            d_c, q_c, _fold_each(k0, c), _fold_each(k3, c),
            _fold_each(k1, c), _fold_each(k2, c))

    v = cblp_mod.cycle_share(outs, p)
    if v_range is None:
        v_range = (0.0, 255.0 * md_gain(p))
    code = adc_mod.adc(v, v_range[0], v_range[1], p)
    return DimaOut(code, v, n_cycles, 1)


def _cycles_per_op(n, p: DimaParams) -> int:
    return max(n, p.dims_per_conversion) // p.words_per_access


def dima_matvec(d_mat, p_vec, p: DimaParams, chip=None, key=None,
                mode="dp", v_range=None) -> DimaOut:
    """All stored vectors against one query: d_mat (m, n), p_vec (n,).
    Physically: m×(n/128) access cycles on one bank, or m/32 of that in
    the 32-bank scenario — accounted by energy.py, simulated as a vmap.

    One dispatch for the whole matrix; per-row rng keys are derived
    exactly as the seed's per-row loop (``jax.random.split(key, m)``), so
    results are bit-for-bit identical to ``dima_matvec_loop``.
    """
    d_mat = jnp.asarray(d_mat)
    m = d_mat.shape[0]
    f = dima_dot if mode == "dp" else dima_manhattan
    n_cycles = m * _cycles_per_op(d_mat.shape[-1], p)
    if key is None:
        out = f(d_mat, p_vec, p, chip, None, v_range)
        return DimaOut(out.code, out.volts, n_cycles, m)
    keys = jax.random.split(key, m)
    code, volts = jax.vmap(
        lambda row, k: f(row, p_vec, p, chip, k, v_range)[:2])(d_mat, keys)
    return DimaOut(code, volts, n_cycles, m)


def dima_matmat(d_mat, p_mat, p: DimaParams, chip=None, key=None,
                mode="dp", v_range=None):
    """All stored vectors against a query batch: d_mat (m, n), p_mat
    (b, n) -> (code (b, m), volts (b, m)).  Query j draws its key from
    ``jax.random.split(key, b)[j]`` — THE per-query convention every
    backend follows, defined once here so the reference backend, the
    fused multibank path, and the mesh (``shard_map``) path cannot
    drift apart."""
    f = dima_dot if mode == "dp" else dima_manhattan
    if key is None:
        return f(d_mat[None, :, :], p_mat[:, None, :], p, chip, None,
                 v_range)[:2]
    return jax.vmap(
        lambda qj, kj: dima_matvec(d_mat, qj, p, chip, kj, mode,
                                   v_range)[:2])(
        p_mat, jax.random.split(key, p_mat.shape[0]))


def dima_matvec_loop(d_mat, p_vec, p: DimaParams, chip=None, key=None,
                     mode="dp", v_range=None) -> DimaOut:
    """The seed's per-row Python-loop matvec: one traced dima op per
    stored row.  Kept as the reference the vectorized ``dima_matvec`` is
    tested bit-for-bit against, and as the benchmark baseline
    (benchmarks/run.py emits BENCH_dima_api.json comparing the two)."""
    m = d_mat.shape[0]
    keys = (jax.random.split(key, m) if key is not None else [None] * m)
    f = dima_dot if mode == "dp" else dima_manhattan
    outs = [f(d_mat[i], p_vec, p, chip, keys[i], v_range) for i in range(m)]
    code = jnp.stack([o.code for o in outs])
    volts = jnp.stack([o.volts for o in outs])
    return DimaOut(code, volts, sum(o.n_cycles for o in outs),
                   sum(o.n_conversions for o in outs))


# ---------------------------------------------------------------------------
# conventional-architecture digital reference (exact 8-b arithmetic)
# ---------------------------------------------------------------------------

def digital_dot(d_words, p_words):
    d = jnp.asarray(d_words, jnp.int32)
    q = jnp.asarray(p_words, jnp.int32)
    return jnp.sum(d * q, axis=-1)   # ≤ 256·255² < 2³¹


def digital_manhattan(d_words, p_words):
    d = jnp.asarray(d_words, jnp.int32)
    q = jnp.asarray(p_words, jnp.int32)
    return jnp.sum(jnp.abs(d - q), axis=-1)


def code_to_dot(code, p: DimaParams, v_range=None):
    """Decode an ADC code back to dot-product units (for comparisons).
    The CBLP mean is over dims_per_conversion (zero-padded), so the sum
    rescales by that fixed count."""
    if v_range is None:
        v_range = (0.0, 255.0 * 255.0 * dp_gain(p))
    v = adc_mod.dac(code, v_range[0], v_range[1], p)
    return v / dp_gain(p) * p.dims_per_conversion


def code_to_md(code, p: DimaParams, v_range=None):
    if v_range is None:
        v_range = (0.0, 255.0 * md_gain(p))
    v = adc_mod.dac(code, v_range[0], v_range[1], p)
    return v / md_gain(p) * p.dims_per_conversion


def trim_epilogue(code, q_sum, coef, p: DimaParams, v_range=None,
                  mode="dp"):
    """The calibration epilogue as ONE float32 jnp expression:
    decode the ADC code to dot units and apply the affine trim
    ``c₀·d̂ + c₁·Σq + c₂`` (``calibration.affine_trim``'s feature order).

    This is the single definition of the fused-epilogue arithmetic: the
    Pallas kernel bodies (kernels/dima_{dp,md}.py) inline this operation
    order, and the host fused paths call it verbatim.  The ADC *codes*
    stay bitwise identical whether or not the epilogue runs; the f32
    ``trimmed`` value itself may differ by 1-2 ulp of the score scale
    across compilation contexts (XLA fuses/reassociates the chain
    differently per surrounding program — even eager vs jit of this very
    function differ), so cross-substrate comparisons of ``trimmed`` use
    a ~1e-6 relative tolerance, never exact equality.  ``v_range`` is
    cast to float32 up front — the kernels carry it as a f32 operand,
    and a float64 window here would silently break code parity.

    Distinct from ``calibration.apply_trim`` (the float64 numpy oracle
    used when fitting): this is the deployable f32 form whose residual vs
    the oracle is ≤ a few ulp of the score scale."""
    gain = dp_gain(p) if mode == "dp" else md_gain(p)
    if v_range is None:
        full_val = 255.0 * 255.0 if mode == "dp" else 255.0
        v_range = (0.0, full_val * gain)
    vr = jnp.asarray(v_range, jnp.float32)
    v = adc_mod.dac(code, vr[0], vr[1], p)
    dot_hat = v / gain * p.dims_per_conversion
    c = jnp.asarray(coef, jnp.float32)
    q_sum = jnp.asarray(q_sum, jnp.float32)
    return (c[0] * dot_hat + c[1] * q_sum) + c[2]


# ---------------------------------------------------------------------------

def _keys(key, n):
    if key is None:
        return [None] * n
    return list(jax.random.split(key, n))
