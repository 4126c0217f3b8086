"""Pallas kernel: the DIMA DP-mode analog pipeline (MR-FR → BLP capacitive
multiply → CBLP charge share → ADC) for a block of stored rows against a
batch of streamed queries.

This is the *simulation* kernel (used by the banked Monte-Carlo accuracy
studies, where millions of analog ops dominate wall time): the full
transfer-function + mismatch + noise math runs vectorized on (BM, 256)
tiles in VMEM.  Noise is an explicit operand — kernels must be
deterministic — and the jnp oracle is kernels/ref.py::dima_dp_ref.

Grid: (NB, B, M/BM) — banks, queries, stored-row blocks
(kernels/_banked.py), so a banked matmat is ONE kernel launch, a
single-bank matmat is NB=1 and a matvec is B=1 (``dima_dp``).  Lane
layout: the 128 columns of one access cycle sit on the 128-lane axis;
the two sub-range cycles stack on sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import pipeline as pipeline_mod
from repro.core.params import DimaParams
from repro.kernels._banked import BM, banked_call


def _make_kernel(p: DimaParams, trim: bool = False):
    def kernel(d_ref, q_ref, cg_ref, ce_ref, mg_ref, mo_ref, rn_ref,
               cn_ref, vr_ref, *rest):
        if trim:
            ep_ref, code_ref, volt_ref, trim_ref = rest
        else:
            code_ref, volt_ref = rest
        d = d_ref[...].astype(jnp.int32).reshape(BM, 2, 128)
        q = q_ref[...].astype(jnp.int32).reshape(2, 128)

        # MR-FR: PWM transfer per 4-b sub-word + 16:1 sub-range merge
        m = ((d >> 4) & 0xF).astype(jnp.float32)
        l = (d & 0xF).astype(jnp.float32)
        vm = p.delta_v_lsb * m * (1.0 - p.inl_beta * m)
        vl = p.delta_v_lsb * l * (1.0 - p.inl_beta * l)
        r = 16.0 * (1.0 + ce_ref[...])              # trim-cap ratio error
        v_word = (r * vm + vl) / (r + 1.0)
        v_word = v_word * cg_ref[...] + rn_ref[...].reshape(BM, 2, 128)

        # BLP: two parallel 4-b capacitive multipliers (P sub-ranged)
        pm = ((q >> 4) & 0xF).astype(jnp.float32)
        plo = (q & 0xF).astype(jnp.float32)
        mg = mg_ref[...]
        mo = mo_ref[...]
        rail_m = v_word * (pm / 16.0) * (1.0 - p.mult_beta * pm) * mg[0] \
            + mo[0] * (pm > 0)
        rail_l = v_word * (plo / 16.0) * (1.0 - p.mult_beta * plo) * mg[1] \
            + mo[1] * (plo > 0)

        # CBLP: column charge-share (mean), cycle merge, 16:1 rail merge
        cn = cn_ref[...].reshape(BM, 2, 2)
        vmr = jnp.mean(rail_m, axis=2) + cn[:, :, 0]  # (BM, 2)
        vlr = jnp.mean(rail_l, axis=2) + cn[:, :, 1]
        v = (16.0 * jnp.mean(vmr, axis=1) + jnp.mean(vlr, axis=1)) / 17.0

        # ADC (8-b single-slope); codes land in the (1, BM) output block
        vr = vr_ref[...]
        full = float(2 ** p.adc_bits - 1)
        x = (v - vr[0, 0]) / jnp.maximum(vr[0, 1] - vr[0, 0], 1e-9)
        code = jnp.clip(jnp.round(x * full), 0, full).astype(jnp.int32)
        code_ref[...] = code.reshape(code_ref.shape)
        volt_ref[...] = v.reshape(volt_ref.shape)
        if trim:
            # fused calibration epilogue — same operation order as
            # pipeline.trim_epilogue (dac -> dot units -> affine trim);
            # codes stay bitwise, the f32 trimmed value agrees with the
            # host helper to ~1 ulp of the score scale (XLA reassociates
            # per compilation context).  ep row: [c0, c1, c2, Σq].
            ep = ep_ref[...]
            vd = vr[0, 0] + code.astype(jnp.float32) / full \
                * (vr[0, 1] - vr[0, 0])
            dot_hat = vd / pipeline_mod.dp_gain(p) * p.dims_per_conversion
            trimmed = (ep[0, 0] * dot_hat + ep[0, 1] * ep[0, 3]) + ep[0, 2]
            trim_ref[...] = trimmed.reshape(trim_ref.shape)

    return kernel


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def dima_dp_bank_batch(d, qs, col_gain, cap_eps, mult_gain, mult_off,
                       read_noise, cblp_noise, v_range, ep=None, *,
                       params: DimaParams = DimaParams(), interpret=None):
    """Bank-leading grid: d (NB, M, 256) uint8 — one multibank shard per
    leading index; qs (B, 256) uint8; chip arrays (…,128); read_noise
    (NB, B, M, 2, 128); cblp_noise (NB, B, M, 2, 2); v_range (NB, 2) —
    one ADC window per bank (equal rows ≡ one shared window; distinct
    rows serve the bitserial per-plane calibrated windows).  Returns
    (codes (NB, B, M) int32, volts (NB, B, M) f32) from ONE kernel
    launch over a (NB, B, M/BM) grid.

    ``ep`` (B,4) f32 rows ``[c0, c1, c2, Σq_b]`` switch on the fused
    calibration epilogue: a third output ``trimmed`` (NB, B, M) f32 is
    appended, computed in-kernel as ``pipeline.trim_epilogue``."""
    trim = ep is not None
    consts = [col_gain.reshape(1, 128), cap_eps.reshape(1, 128),
              mult_gain, mult_off]
    return banked_call(_make_kernel(params, trim), d, qs, consts,
                       [read_noise, cblp_noise], v_range, ep,
                       3 if trim else 2, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def dima_dp_batch(d, qs, col_gain, cap_eps, mult_gain, mult_off, read_noise,
                  cblp_noise, v_range, ep=None, *,
                  params: DimaParams = DimaParams(), interpret=None):
    """One bank: d (M,256); qs (B,256); read_noise (B,M,2,128);
    cblp_noise (B,M,2,2); v_range (1,2).  Returns (codes (B,M), volts
    (B,M)[, trimmed (B,M)]) — ``dima_dp_bank_batch`` at NB=1."""
    out = dima_dp_bank_batch(
        d[None], qs, col_gain, cap_eps, mult_gain, mult_off,
        read_noise[None], cblp_noise[None], v_range, ep, params=params,
        interpret=interpret)
    return tuple(o[0] for o in out)


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def dima_dp(d, q, col_gain, cap_eps, mult_gain, mult_off, read_noise,
            cblp_noise, v_range, ep=None, *,
            params: DimaParams = DimaParams(), interpret=None):
    """d (M,256) uint8; q (256,) uint8; chip arrays (…,128); read_noise
    (M,2,128); cblp_noise (M,2,2); v_range (1,2) f32.
    Returns (codes (M,) int32, volts (M,) f32).  B=1 of ``dima_dp_batch``;
    with ``ep`` (1,4) a third ``trimmed`` (M,) output is appended."""
    out = dima_dp_batch(
        d, q.reshape(1, 256), col_gain, cap_eps, mult_gain, mult_off,
        read_noise[None], cblp_noise[None], v_range, ep, params=params,
        interpret=interpret)
    return tuple(o[0] for o in out)
