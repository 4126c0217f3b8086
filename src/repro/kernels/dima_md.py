"""Pallas kernel: DIMA MD-mode (Manhattan distance) analog pipeline.

Dual-rail functional read — BL develops f(D + P̄), BLB the complementary
f(D̄ + P) — comparator + mux pick the deeper swing, CBLP averages, ADC
converts.  Oracle: kernels/ref.py::dima_md_ref.

Grid: (NB, B, M/BM) like dima_dp.py (kernels/_banked.py) — a banked
matmat in one launch, a single bank is NB=1 and a matvec is B=1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import pipeline as pipeline_mod
from repro.core.params import DimaParams
from repro.kernels._banked import BM, banked_call


def _transfer(c, p, beta):
    return p.delta_v_lsb * c * (1.0 - beta * c)


def _make_kernel(p: DimaParams, trim: bool = False):
    beta = p.md_inl_beta

    def kernel(d_ref, q_ref, cg_ref, ce_ref, cmp_ref, rn_ref, rnb_ref,
               cn_ref, vr_ref, *rest):
        if trim:
            ep_ref, code_ref, volt_ref, trim_ref = rest
        else:
            code_ref, volt_ref = rest
        d = d_ref[...].astype(jnp.int32).reshape(BM, 2, 128)
        q = q_ref[...].astype(jnp.int32).reshape(2, 128)
        cg = cg_ref[...]
        r = 16.0 * (1.0 + ce_ref[...])

        def read(words, rep, noise):
            m = ((words >> 4) & 0xF).astype(jnp.float32) \
                + ((rep >> 4) & 0xF).astype(jnp.float32)
            l = (words & 0xF).astype(jnp.float32) \
                + (rep & 0xF).astype(jnp.float32)
            vm = _transfer(m, p, beta)
            vl = _transfer(l, p, beta)
            return ((r * vm + vl) / (r + 1.0)) * cg + noise

        v_bl = read(d, 255 - q, rn_ref[...].reshape(BM, 2, 128))   # f(D + P̄)
        v_blb = read(255 - d, q, rnb_ref[...].reshape(BM, 2, 128))  # f(D̄ + P)
        vref = (16.0 * _transfer(jnp.float32(15.0), p, beta)
                + _transfer(jnp.float32(15.0), p, beta)) / 17.0
        pick = (v_bl + cmp_ref[...].reshape(BM, 2, 128)) >= v_blb
        v_abs = jnp.maximum(jnp.where(pick, v_bl, v_blb) - vref, 0.0)

        v = jnp.mean(v_abs, axis=2) + cn_ref[...].reshape(BM, 2)
        v = jnp.mean(v, axis=1)

        vr = vr_ref[...]
        full = float(2 ** p.adc_bits - 1)
        x = (v - vr[0, 0]) / jnp.maximum(vr[0, 1] - vr[0, 0], 1e-9)
        code = jnp.clip(jnp.round(x * full), 0, full).astype(jnp.int32)
        code_ref[...] = code.reshape(code_ref.shape)
        volt_ref[...] = v.reshape(volt_ref.shape)
        if trim:
            # fused calibration epilogue — mirrors pipeline.trim_epilogue
            # (mode="md") operation-for-operation; ep row: [c0, c1, c2, Σq]
            ep = ep_ref[...]
            vd = vr[0, 0] + code.astype(jnp.float32) / full \
                * (vr[0, 1] - vr[0, 0])
            dot_hat = vd / pipeline_mod.md_gain(p) * p.dims_per_conversion
            trimmed = (ep[0, 0] * dot_hat + ep[0, 1] * ep[0, 3]) + ep[0, 2]
            trim_ref[...] = trimmed.reshape(trim_ref.shape)

    return kernel


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def dima_md_bank_batch(d, qs, col_gain, cap_eps, cmp_noise, read_noise,
                       read_noise_b, cblp_noise, v_range, ep=None, *,
                       params: DimaParams = DimaParams(), interpret=None):
    """Bank-leading grid: d (NB, M, 256) — one multibank shard per
    leading index; qs (B, 256); cmp/read noise (NB, B, M, 2, 128); cblp
    (NB, B, M, 2); v_range (NB, 2) — one ADC window per bank.  Returns
    (codes (NB, B, M), volts (NB, B, M)) from ONE kernel launch over a
    (NB, B, M/BM) grid; ``ep`` (B,4) appends a fused-trim third output
    (see ``dima_dp.dima_dp_bank_batch``)."""
    trim = ep is not None
    consts = [col_gain.reshape(1, 128), cap_eps.reshape(1, 128)]
    return banked_call(_make_kernel(params, trim), d, qs, consts,
                       [cmp_noise, read_noise, read_noise_b, cblp_noise],
                       v_range, ep, 3 if trim else 2, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def dima_md_batch(d, qs, col_gain, cap_eps, cmp_noise, read_noise,
                  read_noise_b, cblp_noise, v_range, ep=None, *,
                  params: DimaParams = DimaParams(), interpret=None):
    """One bank: d (M,256); qs (B,256); cmp/read noise (B,M,2,128); cblp
    (B,M,2); v_range (1,2).  Returns (codes (B,M), volts (B,M)[, trimmed
    (B,M)]) — ``dima_md_bank_batch`` at NB=1."""
    out = dima_md_bank_batch(
        d[None], qs, col_gain, cap_eps, cmp_noise[None], read_noise[None],
        read_noise_b[None], cblp_noise[None], v_range, ep, params=params,
        interpret=interpret)
    return tuple(o[0] for o in out)


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def dima_md(d, q, col_gain, cap_eps, cmp_noise, read_noise, read_noise_b,
            cblp_noise, v_range, ep=None, *,
            params: DimaParams = DimaParams(), interpret=None):
    """d (M,256) uint8; q (256,); cmp/read noise (M,2,128); cblp (M,2);
    v_range (1,2).  Returns (codes (M,), volts (M,)).  B=1 of
    ``dima_md_batch``; with ``ep`` (1,4) a third ``trimmed`` (M,) output
    is appended."""
    out = dima_md_batch(
        d, q.reshape(1, 256), col_gain, cap_eps, cmp_noise[None],
        read_noise[None], read_noise_b[None], cblp_noise[None], v_range,
        ep, params=params, interpret=interpret)
    return tuple(o[0] for o in out)
