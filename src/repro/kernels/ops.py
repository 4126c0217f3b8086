"""Public jit'd wrappers around the Pallas kernels: padding to tile
multiples, activation quantization, GQA head-folding, chip-record /
key-based noise expansion — so callers never see BlockSpec details.

On CPU (this container) kernels run in interpret mode; on TPU they lower
natively.  Every op has a jnp oracle in ref.py and an allclose test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pipeline as pl
from repro.core.params import DimaParams
from repro.kernels import ref as ref_mod
from repro.kernels.dima_dp import dima_dp as _dima_dp_kernel
from repro.kernels.dima_dp import dima_dp_bank_batch as _dima_dp_bank_kernel
from repro.kernels.dima_dp import dima_dp_batch as _dima_dp_batch_kernel
from repro.kernels.dima_md import dima_md as _dima_md_kernel
from repro.kernels.dima_md import dima_md_bank_batch as _dima_md_bank_kernel
from repro.kernels.dima_md import dima_md_batch as _dima_md_batch_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.subrange_matmul import subrange_matmul as _subrange_kernel


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def subrange_matmul(x, w_rec, *, interpret=None):
    """x: (..., K) float; w_rec from quant.subrange.quantize_weight (w8).
    Quantizes activations per-row to int8 and runs the w8a8 kernel."""
    assert "q" in w_rec, "kernel path is w8 (two 4-b planes)"
    orig_shape = x.shape
    K = x.shape[-1]
    N = w_rec["q"].shape[-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    xq, xs = ref_mod.quantize_act_ref(x2)
    xq = _pad_to(_pad_to(xq, 128, 0), 128, 1)
    xs = _pad_to(xs, 128, 0)
    wq = _pad_to(_pad_to(w_rec["q"], 128, 0), 128, 1)
    ws = _pad_to(w_rec["scale"].reshape(1, N), 128, 1)
    y = _subrange_kernel(xq, xs, wq, ws, interpret=interpret)
    return y[:M, :N].reshape(*orig_shape[:-1], N).astype(x.dtype)


def _expand_noise(key, p: DimaParams, M, kind):
    """Per-read dynamic noise arrays for the analog kernels."""
    if key is None:
        z = lambda *s: jnp.zeros(s, jnp.float32)
        if kind == "dp":
            return z(M, 2, 128), z(M, 2, 2)
        return z(M, 2, 128), z(M, 2, 128), z(M, 2, 128), z(M, 2)
    ks = jax.random.split(key, 4)
    rd = p.sigma_read_mv * 1e-3
    cb = p.sigma_cblp_mv * 1e-3
    if kind == "dp":
        return (rd * jax.random.normal(ks[0], (M, 2, 128)),
                cb * jax.random.normal(ks[1], (M, 2, 2)))
    cm = p.sigma_cmp_off_mv * 1e-3
    return (cm * jax.random.normal(ks[0], (M, 2, 128)),
            rd * jax.random.normal(ks[1], (M, 2, 128)),
            rd * jax.random.normal(ks[2], (M, 2, 128)),
            cb * jax.random.normal(ks[3], (M, 2)))


def _chip_arrays(chip, p: DimaParams):
    if chip is None:
        return (jnp.ones((128,)), jnp.zeros((128,)),
                jnp.ones((2, 128)), jnp.zeros((2, 128)))
    return (chip["col_gain"], chip["cap_ratio_err"],
            chip["mult_gain"], chip["mult_off"])


def _trim_ep(trim, qs):
    """Pack the fused-epilogue kernel operand from a trim coefficient
    triple and the (possibly padded) query batch: (B, 4) f32 rows
    ``[c0, c1, c2, Σq_b]``.  The query sum is exact in float32 (≤
    256·255 < 2²⁴) and zero padding cannot change it, so it equals the
    host epilogue's ``q_sum`` feature bit-for-bit."""
    if trim is None:
        return None
    qs = jnp.asarray(qs)
    qsum = qs.astype(jnp.float32).sum(-1)                     # (B,)
    c = jnp.asarray(trim, jnp.float32).reshape(3)
    return jnp.concatenate(
        [jnp.broadcast_to(c, (qsum.shape[0], 3)), qsum[:, None]], axis=1)


def dima_dp_banked(d, q, p: DimaParams = DimaParams(), chip=None, key=None,
                   v_range=None, interpret=None, trim=None):
    """Banked DP: d (M,256) uint8 rows vs one query q (256,).
    Returns (codes, volts), M padded internally to 128; with
    ``trim=(c0,c1,c2)`` the fused epilogue appends trimmed scores."""
    M = d.shape[0]
    dp_ = _pad_to(jnp.asarray(d, jnp.uint8), 128, 0)
    Mp = dp_.shape[0]
    cg, ce, mg, mo = _chip_arrays(chip, p)
    rn, cn = _expand_noise(key, p, Mp, "dp")
    if v_range is None:
        from repro.core.pipeline import dp_gain
        v_range = (0.0, 255.0 * 255.0 * dp_gain(p))
    vr = jnp.asarray([v_range], jnp.float32)
    q8 = jnp.asarray(q, jnp.uint8)
    out = _dima_dp_kernel(dp_, q8, cg, ce, mg, mo, rn, cn, vr,
                          _trim_ep(trim, q8.reshape(1, -1)), params=p,
                          interpret=interpret)
    return tuple(o[:M] for o in out)


def dima_md_banked(d, q, p: DimaParams = DimaParams(), chip=None, key=None,
                   v_range=None, interpret=None, trim=None):
    """Banked MD: d (M,256) rows vs one query. Returns (codes, volts);
    ``trim`` appends fused trimmed scores."""
    M = d.shape[0]
    dp_ = _pad_to(jnp.asarray(d, jnp.uint8), 128, 0)
    Mp = dp_.shape[0]
    cg, ce, mg, mo = _chip_arrays(chip, p)
    cmp_n, rn, rnb, cn = _expand_noise(key, p, Mp, "md")
    if v_range is None:
        from repro.core.pipeline import md_gain
        v_range = (0.0, 255.0 * md_gain(p))
    vr = jnp.asarray([v_range], jnp.float32)
    q8 = jnp.asarray(q, jnp.uint8)
    out = _dima_md_kernel(dp_, q8, cg, ce, cmp_n, rn, rnb, cn, vr,
                          _trim_ep(trim, q8.reshape(1, -1)), params=p,
                          interpret=interpret)
    return tuple(o[:M] for o in out)


def _batch_noise(key, p: DimaParams, B, Mp, kind):
    """Per-query noise stacks for the query-batched kernels: query j draws
    from ``jax.random.split(key, B)[j]`` — the same per-query key layout
    as the reference backend's matmat."""
    if key is None:
        return tuple(jnp.zeros((B,) + a.shape, a.dtype)
                     for a in _expand_noise(None, p, Mp, kind))
    keys = jax.random.split(key, B)
    return jax.vmap(lambda k: _expand_noise(k, p, Mp, kind))(keys)


def dima_dp_matmat(d, qs, p: DimaParams = DimaParams(), chip=None, key=None,
                   v_range=None, interpret=None, trim=None):
    """Query-batched DP: d (M,256) uint8 rows vs queries qs (B,256).
    Returns (codes (B,M), volts (B,M)) from ONE kernel launch — the grid
    is (1, B, M/128), so the per-query Python loop disappears.  ``trim``
    appends fused trimmed scores (B,M)."""
    M = d.shape[0]
    B = qs.shape[0]
    dp_ = _pad_to(jnp.asarray(d, jnp.uint8), 128, 0)
    Mp = dp_.shape[0]
    cg, ce, mg, mo = _chip_arrays(chip, p)
    rn, cn = _batch_noise(key, p, B, Mp, "dp")
    if v_range is None:
        from repro.core.pipeline import dp_gain
        v_range = (0.0, 255.0 * 255.0 * dp_gain(p))
    vr = jnp.asarray([v_range], jnp.float32)
    qs8 = jnp.asarray(qs, jnp.uint8)
    out = _dima_dp_batch_kernel(dp_, qs8, cg, ce, mg, mo, rn, cn, vr,
                                _trim_ep(trim, qs8), params=p,
                                interpret=interpret)
    return tuple(o[:, :M] for o in out)


def dima_md_matmat(d, qs, p: DimaParams = DimaParams(), chip=None, key=None,
                   v_range=None, interpret=None, trim=None):
    """Query-batched MD: d (M,256) rows vs queries qs (B,256).
    Returns (codes (B,M), volts (B,M)) from one kernel launch; ``trim``
    appends fused trimmed scores."""
    M = d.shape[0]
    B = qs.shape[0]
    dp_ = _pad_to(jnp.asarray(d, jnp.uint8), 128, 0)
    Mp = dp_.shape[0]
    cg, ce, mg, mo = _chip_arrays(chip, p)
    cmp_n, rn, rnb, cn = _batch_noise(key, p, B, Mp, "md")
    if v_range is None:
        from repro.core.pipeline import md_gain
        v_range = (0.0, 255.0 * md_gain(p))
    vr = jnp.asarray([v_range], jnp.float32)
    qs8 = jnp.asarray(qs, jnp.uint8)
    out = _dima_md_batch_kernel(dp_, qs8, cg, ce, cmp_n, rn, rnb, cn, vr,
                                _trim_ep(trim, qs8), params=p,
                                interpret=interpret)
    return tuple(o[:, :M] for o in out)


# ---------------------------------------------------------------------------
# bank-fused wrappers: the multibank backend's full banks as ONE launch
# ---------------------------------------------------------------------------

def _stack_bank_noise(key, p: DimaParams, NB, Mp, kind, B=None, offset=0):
    """Per-bank noise stacks for the bank-leading kernels: bank ``b``
    draws from ``fold_in(key, offset + b)`` — the multibank key
    convention — with the per-bank layout of ``_expand_noise`` (matvec,
    ``B=None``) or ``_batch_noise`` (matmat), so the fused launch is
    bitwise equal to per-bank ``dima_*_banked`` / ``dima_*_matmat``
    launches.  ``offset`` may be a traced scalar: the mesh path passes
    each shard's global first-bank index so fold_in streams match the
    host path bank-for-bank."""
    one = ((lambda k: _expand_noise(k, p, Mp, kind)) if B is None
           else (lambda k: _batch_noise(k, p, B, Mp, kind)))
    if key is None:
        return tuple(jnp.zeros((NB,) + a.shape, a.dtype) for a in one(None))
    return jax.vmap(one)(pl._fold_each(key, offset + jnp.arange(NB)))


def _bank_operands(d, q_or_qs, p, chip, v_range, mode, matvec, trim,
                   offset, rows_per):
    """Traced: the kernel operands from the caller's raw ones, ``(d, qs,
    consts, vr, ep, offset)``.  ``d`` is either the stored rows (m, n),
    split here into ``m // rows_per`` full banks, or banks already
    stacked (NB, M, n).  Rows and queries are padded to one conversion
    and cast to u8, each bank's rows padded to the 128-row block; then
    the chip arrays the mode's kernel reads, the per-bank ADC windows
    (a shared (lo, hi) or a per-bank (NB, 2)), the fused-trim operand
    and the first bank's global index."""
    d = pl._pad_to_conversion(d.astype(jnp.int32), p).astype(jnp.uint8)
    if d.ndim == 2:
        n_full = d.shape[0] // rows_per
        d = d[:n_full * rows_per].reshape(n_full, rows_per, d.shape[-1])
    NB = d.shape[0]
    dp_ = _pad_to(d, 128, 1)
    cg, ce, mg, mo = _chip_arrays(chip, p)
    if v_range is None:
        v_range = ((0.0, 255.0 * 255.0 * pl.dp_gain(p)) if mode == "dp"
                   else (0.0, 255.0 * pl.md_gain(p)))
    vr = jnp.asarray(v_range, jnp.float32)
    if vr.ndim == 1:
        vr = vr[None]
    if vr.shape[0] != NB:                  # shared window -> one row/bank
        vr = jnp.broadcast_to(vr, (NB, 2))
    qs = pl._pad_to_conversion(q_or_qs.astype(jnp.int32),
                               p).astype(jnp.uint8)
    qs2 = qs.reshape(1, -1) if matvec else qs
    consts = (cg, ce, mg, mo) if mode == "dp" else (cg, ce)
    return (dp_, qs2, consts, vr, _trim_ep(trim, qs2),
            jnp.asarray(offset, jnp.int32))


def _bank_unpad(out, M, matvec):
    """Traced: drop the padding rows, (NB, M) for a matvec, (NB, B, M)
    else."""
    if matvec:
        return tuple(o[:, 0, :M] for o in out)
    return tuple(o[:, :, :M] for o in out)


@functools.partial(jax.jit, static_argnames=(
    "params", "interpret", "mode", "matvec", "rows_per"))
def _bank_call(d, q_or_qs, chip, key, v_range, trim, offset, *,
               params: DimaParams, interpret, mode, matvec, rows_per=None):
    """The banked step as ONE execution: operands, noise stacks, the
    (NB, B, M/128) kernel grid and the unpad.  Stored rows (m, n) come
    back merged in row order, (m_full,) or (B, m_full); stacked banks
    (NB, M, n) come back per bank, (NB, M) or (NB, B, M)."""
    rows = d.ndim == 2
    M = rows_per if rows else d.shape[1]
    d, qs, consts, vr, ep, offset = _bank_operands(
        d, q_or_qs, params, chip, v_range, mode, matvec, trim, offset,
        rows_per)
    NB, Mp = d.shape[0], d.shape[1]
    with jax.named_scope("dima.noise"):
        noise = _stack_bank_noise(key, params, NB, Mp, mode,
                                  B=None if matvec else qs.shape[0],
                                  offset=offset)
        if matvec:
            noise = tuple(a[:, None] for a in noise)
    kernel = _dima_dp_bank_kernel if mode == "dp" else _dima_md_bank_kernel
    with jax.named_scope("dima.kernel"):
        out = kernel(d, qs, *consts, *noise, vr, ep, params=params,
                     interpret=interpret)
    out = _bank_unpad(out, M, matvec)
    if not rows:
        return out
    if matvec:                              # (NB, rows) -> (m_full,)
        return tuple(o.reshape(-1) for o in out)
    return pl._merge_banked(*out)           # (NB, B, rows) -> (B, m_full)


def _bank_fused(d, q_or_qs, p, chip, key, v_range, interpret, mode, matvec,
                trim=None, bank_offset=0):
    """The public banked and plane wrappers' common entry: the
    stacked banks through ``_bank_call``, so a fused banked op is a
    single execution with per-bank outputs.

    ``v_range`` may be a shared (lo, hi) window or a per-bank (NB, 2)
    array (the bitserial per-plane calibrated windows); ``trim`` a
    coefficient triple switching on the in-kernel calibration epilogue;
    ``bank_offset`` the global index of bank 0 for the fold_in streams
    (the mesh path's shard start, possibly traced)."""
    return _bank_call(d, q_or_qs, chip, key, v_range, trim, bank_offset,
                      params=p, interpret=interpret, mode=mode,
                      matvec=matvec)


def dima_dp_bank_matvec(d, q, p: DimaParams = DimaParams(), chip=None,
                        key=None, v_range=None, interpret=None, trim=None,
                        bank_offset=0):
    """Banked fused DP matvec: d (NB, M, 256) uint8 — the multibank
    backend's stacked full banks — vs one query q (256,).  Bank ``b``
    draws noise from ``fold_in(key, bank_offset + b)`` with the
    ``dima_dp_banked`` layout.  Returns (codes (NB, M), volts (NB, M))
    from ONE launch; ``trim`` appends fused trimmed scores (NB, M)."""
    return _bank_fused(d, q, p, chip, key, v_range, interpret, "dp", True,
                       trim, bank_offset)


def dima_md_bank_matvec(d, q, p: DimaParams = DimaParams(), chip=None,
                        key=None, v_range=None, interpret=None, trim=None,
                        bank_offset=0):
    """Banked fused MD matvec (see ``dima_dp_bank_matvec``)."""
    return _bank_fused(d, q, p, chip, key, v_range, interpret, "md", True,
                       trim, bank_offset)


def dima_dp_bank_matmat(d, qs, p: DimaParams = DimaParams(), chip=None,
                        key=None, v_range=None, interpret=None, trim=None,
                        bank_offset=0):
    """Banked fused DP matmat: d (NB, M, 256) vs queries qs (B, 256);
    bank ``b`` uses the ``dima_dp_matmat`` noise layout seeded with
    ``fold_in(key, bank_offset + b)``.  Returns (codes (NB, B, M),
    volts) from ONE (NB, B, M/128)-grid launch; ``trim`` appends fused
    trimmed scores."""
    return _bank_fused(d, qs, p, chip, key, v_range, interpret, "dp", False,
                       trim, bank_offset)


def dima_md_bank_matmat(d, qs, p: DimaParams = DimaParams(), chip=None,
                        key=None, v_range=None, interpret=None, trim=None,
                        bank_offset=0):
    """Banked fused MD matmat (see ``dima_dp_bank_matmat``)."""
    return _bank_fused(d, qs, p, chip, key, v_range, interpret, "md", False,
                       trim, bank_offset)


# ---------------------------------------------------------------------------
# plane-fused wrappers: the bitserial backend's bit planes as ONE launch
# ---------------------------------------------------------------------------
#
# A bit-plane stack from ``quant.bitplanes.split_planes`` has exactly the
# layout of the multibank backend's stacked full banks — (B, M, 256)
# uint8 with an independent leading axis — so the *physical* per-plane
# readout rides the existing bank-leading kernel grids unchanged: plane
# ``k`` takes the slot (and the ``fold_in(key, k)`` noise stream) bank
# ``k`` would.  One launch for all planes; the shifted digital accumulate
# happens in the caller (``BitSerialBackend(physical=True)``), exactly
# like the multibank digital code merge.

def dima_dp_plane_matvec(planes, q, p: DimaParams = DimaParams(), chip=None,
                         key=None, v_range=None, interpret=None):
    """Plane-fused DP matvec: planes (B, M, 256) uint8 bit planes vs one
    query q (256,).  Plane ``k`` draws noise from ``fold_in(key, k)``.
    Returns (codes (B, M), volts (B, M)) from ONE launch.  Pass a
    ``calibration.plane_v_range`` window — the full-scale default wastes
    the code space on narrow planes — or a per-plane (B, 2) array of
    calibrated windows (``calibration.calibrate_plane_range``)."""
    return _bank_fused(planes, q, p, chip, key, v_range, interpret,
                       "dp", True)


def dima_dp_plane_matmat(planes, qs, p: DimaParams = DimaParams(), chip=None,
                         key=None, v_range=None, interpret=None):
    """Plane-fused DP matmat: planes (B, M, 256) vs queries qs (b, 256);
    returns (codes (B, b, M), volts) from ONE (B, b, M/128)-grid
    launch (see ``dima_dp_plane_matvec``)."""
    return _bank_fused(planes, qs, p, chip, key, v_range, interpret,
                       "dp", False)


def flash_attention_gqa(q, k, v, *, interpret=None):
    """q: (B, S, H, dh); k, v: (B, S, KV, dh); causal.
    Folds (B, groups) onto the kernel batch axis."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, dh)
    of = _flash_kernel(qf, kf, vf, interpret=interpret)
    return of.reshape(B, H, S, dh).transpose(0, 2, 1, 3)
