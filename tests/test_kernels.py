"""Pallas kernels vs jnp oracles (interpret mode): shape/dtype sweeps +
equivalence with the core analog pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from _parity import assert_outs_equal
from repro.core import noise as noise_mod
from repro.core import pipeline as pl_core
from repro.core.params import DimaParams
from repro.kernels import (dima_dp_banked, dima_md_banked,
                           flash_attention_gqa, subrange_matmul)
from repro.kernels import ref as R
from repro.kernels.subrange_matmul import subrange_matmul as raw_subrange
from repro.quant import quantize_weight

P = DimaParams()


# ---------------------------------------------------------------------------
# sub-ranged w8a8 matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 384, 128),
                                   (128, 512, 256)])
def test_subrange_kernel_vs_ref(M, K, N):
    rng = np.random.default_rng(M + K + N)
    xq = jnp.asarray(rng.integers(-127, 128, (M, K)), jnp.int8)
    xs = jnp.asarray(rng.uniform(0.001, 0.02, (M, 1)), jnp.float32)
    wq = jnp.asarray(rng.integers(0, 256, (K, N)), jnp.uint8)
    ws = jnp.asarray(rng.uniform(0.001, 0.01, (1, N)), jnp.float32)
    y_ref = R.subrange_matmul_ref(xq, xs, wq, ws)
    y_ker = raw_subrange(xq, xs, wq, ws)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape", [(8, 100), (130, 96), (1, 300)])
def test_subrange_wrapper_padding(shape):
    """Non-128-multiple shapes pad correctly through the public wrapper."""
    rng = np.random.default_rng(0)
    M, K = shape
    N = 72
    x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.1, (K, N)), jnp.float32)
    rec = quantize_weight(w)
    y = subrange_matmul(x, rec)
    from repro.quant import dequantize_weight, subrange_matmul_jnp
    y_jnp = subrange_matmul_jnp(x, rec)
    # kernel also quantizes activations (a8): compare against fp within a8 err
    ref = x @ dequantize_weight(rec)
    scale = float(jnp.abs(ref).max()) + 1e-9
    assert float(jnp.abs(y - ref).max()) / scale < 0.03
    assert y.shape == y_jnp.shape == (M, N)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_subrange_kernel_property(seed):
    rng = np.random.default_rng(seed)
    xq = jnp.asarray(rng.integers(-127, 128, (128, 128)), jnp.int8)
    xs = jnp.ones((128, 1), jnp.float32)
    wq = jnp.asarray(rng.integers(0, 256, (128, 128)), jnp.uint8)
    ws = jnp.ones((1, 128), jnp.float32)
    y = raw_subrange(xq, xs, wq, ws)
    # exact integer identity vs int32 matmul on dequantized weights
    exact = (xq.astype(jnp.int32) @ (wq.astype(jnp.int32) - 128))
    np.testing.assert_array_equal(np.asarray(y, np.int64),
                                  np.asarray(exact, np.int64))


# ---------------------------------------------------------------------------
# DIMA analog kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M", [64, 128, 200])
def test_dima_dp_kernel_matches_core(M):
    rng = np.random.default_rng(M)
    D = jnp.asarray(rng.integers(0, 256, (M, 256)), jnp.uint8)
    Q = jnp.asarray(rng.integers(0, 256, (256,)), jnp.uint8)
    out = pl_core.dima_dot(D.astype(jnp.int32), Q.astype(jnp.int32), P)
    assert_outs_equal(dima_dp_banked(D, Q, P), out, volts_atol=1e-7,
                      label="dp kernel vs core")


@pytest.mark.parametrize("M", [64, 128])
def test_dima_md_kernel_matches_core(M):
    rng = np.random.default_rng(M + 1)
    D = jnp.asarray(rng.integers(0, 256, (M, 256)), jnp.uint8)
    Q = jnp.asarray(rng.integers(0, 256, (256,)), jnp.uint8)
    out = pl_core.dima_manhattan(D.astype(jnp.int32), Q.astype(jnp.int32), P)
    assert_outs_equal(dima_md_banked(D, Q, P), out, volts_atol=1e-7,
                      label="md kernel vs core")


def test_dima_dp_kernel_noisy_vs_ref():
    """With chip mismatch + explicit noise: kernel == ref bitwise-ish."""
    from repro.kernels.ops import _chip_arrays, _expand_noise, _pad_to
    rng = np.random.default_rng(5)
    D = jnp.asarray(rng.integers(0, 256, (128, 256)), jnp.uint8)
    Q = jnp.asarray(rng.integers(0, 256, (256,)), jnp.uint8)
    chip = noise_mod.sample_chip(jax.random.PRNGKey(3), P)
    key = jax.random.PRNGKey(9)
    codes_k, volts_k = dima_dp_banked(D, Q, P, chip, key)
    cg, ce, mg, mo = _chip_arrays(chip, P)
    rn, cn = _expand_noise(key, P, 128, "dp")
    vr = (0.0, 255.0 * 255.0 * pl_core.dp_gain(P))
    codes_r, volts_r = R.dima_dp_ref(D, Q, P, cg, ce, mg, mo, rn, cn, vr)
    assert_outs_equal((codes_k, volts_k), (codes_r, volts_r),
                      volts_atol=1e-7, label="noisy kernel vs ref")


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,dh,H,KV,dtype", [
    (128, 64, 2, 1, jnp.float32),
    (256, 128, 4, 2, jnp.float32),
    (256, 64, 4, 4, jnp.bfloat16),
])
def test_flash_attention_sweep(S, dh, H, KV, dtype):
    rng = np.random.default_rng(S + dh)
    B = 2
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, dh)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (B, S, KV, dh)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (B, S, KV, dh)), dtype)
    o = flash_attention_gqa(q, k, v)
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, dh)
    o_ref = R.flash_attention_ref(qf, kf, vf).reshape(B, H, S, dh)
    o_ref = o_ref.transpose(0, 2, 1, 3)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=atol)


def test_flash_vs_model_chunked_attention():
    """The Pallas kernel and the model's GSPMD chunked-flash agree."""
    from repro.models.attention import flash_attention as model_flash
    from repro.distributed.sharding import ShardCtx
    from repro.configs import get_arch, reduced
    cfg = reduced(get_arch("yi-34b"))
    rng = np.random.default_rng(1)
    B, S, H, KV, dh = 2, 128, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, KV, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, KV, dh)), jnp.float32)
    o_model = model_flash(q, k, v, cfg=cfg, ctx=ShardCtx(None))
    o_kernel = flash_attention_gqa(q, k, v)
    np.testing.assert_allclose(np.asarray(o_model), np.asarray(o_kernel),
                               atol=3e-5)
