"""Sub-ranged quantization: roundtrip bounds, exact matmul identity,
LM integration, the DIMA noise model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_arch, reduced
from repro.models import LM
from repro.quant import (DimaNoiseModel, dequantize_weight, quantize_params,
                         quantize_weight, subrange_matmul_jnp)

KEY = jax.random.PRNGKey(0)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([4, 8]))
def test_quantize_roundtrip_bound(seed, bits):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(0, 0.5, (32, 16)), jnp.float32)
    rec = quantize_weight(w, bits=bits)
    wd = dequantize_weight(rec)
    step = rec["scale"][None, :]
    assert bool(jnp.all(jnp.abs(wd - w) <= 0.5 * step + 1e-7))


def test_subrange_equals_dequant_matmul():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1, (6, 48)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.2, (48, 24)), jnp.float32)
    for bits in (4, 8):
        rec = quantize_weight(w, bits=bits)
        y_sub = subrange_matmul_jnp(x, rec)
        y_ref = x @ dequantize_weight(rec)
        np.testing.assert_allclose(np.asarray(y_sub), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)


def test_expert_einsum_quant():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(0, 1, (2, 3, 4, 5, 16)), jnp.float32)  # bnecd
    w = jnp.asarray(rng.normal(0, 0.2, (4, 16, 8)), jnp.float32)      # edf
    rec = quantize_weight(w)
    y_sub = subrange_matmul_jnp(x, rec, expert_axes="bnecd,edf->bnecf")
    y_ref = jnp.einsum("bnecd,edf->bnecf", x, dequantize_weight(rec))
    np.testing.assert_allclose(np.asarray(y_sub), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", ["yi-34b", "phi3.5-moe-42b-a6.6b",
                                  "xlstm-1.3b", "recurrentgemma-2b"])
def test_quantized_lm_matches_dequantized(name):
    """w8 LM forward == forward with explicitly dequantized weights (the
    sub-range arithmetic itself is exact; only routing/fp order differs)."""
    cfg = dataclasses.replace(reduced(get_arch(name)), dtype="float32")
    m = LM(cfg)
    params = m.init(KEY)
    qparams = quantize_params(params)
    deq = jax.tree_util.tree_map(
        lambda l: l, qparams,
        is_leaf=lambda l: isinstance(l, dict) and ("q" in l or "q4" in l))
    deq = jax.tree_util.tree_map(
        lambda l: dequantize_weight(l)
        if isinstance(l, dict) and ("q" in l or "q4" in l) else l,
        qparams,
        is_leaf=lambda l: isinstance(l, dict) and ("q" in l or "q4" in l))
    toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    lg_q, _ = m.forward(qparams, tokens=toks)
    lg_d, _ = m.forward(deq, tokens=toks)
    scale = float(jnp.abs(lg_d).max()) + 1e-9
    assert float(jnp.abs(lg_q - lg_d).max()) / scale < 2e-4, name


def test_dima_noise_model_bounded():
    nm = DimaNoiseModel(sigma_rel=0.004)
    y = jnp.asarray(np.random.default_rng(3).normal(0, 1, (8, 256, 64)),
                    jnp.float32)
    y2 = nm.apply(y, jax.random.PRNGKey(1))
    rel = float(jnp.abs(y2 - y).max() / jnp.abs(y).max())
    assert 0 < rel < 0.05


def test_w4_traffic_advantage():
    """The w4 record is half the bytes of w8, quarter of bf16."""
    w = jnp.zeros((256, 256), jnp.float32)
    r8 = quantize_weight(w, bits=8)
    r4 = quantize_weight(w, bits=4)
    assert r8["q"].dtype == jnp.uint8 and r4["q4"].dtype == jnp.uint8
    # (q4 packs one nibble per byte here; the Pallas kernel reads the
    # packed plane — accounting in benchmarks/roofline uses 0.5 B/weight)
