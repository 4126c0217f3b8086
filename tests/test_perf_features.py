"""The perf-iteration features: int8 KV cache, fused-dequant w8, the
hlo_cost trip-count control, and sharding variants."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_arch, reduced
from repro.models import LM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_int8_kv_decode_accuracy():
    """int8 KV with per-token scales: decode logits within ~1% of fp."""
    cfg = dataclasses.replace(reduced(get_arch("yi-34b")), dtype="float32")
    m_fp = LM(cfg, RunConfig())
    m_q8 = LM(cfg, RunConfig(kv_dtype="int8"))
    params = m_fp.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab_size)
    full, _ = m_fp.forward(params, tokens=toks)
    cache = m_q8.init_cache(2, 12)
    assert cache["k"].dtype == jnp.int8 and "k_scale" in cache
    lg, cache = m_q8.prefill(params, cache, tokens=toks[:, :8])
    errs = [float(jnp.abs(lg - full[:, 7]).max())]
    for t in range(8, 12):
        lg, cache = m_q8.decode_step(params, cache, jnp.asarray(t, jnp.int32),
                                     tokens=toks[:, t:t + 1])
        errs.append(float(jnp.abs(lg - full[:, t]).max()))
    scale = float(jnp.abs(full).max())
    assert max(errs) < 0.03 * scale, (max(errs), scale)


def test_int8_kv_halves_cache_bytes():
    cfg = reduced(get_arch("yi-34b"))
    m8 = LM(cfg, RunConfig(kv_dtype="int8"))
    m16 = LM(cfg, RunConfig())
    nbytes = lambda c: sum(l.size * l.dtype.itemsize
                           for l in jax.tree_util.tree_leaves(c))
    b8 = nbytes(jax.eval_shape(lambda: m8.init_cache(4, 128)))
    b16 = nbytes(jax.eval_shape(lambda: m16.init_cache(4, 128)))
    assert b8 < 0.6 * b16, (b8, b16)


def test_hlo_cost_counts_loop_trips():
    """The control experiment from EXPERIMENTS.md §Dry-run: XLA's own
    cost_analysis counts scan bodies once; hlo_cost multiplies them."""
    from repro.launch.hlo_cost import analyze_hlo

    def make(K):
        def f(x, w):
            def body(c, _):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, None, length=K)
            return y
        return f

    sds = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    per_iter = 2 * 256 ** 3
    for K in (2, 8):
        c = jax.jit(make(K)).lower(sds, sds).compile()
        cost = c.cost_analysis()
        xla = cost["flops"]
        ours = analyze_hlo(c.as_text())["flops"]
        assert abs(xla - per_iter) / per_iter < 0.01      # XLA: once
        assert abs(ours - K * per_iter) / (K * per_iter) < 0.01  # ours: ×K


def test_fused_dequant_matches_two_plane():
    from repro.quant import quantize_weight, subrange_matmul_jnp
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (4, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.2, (32, 16)), jnp.float32)
    rec = quantize_weight(w)
    y1 = subrange_matmul_jnp(x, rec, fused_dequant=True)
    y2 = subrange_matmul_jnp(x, rec, fused_dequant=False)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)


def test_variant_cells_recorded():
    """The §Perf variant dry-runs are green on disk."""
    d = os.path.join(ROOT, "experiments", "dryrun")
    expected = [
        "yi-34b__decode_32k__pod16x16__w4kv8.json",
        "yi-34b__train_4k__pod16x16__wg_ffn.json",
        "xlstm-1.3b__train_4k__pod16x16__no_tp2.json",
    ]
    if not all(os.path.exists(os.path.join(d, fn)) for fn in expected):
        pytest.skip("variant dry-run artifacts not generated "
                    "(python -m repro.launch.dryrun)")
    for fn in expected:
        rec = json.load(open(os.path.join(d, fn)))
        assert rec["ok"], fn


def test_wg_ffn_variant_lowers_on_small_mesh(devices8):
    code = """
import jax, jax.numpy as jnp
from repro.configs import RunConfig, get_arch, reduced
from repro.data import TokenPipeline
from repro.distributed.sharding import ShardCtx, batch_shardings, param_shardings
from repro.launch.steps import make_train_step
from repro.models import LM
from repro.optim import adamw_init

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx = ShardCtx(mesh, variant="wg_ffn")
cfg = reduced(get_arch("yi-34b"))
run = RunConfig(total_steps=2, warmup_steps=1)
model = LM(cfg, run, ctx)
params = model.init(jax.random.PRNGKey(0))
pipe = TokenPipeline(cfg.vocab_size, 32, 8)
p_sh = param_shardings(model.init_shapes(), ctx)
o_sh = {"m": p_sh, "v": p_sh, "step": ctx.named(jax.sharding.PartitionSpec())}
b_sh = batch_shardings(jax.eval_shape(lambda: pipe.batch(0)), ctx)
step = jax.jit(make_train_step(model, run),
               in_shardings=(p_sh, o_sh, b_sh), out_shardings=(p_sh, o_sh, None))
params = jax.device_put(params, p_sh)
opt = jax.device_put(adamw_init(params), o_sh)
params, opt, m = step(params, opt, pipe.batch(0))
import numpy as np
assert np.isfinite(float(m["loss"]))
print("WG_FFN_OK")
"""
    assert "WG_FFN_OK" in devices8(code, timeout=560)
