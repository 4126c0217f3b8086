"""Distribution: sharding rules, multi-device train step, gradient
compression semantics + its collective, straggler watchdog."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.distributed.compression import init_error_state, quantize_leaf
from repro.distributed.fault_tolerance import StepWatchdog


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_param_shardings_cover_tree(devices8):
    code = """
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import get_arch, reduced
from repro.distributed.sharding import ShardCtx, param_shardings
from repro.models import LM

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx = ShardCtx(mesh)
for name in ("yi-34b", "phi3.5-moe-42b-a6.6b", "xlstm-1.3b",
             "recurrentgemma-2b"):
    cfg = reduced(get_arch(name))
    m = LM(cfg, ctx=ctx)
    shapes = m.init_shapes()
    sh = param_shardings(shapes, ctx)
    n_leaves = len(jax.tree_util.tree_leaves(shapes))
    n_sh = len(jax.tree_util.tree_leaves(
        sh, is_leaf=lambda x: x is None or hasattr(x, "spec")))
    assert n_leaves == n_sh, (name, n_leaves, n_sh)
    # every sharding's partitioned dims must divide the dimension
    flat_s = jax.tree_util.tree_leaves(sh, is_leaf=lambda x: hasattr(x, "spec"))
    flat_l = jax.tree_util.tree_leaves(shapes)
    for leaf, s in zip(flat_l, flat_s):
        for dim, part in zip(leaf.shape, tuple(s.spec) + (None,) * 9):
            if part is None: continue
            axes = (part,) if isinstance(part, str) else part
            n = 1
            for a in axes: n *= mesh.shape[a]
            assert dim % n == 0, (name, leaf.shape, s.spec)
print("SHARDINGS_OK")
"""
    assert "SHARDINGS_OK" in devices8(code)


def test_multidevice_train_step_runs(devices8):
    """A real sharded train step on an 8-device (2,4) mesh: loss finite,
    params update, gradients synchronized (all replicas identical)."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import RunConfig, get_arch, reduced
from repro.data import TokenPipeline
from repro.distributed.sharding import (ShardCtx, batch_shardings,
                                        param_shardings)
from repro.launch.steps import make_train_step
from repro.models import LM
from repro.optim import adamw_init

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx = ShardCtx(mesh)
cfg = reduced(get_arch("gemma3-1b"))
run = RunConfig(total_steps=4, warmup_steps=1)
model = LM(cfg, run, ctx)
params = model.init(jax.random.PRNGKey(0))
opt = adamw_init(params)
pipe = TokenPipeline(cfg.vocab_size, 32, 8)
p_sh = param_shardings(model.init_shapes(), ctx)
o_sh = {"m": p_sh, "v": p_sh, "step": ctx.named(jax.sharding.PartitionSpec())}
b_sh = batch_shardings(jax.eval_shape(lambda: pipe.batch(0)), ctx)
step = jax.jit(make_train_step(model, run),
               in_shardings=(p_sh, o_sh, b_sh),
               out_shardings=(p_sh, o_sh, None))
params = jax.device_put(params, p_sh)
opt = jax.device_put(opt, o_sh)
losses = []
for s in range(3):
    params, opt, m = step(params, opt, pipe.batch(s))
    losses.append(float(m["loss"]))
assert all(np.isfinite(l) for l in losses), losses
assert losses[2] < losses[0] + 0.5
print("TRAINSTEP_OK", losses)
"""
    assert "TRAINSTEP_OK" in devices8(code, timeout=560)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_quantize_leaf_error_feedback(seed):
    """EF invariant: q·scale + new_err == g + err exactly (no signal loss)."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(0, 1, (32,)), jnp.float32)
    err = jnp.asarray(rng.normal(0, 0.1, (32,)), jnp.float32)
    q, scale, new_err = quantize_leaf(g, err)
    np.testing.assert_allclose(
        np.asarray(q, np.float32) * float(scale) + np.asarray(new_err),
        np.asarray(g + err), rtol=1e-5, atol=1e-6)
    assert q.dtype == jnp.int8


def test_compressed_psum_converges(devices8):
    """int8-EF all-reduce over a 4-pod axis tracks the exact mean over
    repeated steps (error feedback catches the residual)."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import (compressed_cross_pod_psum,
                                           init_error_state)

mesh = jax.make_mesh((4, 2), ("pod", "data"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
G = jax.random.normal(jax.random.PRNGKey(0), (4, 64))  # per-pod grads

def step(g_local, err):
    (mean_g,), (new_err,) = compressed_cross_pod_psum(
        (g_local,), (err,), axis_name="pod")
    return mean_g, new_err

f = jax.shard_map(step, mesh=mesh, in_specs=(P("pod"), P("pod")),
                  out_specs=(P("pod"), P("pod")))
err = jnp.zeros((4, 64))
exact = jnp.mean(G, axis=0)
accum_c = jnp.zeros((64,))
accum_e = jnp.zeros((64,))
for t in range(20):
    mean_g, err = f(G, err)
    accum_c = accum_c + mean_g[0]
    accum_e = accum_e + exact
rel = float(jnp.linalg.norm(accum_c - accum_e) / jnp.linalg.norm(accum_e))
assert rel < 0.01, rel
one_step = float(jnp.linalg.norm(mean_g[0] - exact) / jnp.linalg.norm(exact))
print("COMPRESS_OK", rel, one_step)
"""
    assert "COMPRESS_OK" in devices8(code)


def test_watchdog_flags_stragglers():
    wd = StepWatchdog(threshold=3.0)
    for _ in range(20):
        assert not wd.observe(1.0)
    assert wd.observe(10.0)
    assert wd.straggler_steps == 1
    assert not wd.observe(1.1)
