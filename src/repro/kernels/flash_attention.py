"""Pallas TPU kernel: causal flash attention (single KV head — the GQA
wrapper in ops.py maps kv groups onto the batch·head grid axis).

Standard online-softmax over KV blocks with fp32 running (m, l, acc) in
VMEM scratch; the grid walks (batch·heads, q blocks) and the inner KV loop
is the innermost grid dim so accumulators persist across it.  Causality
skips fully-masked KV blocks via pl.when (real work, not masked waste).
Oracle: kernels/ref.py::flash_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._interpret import resolve_interpret

BQ, BK = 128, 128
NEG = -0.7 * float(np.finfo(np.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i, *, scale):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG)
        l_i[...] = jnp.zeros_like(l_i)

    # causal block skip: kv block strictly after the q block does nothing
    @pl.when(kb * BK <= qb * BQ + BQ - 1)
    def _work():
        q = q_ref[0]                                  # (BQ, dh)
        k = k_ref[0]                                  # (BK, dh)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        qpos = qb * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
        kpos = kb * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
        s = jnp.where(kpos <= qpos, s, NEG)

        m_new = jnp.maximum(m_i[...], s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_i[...] - m_new)
        l_i[...] = l_i[...] * corr + p.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_i[...] = m_new

    @pl.when(kb == nk - 1)
    def _done():
        o_ref[0] = (acc[...] / jnp.maximum(l_i[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_attention(q, k, v, *, interpret=None):
    """q,k,v: (B, S, dh), causal. B folds batch×heads. S % 128 == 0."""
    B, S, dh = q.shape
    assert S % BQ == 0 and S % BK == 0, S
    interpret = resolve_interpret(interpret)
    scale = 1.0 / np.sqrt(dh)
    grid = (B, S // BQ, S // BK)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BQ, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, BK, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ, dh), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
