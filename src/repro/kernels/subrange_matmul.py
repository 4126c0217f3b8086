"""Pallas TPU kernel: sub-ranged w8a8 matmul — DIMA's MR-FR/BLP/CBLP
mapped onto the MXU (DESIGN.md §3).

Weights are stored as offset-binary uint8 = the packed (MSB, LSB) nibble
pair, i.e. the chip's column-pair layout; the kernel unpacks the two 4-b
planes at the compute site and runs two int8 MXU dots merged 16:1 —
exactly the paper's sub-ranged arithmetic, with the CBLP's charge-share
sum realized by the systolic int32 accumulator.  One HBM transaction
feeds both planes of a tile (the MR-FR "one precharge, many rows"
economics), and weight traffic is half of bf16.

Grid: (M/BM, N/BN, K/BK), K innermost; fp32/int32 accumulation in VMEM
scratch; MXU-aligned 128-multiple tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._interpret import resolve_interpret

BM, BN, BK = 128, 128, 128


def _kernel(x_ref, xs_ref, w_ref, ws_ref, o_ref, accm, accl, sumx):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        accm[...] = jnp.zeros_like(accm)
        accl[...] = jnp.zeros_like(accl)
        sumx[...] = jnp.zeros_like(sumx)

    x = x_ref[...]                                   # (BM, BK) int8
    w = w_ref[...]                                   # (BK, BN) uint8
    msb = ((w >> 4) & 0xF).astype(jnp.int8)          # the two 4-b planes
    lsb = (w & 0xF).astype(jnp.int8)
    accm[...] += jax.lax.dot(x, msb, preferred_element_type=jnp.int32)
    accl[...] += jax.lax.dot(x, lsb, preferred_element_type=jnp.int32)
    sumx[...] += jnp.sum(x.astype(jnp.int32), axis=1, keepdims=True)

    @pl.when(k == nk - 1)
    def _done():
        acc = 16 * accm[...] + accl[...] - 128 * sumx[...]   # 16:1 merge
        o_ref[...] = (acc.astype(jnp.float32) * xs_ref[...] * ws_ref[...]
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def subrange_matmul(x_q, x_scale, w_q, w_scale, *, interpret=None):
    """x_q (M,K) int8; x_scale (M,1) f32; w_q (K,N) uint8; w_scale (1,N) f32
    -> (M,N) f32.  M,K,N padded to 128 multiples by the wrapper in ops.py."""
    M, K = x_q.shape
    N = w_q.shape[1]
    assert M % BM == 0 and K % BK == 0 and N % BN == 0, (M, K, N)
    interpret = resolve_interpret(interpret)
    grid = (M // BM, N // BN, K // BK)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, k: (i, k)),
            pl.BlockSpec((BM, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((BK, BN), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, BN), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[
            _scratch((BM, BN), jnp.int32),
            _scratch((BM, BN), jnp.int32),
            _scratch((BM, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x_q, x_scale, w_q, w_scale)


def _scratch(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
