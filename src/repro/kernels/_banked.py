"""The one ``pallas_call`` layout every DIMA kernel launches through.

Grid ``(NB, B, M/BM)``: stored-row banks, then queries, then 128-row
blocks.  A single-bank matmat is ``NB=1`` and a matvec is ``B=1``, so DP
and MD, matvec and matmat, banked or not, all lower through this one
set of block specs.

Mosaic requires the last two dims of every block to be (8, 128)-aligned
or equal to the array's.  Per-query, per-bank and per-row operands
therefore carry a unit axis in front of their last dim (``(B, 1, 256)``
queries, ``(NB, 1, 2)`` ADC windows, ``(NB, B, 1, M)`` outputs), and the
leading axes are squeezed (``None``) out of the block, so the kernel
bodies see the same 2-D refs at every grid position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._interpret import resolve_interpret

BM = 128


def banked_call(kernel, d, qs, consts, noise, v_range, ep, n_out, *,
                interpret):
    """Launch ``kernel`` over d (NB, M, 256) uint8 against qs (B, 256).

    ``consts`` are 2-D chip arrays read whole at every grid step;
    ``noise`` are (NB, B, M, ...) arrays blocked 128 rows at a time;
    ``v_range`` is (NB, 2); ``ep`` (B, 4) or None appends the fused-trim
    operand.  Returns ``n_out`` outputs of shape (NB, B, M): int32 codes,
    then float32 volts (and trimmed scores)."""
    NB, M = d.shape[0], d.shape[1]
    B = qs.shape[0]
    assert M % BM == 0, M

    def whole(a):
        return pl.BlockSpec(a.shape, lambda nb, b, i: (0,) * a.ndim)

    def rows(a):
        tail = a.shape[3:]
        return pl.BlockSpec((None, None, BM) + tail,
                            lambda nb, b, i: (nb, b, i) + (0,) * len(tail))

    in_specs = ([pl.BlockSpec((None, BM, 256), lambda nb, b, i: (nb, i, 0)),
                 pl.BlockSpec((None, 1, 256), lambda nb, b, i: (b, 0, 0))]
                + [whole(c) for c in consts] + [rows(n) for n in noise]
                + [pl.BlockSpec((None, 1, 2), lambda nb, b, i: (nb, 0, 0))])
    operands = [d, qs[:, None], *consts, *noise, v_range[:, None]]
    if ep is not None:
        in_specs.append(pl.BlockSpec((None, 1, 4),
                                     lambda nb, b, i: (b, 0, 0)))
        operands.append(ep[:, None])
    out_block = pl.BlockSpec((None, None, 1, BM),
                             lambda nb, b, i: (nb, b, 0, i))
    dtypes = [jnp.int32] + [jnp.float32] * (n_out - 1)
    out = pl.pallas_call(
        kernel,
        grid=(NB, B, M // BM),
        in_specs=in_specs,
        out_specs=[out_block] * n_out,
        out_shape=[jax.ShapeDtypeStruct((NB, B, 1, M), t) for t in dtypes],
        interpret=resolve_interpret(interpret),
    )(*operands)
    return tuple(o[:, :, 0] for o in out)
