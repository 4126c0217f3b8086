"""The work of a DIMA call, counted from its shapes, and the chip's peaks.

A call answers ``B`` queries of ``N`` 8-b words against ``M`` stored rows
of ``N`` 8-b words.  Its algorithm needs, whatever implements it:

* operations ``2 * B * M * N``: a multiply and an add per word pair in DP
  mode, a difference and an add per word pair in MD mode;
* bytes: the stored ``uint8`` array once, the ``uint8`` queries, and one
  8-b ADC code per (query, row).

The analog noise that a simulator streams or draws counts as neither:
how it is made is the implementation's choice, so a share of the
roofline computed from these counts can never pass 100%.
"""
from __future__ import annotations

#: Published peaks of one chip, keyed by JAX's ``device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def ops(B: int, M: int, N: int) -> int:
    """Operations of one call of B queries against M rows of N words."""
    return 2 * B * M * N


def bytes_moved(B: int, M: int, N: int) -> int:
    """Least bytes one call must move: stored rows, queries, 8-b codes."""
    return M * N + B * N + B * M


def min_seconds(B: int, M: int, N: int, device_kind: str) -> float:
    """Least time the chip could take for one call: the larger of the
    operation bound (int8 peak) and the memory bound (HBM peak)."""
    pk = peaks(device_kind)
    return max(ops(B, M, N) / pk["int8_ops"],
               bytes_moved(B, M, N) / pk["hbm_bytes_per_s"])


def roofline_pct(n_calls: int, B: int, M: int, N: int, device_kind: str,
                 kernel_s: float):
    """Share of the roofline, in %, that ``n_calls`` calls reached in
    ``kernel_s`` seconds of kernel time; None where nothing was timed."""
    if n_calls <= 0 or kernel_s <= 0:
        return None
    return 100.0 * n_calls * min_seconds(B, M, N, device_kind) / kernel_s


def mfu_pct(calls_per_s: float, B: int, M: int, N: int, device_kind: str):
    """Calls per second times the operations of one call, over the int8
    peak, in %."""
    if calls_per_s <= 0:
        return None
    return (100.0 * calls_per_s * ops(B, M, N)
            / peaks(device_kind)["int8_ops"])
