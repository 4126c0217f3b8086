"""Calls per second of the traced window times the operations of one
call (2 * B * M * N, bench/work.py), over the chip's int8 peak, in %."""
from bench import work


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return work.mfu_pct(ctx["calls"] / ctx["window_s"], ctx["B"], ctx["M"],
                        ctx["N"], ctx["device_kind"])
