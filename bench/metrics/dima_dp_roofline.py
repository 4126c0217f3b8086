"""Share of the roofline that the DP kernel (kernels/dima_dp.py, launched
through kernels/_banked.py) reaches: the least time of the algorithm's
work per call (bench/work.py) times the calls of the traced window (its
``bench.call`` spans), over the summed device time of every launch of
the kernel in the trace, however many launches a call makes."""
from bench import trace_reduce, work

#: names the DP kernel's launches carry in the trace's XLA Ops line (the HLO instruction of the Pallas call)
PATTERNS = [r"^%dima_dp_bank_batch(\.\d+)? = "]


def read(ctx):
    s = ctx["summary"]
    return work.roofline_pct(trace_reduce.calls(s),
                             ctx["B"], ctx["M"], ctx["N"],
                             ctx["device_kind"],
                             trace_reduce.kernel_s(s, PATTERNS))
