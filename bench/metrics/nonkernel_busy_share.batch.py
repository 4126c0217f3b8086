"""Share of the device's busy time spent outside the DIMA kernels: noise
expansion and the glue around the fused launch (kernels/ops.py,
core/api.py), in %."""
from bench import trace_reduce

#: names the DP and MD kernels' launches carry in the trace
PATTERNS = [r"^%dima_dp_bank_batch(\.\d+)? = ",
            r"^%dima_md_bank_batch(\.\d+)? = "]


def read(ctx):
    busy = ctx["busy_s"]
    if busy <= 0:
        return None
    kernel = trace_reduce.kernel_s(ctx["summary"], PATTERNS)
    return 100.0 * (busy - kernel) / busy
