"""Program executions on the device per call in the traced window: the
fused launch plus every eager op around it (core/api.py
``MultiBankBackend``, the kernels/ops.py wrappers, the decision)."""
from bench import trace_reduce


def read(ctx):
    n = trace_reduce.executions(ctx["summary"])
    if n <= 0 or ctx["calls"] <= 0:
        return None
    return n / ctx["calls"]
