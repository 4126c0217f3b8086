"""Share of the device's busy time spent in the operations the program
scopes ``dima.kernel`` (kernels/ops.py: the banked Pallas launch inside
the jitted ``_bank_call_dp/md``), in %."""
from bench import program_trace as pt


def read(ctx):
    t = pt.find(ctx["summary"])
    if t is None or ctx["busy_s"] <= 0:
        return None
    kernel = pt.scope_s(t, "dima.kernel")
    return 100.0 * kernel / ctx["busy_s"] if kernel > 0 else None
