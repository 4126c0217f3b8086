"""Share of the device's busy time spent in the operations the program
scopes ``dima.noise`` (kernels/ops.py: the per-bank noise stacks inside
the jitted ``_bank_call_dp/md``), in %."""
from bench import program_trace as pt


def read(ctx):
    t = pt.find(ctx["summary"])
    if t is None or ctx["busy_s"] <= 0:
        return None
    noise = pt.scope_s(t, "dima.noise")
    return 100.0 * noise / ctx["busy_s"] if noise > 0 else None
