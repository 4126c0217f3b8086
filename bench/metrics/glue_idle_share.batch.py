"""Share of the traced window in which the device is idle while the host
is in the program's ``dima.prep`` or ``dima.assemble`` spans (core/api.py
``MultiBankBackend``: the eager work before and after the one fused
launch), in %."""
from bench import program_trace as pt


def read(ctx):
    t = pt.find(ctx["summary"])
    if t is None or ctx["window_s"] <= 0 or not pt.span_s(t, pt.GLUE):
        return None
    return 100.0 * pt.idle_in_spans_s(t, pt.GLUE) / ctx["window_s"]
