"""Host milliseconds per call in the program's ``dima.prep`` and
``dima.assemble`` spans (core/api.py ``MultiBankBackend``: the eager work
before and after the one fused launch)."""
from bench import program_trace as pt


def read(ctx):
    t = pt.find(ctx["summary"])
    if t is None or ctx["calls"] <= 0:
        return None
    ms = pt.span_s(t, pt.GLUE) * 1e3
    return ms / ctx["calls"] if ms > 0 else None
