"""Host milliseconds per call in the program's ``dima.launch`` span
(core/api.py ``MultiBankBackend``: the call into the jitted banked
launch, which enqueues its one execution)."""
from bench import program_trace as pt


def read(ctx):
    t = pt.find(ctx["summary"])
    if t is None or ctx["calls"] <= 0:
        return None
    ms = pt.span_s(t, ("dima.launch",)) * 1e3
    return ms / ctx["calls"] if ms > 0 else None
