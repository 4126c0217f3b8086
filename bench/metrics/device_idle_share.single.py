"""Share of the traced window in which no operation ran on the device,
in %: one minus the union of the device's operation intervals over the
window's host-clock length."""


def read(ctx):
    if ctx["window_s"] <= 0 or ctx["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
