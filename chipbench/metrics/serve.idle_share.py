"""Share of the traced batch in which no operation ran on the device:
one minus the union of the ``XLA Ops`` intervals over the window."""


def read(ctx):
    if ctx["kind"] != "serve" or "busy_s" not in ctx:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
