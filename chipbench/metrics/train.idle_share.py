"""Share of the traced training steps in which no operation ran on the
device."""


def read(ctx):
    if ctx["kind"] != "train" or "busy_s" not in ctx:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
