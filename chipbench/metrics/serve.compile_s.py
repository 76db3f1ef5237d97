"""Seconds ``serve()`` spent tracing, lowering and loading its programs
inside the window (JAX's ``/jax/core/compile/*`` events): the cost of its
building new ``jax.jit`` closures on every call."""


def read(ctx):
    return ctx["compile_s"] if ctx["kind"] == "serve" else None
