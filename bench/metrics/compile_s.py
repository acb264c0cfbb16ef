"""compile_s: seconds of XLA backend compilation during set-up, the sum
of JAX's ``/jax/core/compile/backend_compile_duration`` events.  A run
served from the persistent compilation cache reads near 0."""


def read(ctx):
    return ctx["compile_s"]
