"""XLA programs compiled (or loaded from the persistent cache) inside the
window, from jax.monitoring events: 0 when set-up warmed every shape."""


def read(run):
    return run.compiles_in_window
