"""Programs lowered inside the window (compiled, or loaded from the
persistent cache), counted through ``jax.monitoring``. Expect 0."""


def read(run):
    return run.compiles_in_window
