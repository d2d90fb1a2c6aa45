"""compilation: jit lowerings plus backend compiles between the window's
start and its end (jax.monitoring). Should be 0."""


def read(run):
    return run.window_compiles[0] + run.window_compiles[1]
