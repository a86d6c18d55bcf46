"""Plan build: programs JAX compiled or loaded inside the window
(``jax.monitoring`` backend-compile events); the warm-up should leave 0."""


def read(run):
    return float(run.compiles.compiles)
