"""Programs compiled, or loaded from the persistent cache, inside the
measured window (JAX's backend-compile monitoring events, which it records
for both); every shape should have been warmed in set-up, so this reads 0."""


def read(run):
    return float(run.compiles_in_window)
