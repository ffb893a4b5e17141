"""XLA executables built or loaded from the compile cache inside the
window (JAX's backend-compile events, from every thread, tuner variants
included)."""


def read(run):
    return run.compiles
