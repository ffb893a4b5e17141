"""Set-up: process start to the first request of the window: imports,
weights drawn from the seed, compile-cache loads and compiles, the
warm-up of every prompt length, tuner registration (harness clock)."""


def read(run):
    return run.setup_s
