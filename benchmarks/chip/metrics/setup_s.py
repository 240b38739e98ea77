"""Set-up seconds: process start to the window's start, compiling,
weights, the first steps, set-up saves and the drain included."""


def read(run):
    return run.setup_s
