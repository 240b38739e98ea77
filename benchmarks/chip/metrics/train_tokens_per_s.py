"""Training goodput: the tokens of every step completed in the window,
over the whole window, saves and recoveries included."""


def read(run):
    return run.tokens / run.window_s if run.steps else None
