"""Set-up seconds: weights, inputs, warm-up and (in a cold checkout)
compilation, up to the opening of the window."""


def read(run):
    return run["setup_s"]
