"""Images classified over the window: all completed batches over the
time from the first dispatch to the last result."""


def read(run):
    return run.get("images_per_s")
