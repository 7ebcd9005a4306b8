"""Median wait from a request's due time to the start of its admit
(the engine's ``begin_admit``), through the front-end and its queue."""
from loadgen import percentile


def read(run):
    w = run.get("queue_wait_s")
    return percentile(w, 50) * 1e3 if w else None
