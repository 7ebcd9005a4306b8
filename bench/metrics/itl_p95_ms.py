"""95th percentile of every gap between consecutive tokens of every
request due in the window."""
from loadgen import percentile


def read(run):
    return percentile(run["itl_s"], 95) * 1e3 if run.get("itl_s") else None
