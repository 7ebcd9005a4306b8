"""95th percentile of how late the load generator handed a request to
the front-end after it was due; a starved generator reads high here."""
from loadgen import percentile


def read(run):
    late = run.get("late_s")
    return percentile(late, 95) * 1e3 if late else None
