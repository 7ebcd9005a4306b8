"""95th percentile over every request due in the window of its first
token's time minus its due time, as ``ttft_p95_ms`` reads it: the wait for
the step in flight, the queue and the prefill. A request that gave none
counts with its wait until the run ended."""
from loadgen import percentile


def read(run):
    return percentile(run["ttft_s"], 95) * 1e3 if run.get("ttft_s") else None
