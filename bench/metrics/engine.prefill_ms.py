"""Device time per run of the jitted prefill (``_prefill_impl``), from
the trace."""
from trace_reduce import program_time


def read(run):
    if not run.get("trace"):
        return None
    n, t = program_time(run["trace"], "_prefill_impl")
    return t / n * 1e3 if n else None
