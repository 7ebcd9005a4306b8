"""Share of the traced window in which no operation ran on the device
while the host was inside the program's serving spans (``frontend.*``,
``sched.*``, ``engine.*``): the part of ``idle.serve`` the program's own
host code holds the chip for."""
import span_reduce


def read(run):
    trace = span_reduce.of_run(run, __file__)
    idle = span_reduce.idle_under_spans_s(trace) if trace else None
    return None if idle is None else 100.0 * idle / run["trace_window_s"]
