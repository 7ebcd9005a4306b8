"""Host time per front-end step outside the scheduler and the engine: the
``frontend.step`` span less its ``sched.*`` and ``engine.*`` children,
median over the steps that decode in the traced window (expiry, refill,
streaming tokens to handles, retires)."""
import span_reduce


def read(run):
    trace = span_reduce.of_run(run, __file__)
    return span_reduce.frontend_host_ms(trace) if trace else None
