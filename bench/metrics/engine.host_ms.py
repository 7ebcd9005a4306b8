"""Host time per decode step outside the wait for its tokens: the
``engine.decode`` span less its ``engine.decode.wait``, median over the
traced window (dispatch, the fetch's return and the commit loop)."""
import span_reduce


def read(run):
    trace = span_reduce.of_run(run, __file__)
    return span_reduce.engine_host_ms(trace) if trace else None
