"""Device time per forward of the leaf ops under the blocks' ``mlp`` scope
(norm, the two matmuls, activation, residual), over the ``jit_forward``
programs in the trace."""
import span_reduce


def read(run):
    trace = span_reduce.of_run(run, __file__)
    if not trace:
        return None
    return span_reduce.scope_ms(trace, "mlp",
                                span_reduce.forward_paths(run, trace))
