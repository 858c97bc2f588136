"""Device milliseconds of one decode step: the executions of the jitted
``decode`` module in the traced window, averaged."""
import trace_reduce


def read(ctx):
    m = trace_reduce.module_stats(ctx["trace"], r"^jit_decode$")
    if m is None:
        return None
    return 1e3 * m["s"] / m["n"]
