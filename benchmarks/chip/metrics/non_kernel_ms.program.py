"""Milliseconds per ``Executor.run`` call that are not the program's
Pallas kernels on the device: the traced window less the kernels' device
time, per call. This is packing and unpacking the image, the executor's
dispatch and whatever the device waits for."""
import trace_reduce

PALLAS = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    t = trace_reduce.op_seconds(ctx["trace"], PALLAS)
    if t is None:
        return None
    return 1e3 * (ctx["trace"]["window_s"] - t) / ctx["run"]["calls"]
