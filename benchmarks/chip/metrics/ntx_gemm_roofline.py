"""Share of its roofline that the GEMM Pallas kernel reaches: the least
time of the program's counted work (the larger of operations over the
peak and bytes over the bandwidth) over the kernel's device time per
call. fp32 operations are counted against the bf16 peak, the only one the
chip publishes. The kernel is the ``tpu_custom_call`` with one 2-D result
(the program's other kernels return a tuple)."""
import peaks
import trace_reduce

KERNEL = r'^%tpu_custom_call[\w.]* = f32\[\d+,\d+\]'


def read(ctx):
    t = trace_reduce.op_seconds(ctx["trace"], KERNEL)
    if t is None:
        return None
    w = ctx["work"]
    least = peaks.least_time_s(w["flops"], w["bytes"], ctx["peak"])
    return 100.0 * least * ctx["run"]["calls"] / t
