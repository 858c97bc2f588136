"""Share of its roofline that the MoE layers' grouped GEMMs reach: the
least time of their counted work (the larger of operations over the peak
and bytes over the bandwidth; ``gmm_work`` of the cell's system, from the
program's own count of the assignments routed to the held experts) over
the grouped GEMM kernels' device time in the window. The kernels are
found by their HLO text: ``jax.lax.ragged_dot`` compiles on the TPU to
``ragged-dot-*`` Mosaic kernels beside one ``ragged-dot-metadata`` kernel
per call, which is left out."""
import peaks
import trace_reduce

KERNEL = r"^%ragged-dot-(?!metadata)"


def read(ctx):
    work = ctx["run"].get("gmm_work")
    t = trace_reduce.op_seconds(ctx["trace"], KERNEL)
    if work is None or t is None:
        return None
    return 100.0 * peaks.least_time_s(work["flops"], work["bytes"],
                                      ctx["peak"]) / t
