"""The whole program's share of the chip: the least time of its counted
work (the larger of operations over the peak and bytes over the
bandwidth) over the measured time per ``Executor.run`` call."""
import peaks


def read(ctx):
    w, run = ctx["work"], ctx["run"]
    least = peaks.least_time_s(w["flops"], w["bytes"], ctx["peak"])
    return 100.0 * least * run["calls"] / run["window_s"]
