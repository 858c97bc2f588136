"""Model FLOP/s utilisation of serving: the counted model operations of
the calls in the window over the window times the chip's peak."""


def read(ctx):
    w, run = ctx["work"], ctx["run"]
    return (100.0 * w["flops"] * run["calls"]
            / (run["window_s"] * ctx["peak"]["flops_per_s"]))
