"""Training model-FLOP utilization: 3 x forward FLOPs a step (attention
and the output head counted, the remat recompute not), times the steps
of the run's untraced window, over that window's host-clock length, the
chips and the bf16 peak."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    flops = ctx["step_flops"] * ctx["steps"]
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"]
                            * ctx["peak"].bf16_flops)
