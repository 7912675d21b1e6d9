"""Serving model-FLOP utilization: the forward FLOPs of the real prompt
tokens prefilled and of the decode tokens served in the run's untraced
window (2 x parameters a token plus attention over each token's
context, as the configuration's architecture module counts them), over
that window's host-clock length and the bf16 peak."""
from bench import cost


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    m, arch, flops = ctx["model"], ctx["arch"], 0.0
    for call in ctx["calls"]:
        if call[0] == "prefill":
            n = call[3]
            flops += arch.forward_flops(m, tokens=n,
                                        attended=cost.causal_pairs(n))
        else:
            active = call[3]
            flops += arch.forward_flops(m, tokens=len(active),
                                        attended=sum(active))
    return 100.0 * flops / (ctx["window_s"] * ctx["peak"].bf16_flops)
