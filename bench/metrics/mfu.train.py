"""Model FLOP utilisation of the traced training window: the forward and
backward FLOPs of every round completed in it, counted from the
configuration's shapes (attention included, recomputation not), over the
window times the chips' bf16 peak."""


def read(ctx):
    red = ctx["reduction"]
    flops = ctx["costs"]["model_flops"] * ctx["rounds"]
    return 100.0 * flops / (red["window_s"] * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
