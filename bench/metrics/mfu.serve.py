"""Model FLOP utilisation of the traced serving window: the forward FLOPs
of every prompt token prefilled and every token decoded in it (counted from
the configuration's shapes, attention at the requests' mean context), over
the window times the chips' bf16 peak."""


def read(ctx):
    red, c = ctx["reduction"], ctx["costs"]
    flops = (ctx["prefill_tokens"] * c["prefill_flops_per_token"]
             + ctx["decode_tokens"] * c["decode_flops_per_token"])
    if not flops:
        return None
    return 100.0 * flops / (red["window_s"] * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
