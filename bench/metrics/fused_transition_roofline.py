"""The fused Lemma-1 transition kernel's share of its roofline: the least
time its FLOPs and HBM bytes (counted from the stacked leaf shapes, C, D
and alpha) allow on the chip, over its device time in the traced window."""
from bench import counters, trace

KERNEL = ("fused_transition",)


def read(ctx):
    red = ctx["reduction"]
    ns = calls = 0
    for events in red["per_chip"].values():
        t, c = trace.matching_ns(events, red["t0"], red["t1"], KERNEL)
        ns, calls = ns + t, calls + c
    if not calls:
        return None
    flops, bytes_ = (x * ctx["rounds"] for x in ctx["costs"]["transition"])
    return counters.roofline_share(flops, bytes_, ns / 1e9, ctx["peaks"])
