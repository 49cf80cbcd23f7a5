"""The fused SGD kernel's share of its roofline: the least time its HBM
bytes and FLOPs (read w and g, write w, over every stacked leaf at each
local iteration) allow on the chip, over its device time in the traced
window."""
from bench import counters, trace

KERNEL = ("sgd_update",)  # the kernel's op in the trace, named by its jitted wrapper


def read(ctx):
    red = ctx["reduction"]
    ns = calls = 0
    for events in red["per_chip"].values():
        t, c = trace.matching_ns(events, red["t0"], red["t1"], KERNEL)
        ns, calls = ns + t, calls + c
    if not calls:
        return None
    flops, bytes_ = (x * ctx["rounds"] for x in ctx["costs"]["sgd"])
    return counters.roofline_share(flops, bytes_, ns / 1e9, ctx["peaks"])
