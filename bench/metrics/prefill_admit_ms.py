"""Device time of the serving prefill and admit programs per admitted
request (one prefill call each), in the traced window."""
from bench import trace

PREFILL, ADMIT = ("_prefill",), ("_admit",)  # in the programs' XLA module names


def read(ctx):
    red = ctx["reduction"]
    ns = prefills = 0
    for events in red["modules"].values():
        t, c = trace.matching_ns(events, red["t0"], red["t1"], PREFILL)
        ns, prefills = ns + t, prefills + c
        ns += trace.matching_ns(events, red["t0"], red["t1"], ADMIT)[0]
    return ns / prefills / 1e6 if prefills else None
