"""Mean device time of the serving decode chunk program (``chunk_steps``
decode steps for every slot) per call, in the traced window."""
from bench import trace

PROGRAM = ("_chunk",)  # in the program's XLA module name


def read(ctx):
    red = ctx["reduction"]
    ns = calls = 0
    for events in red["modules"].values():
        t, c = trace.matching_ns(events, red["t0"], red["t1"], PROGRAM)
        ns, calls = ns + t, calls + c
    return ns / calls / 1e6 if calls else None
