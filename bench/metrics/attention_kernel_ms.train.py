"""Device ms per round, on each chip, of the Pallas flash-attention kernel's
forward, dq and dkv passes in the traced training window.  None where no
such op ran: the model took the XLA attention path."""
from bench import trace

# the kernel's ops in the trace, named by the splash attention kernels'
# pallas_call names (``splash_mqa_fwd_residuals.N``, ``splash_mqa_dq_...``)
KERNEL = ("splash_mqa_fwd", "splash_mqa_dq", "splash_mqa_dkv")


def read(ctx):
    red = ctx["reduction"]
    ns = calls = 0
    for events in red["per_chip"].values():
        t, c = trace.matching_ns(events, red["t0"], red["t1"], KERNEL)
        ns, calls = ns + t, calls + c
    if not calls:
        return None
    return ns / 1e6 / red["chips"] / ctx["rounds"]
