"""Names the SD-FEEL round's work carries in a profiler trace.

Device scopes (``jax.named_scope``) end up in every compiled op's HLO
``op_name`` metadata, and survive autodiff and rematerialisation inside
``transpose(jvp(...))`` and ``checkpoint`` paths, so a reader matches a
scope anywhere in an op's path.  Host spans (``jax.profiler.TraceAnnotation``)
sit on the profiler's clock beside the device ops.  A scope costs nothing
at run time; an inactive span about a microsecond.  The module imports
nothing, so the model and the federated core both name their work here.

Nesting, outermost first::

    LOCAL_UPDATE > FORWARD_BACKWARD > EMBED | ATTENTION | MLP | LM_HEAD
    LOCAL_UPDATE > OPTIMIZER
    TRANSITION_INTRA, TRANSITION_INTER
"""
from __future__ import annotations

PREFIX = "sdfeel."

# device scopes
LOCAL_UPDATE = "sdfeel.local_update"
TRANSITION_INTRA = "sdfeel.transition.intra"
TRANSITION_INTER = "sdfeel.transition.inter"
FORWARD_BACKWARD = "sdfeel.forward_backward"
OPTIMIZER = "sdfeel.optimizer"
EMBED = "sdfeel.embed"
ATTENTION = "sdfeel.attention"
MLP = "sdfeel.mlp"
LM_HEAD = "sdfeel.lm_head"
SCOPES = (LOCAL_UPDATE, TRANSITION_INTRA, TRANSITION_INTER, FORWARD_BACKWARD, OPTIMIZER,
          EMBED, ATTENTION, MLP, LM_HEAD)

# host spans
STAGE = "sdfeel.stage"  # a step's batches: produced, stacked, put on the device
DISPATCH = "sdfeel.dispatch"  # the compiled round step's call
SPANS = (STAGE, DISPATCH)
