"""Pallas TPU kernels for SD-FEEL compute hot spots.

Each kernel ships as ``<name>/{kernel.py, ops.py, ref.py}``: the Mosaic TPU
kernel (pl.pallas_call + explicit VMEM BlockSpecs), a jitted wrapper, and a
pure-jnp oracle.  ``flash_attention`` has no ``kernel.py``: its wrapper runs
the splash attention kernels that ship with JAX.  On this CPU container the
kernels are validated with ``interpret=True``; on real TPUs pass
``interpret=False`` (default).
"""
from .gossip_mix import gossip_mix, gossip_mix_tree, gossip_mix_ref
from .cluster_agg import cluster_agg, cluster_agg_tree, cluster_agg_ref
from .fused_transition import (
    fused_transition, fused_transition_tree, fused_transition_ref,
)
from .flash_attention import flash_attention, flash_attention_ref
from .fused_sgd import sgd_update, normalized_update, sgd_update_tree

__all__ = [
    "gossip_mix", "gossip_mix_tree", "gossip_mix_ref",
    "cluster_agg", "cluster_agg_tree", "cluster_agg_ref",
    "fused_transition", "fused_transition_tree", "fused_transition_ref",
    "flash_attention", "flash_attention_ref",
    "sgd_update", "normalized_update", "sgd_update_tree",
]
