from .ops import block_size, flash_attention
from .ref import flash_attention_ref

__all__ = ["block_size", "flash_attention", "flash_attention_ref"]
