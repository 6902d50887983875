"""Positions attended under causal attention.  The operations and bytes
a model needs per token or position are its block kind's
(``blocks/<kind>.py``: ``flops_per_token``, ``attention_flops``,
``logits_flops``, ``kv_bytes_per_position``, ``decode_attention_bytes``).
"""
from __future__ import annotations


def prefill_attended(start: int, n: int) -> int:
    """Positions attended by the ``n`` tokens at ``start .. start+n-1``:
    each attends every earlier position and itself."""
    return n * start + n * (n + 1) // 2
